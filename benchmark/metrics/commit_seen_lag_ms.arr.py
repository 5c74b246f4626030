"""Served path: what the harness's observer adds to the end-to-end number:
its ``t_commit`` of a job less the program's own ``commit_t`` of the record
that completed the job (the last raft apply that committed a plan of it,
joined on ``job_id``). Median over the window's jobs.
layer: served path; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    last: dict = {}
    for r in ctx.get("lifecycle") or []:
        if r.get("commit_t") is not None and r.get("job_id"):
            last[r["job_id"]] = max(last.get(r["job_id"], r["commit_t"]),
                                    r["commit_t"])
    values = [(job["t_commit"] - last[job["id"]]) * 1000.0
              for job in ctx["window"]["records"]
              if job.get("t_commit") is not None and job["id"] in last]
    spans.log("commit_seen_lag_ms.arr", len(values))
    return spans.median(values)
