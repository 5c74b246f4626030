"""Front end: mean wall of Server.register_job, a harness span around the
call. layer: front end; moves submit_commit_p50_ms."""


def read(ctx):
    spans = [(r["t_registered"] - r["t_sent"]) * 1000.0
             for r in ctx["window"]["records"] if "t_registered" in r]
    return sum(spans) / len(spans) if spans else None
