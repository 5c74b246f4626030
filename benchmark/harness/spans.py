"""What the readers of the program's span records share.

The program keeps, on the clock the harness's mark ties to the profiler's
(``time.perf_counter``): one record per delivery of an evaluation with its
stamps and ``stages`` (``ctx["lifecycle"]``, trace/lifecycle.py's
``raw_records``), and one record per device dispatch with the stamps
``t_first_enqueue`` ... ``t_handed`` (``lifecycle.dispatch_records()``).
Here: which of them belong to the window and to the traced slice, the
join of a dispatch record to the run of its scan program on the device,
and the arithmetic over an evaluation's path.

A program that keeps no such record (a parent commit) gives every reader
nothing to read: each returns None and raises nothing. How many samples a
reading was taken over goes to the run's log, never into the number.
"""
from __future__ import annotations

import bisect
import statistics
import sys

from . import scan


def log(metric: str, samples: int, note: str = "") -> None:
    print(f"[bench spans] {metric}: {samples} samples{' ' + note if note else ''}",
          file=sys.stderr, flush=True)


def median(values: list):
    return statistics.median(values) if values else None


def dispatches(ctx) -> list:
    """The batcher's dispatch records that started inside the window,
    oldest first; empty where the program keeps none. Read once a run."""
    if "_span_dispatches" not in ctx:
        found = []
        try:
            from nomad_tpu.trace import lifecycle

            records = lifecycle.dispatch_records()
        except (ImportError, AttributeError):
            records = []
        w = ctx["window"]
        for d in records:
            if (d.get("source") == "batcher" and d.get("t_start") is not None
                    and w["t0"] <= d["t_start"] <= w["t1"]):
                found.append(d)
        ctx["_span_dispatches"] = found
    return ctx["_span_dispatches"]


def evals(ctx) -> list:
    """The window's evaluation records that carry stages: enqueued inside
    the window, acknowledged, with the commit's own stamp."""
    w = ctx["window"]
    return [r for r in ctx.get("lifecycle") or []
            if r.get("stages") and r.get("outcome") == "ack"
            and r.get("commit_t") is not None
            and r.get("enqueue_t") is not None
            and w["t0"] <= r["enqueue_t"] <= w["t1"]]


def join(ctx):
    """Each dispatch record of the traced slice beside the run of the scan
    program that lies inside its [t_stack, t_ready]: {"joined": [(record,
    seconds from t_stack to the run's start, the run's seconds, seconds
    from its end to t_host)], "slice": records in the slice, "none_inside"
    and "several_inside": records left out and counted}. None where there
    is no trace or no record. The differences are taken on the trace's
    clock in ns, where the two stamps of a pair are close together."""
    t = ctx.get("trace")
    runs = sorted(ev for rec in scan.scan_programs(ctx) for ev in rec["events"])
    if not t or not runs or "to_trace_ns" not in t:
        return None
    inside = [d for d in dispatches(ctx)
              if d.get("t_ready") is not None
              and d["t_stack"] >= ctx["profile_t0"]
              and d["t_host"] <= ctx["profile_t1"]]
    if not inside:
        return None
    starts = [a for a, _b in runs]
    to_ns = t["to_trace_ns"]
    out = {"joined": [], "slice": len(inside), "none_inside": 0,
           "several_inside": 0}
    for d in inside:
        lo, hi = to_ns(d["t_stack"]), to_ns(d["t_ready"])
        found = []
        k = bisect.bisect_left(starts, lo)
        while k < len(runs) and runs[k][0] <= hi:
            if runs[k][1] <= hi:
                found.append(runs[k])
            k += 1
        if len(found) == 1:
            start, end = found[0]
            out["joined"].append((d, (start - lo) / 1e9, (end - start) / 1e9,
                                  (to_ns(d["t_host"]) - end) / 1e9))
        elif found:
            out["several_inside"] += 1
        else:
            out["none_inside"] += 1
    return out


def join_note(j: dict) -> str:
    """For the log: how the join went, and the four parts of a dispatch as
    means over the joined records beside the host's own t_start -> t_host."""
    note = (f"of {j['slice']} dispatch records in the traced slice "
            f"({j['none_inside']} with no scan run inside [t_stack, t_ready], "
            f"{j['several_inside']} with several)")
    n = len(j["joined"])
    if n:
        pad = sum(d["t_stack"] - d["t_start"] for d, *_ in j["joined"]) / n
        pre, kernel, post = (sum(row[i] for row in j["joined"]) / n
                             for i in (1, 2, 3))
        whole = sum(d["t_host"] - d["t_start"] for d, *_ in j["joined"]) / n
        note += (f"; means in ms: pad_stack {pad * 1e3:.3f} + pre-kernel "
                 f"{pre * 1e3:.3f} + kernel {kernel * 1e3:.3f} + post-kernel "
                 f"{post * 1e3:.3f} = {(pad + pre + kernel + post) * 1e3:.3f} "
                 f"against t_start -> t_host {whole * 1e3:.3f}")
    return note


def union_seconds(intervals: list, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, at = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b > at:
            total += b - max(a, at)
            at = b
    return total


def named_intervals(rec: dict) -> list:
    """[(start, end, name)] of everything on an eval record that names a
    stretch of its path: its stages, the broker wait (enqueue -> dequeue)
    and the plan-queue wait (submit -> the applier's evaluate)."""
    out = [(a, b, name) for name, a, b in rec["stages"]]
    if rec.get("dequeue_t") is not None:
        out.append((rec["enqueue_t"], rec["dequeue_t"], "broker_wait"))
    if rec.get("submit_t") is not None and rec.get("evaluate_start_t") is not None:
        out.append((rec["submit_t"], rec["evaluate_start_t"], "plan_queue_wait"))
    return out


def covered_share(rec: dict):
    """Share of enqueue -> commit that the record's named intervals
    cover; None where the path has no length."""
    lo, hi = rec["enqueue_t"], rec["commit_t"]
    if hi <= lo:
        return None
    spans = [(a, b) for a, b, _name in named_intervals(rec)]
    return union_seconds(spans, lo, hi) / (hi - lo)


def self_times(rec: dict) -> dict:
    """{name: seconds of enqueue -> commit in which it was the innermost
    open interval of the record}: the stages, ``broker_wait`` and
    ``plan_queue_wait``, and ``unnamed`` for what none covers. The later a
    stage started, the further in it lies."""
    lo, hi = rec["enqueue_t"], rec["commit_t"]
    spans = [(max(a, lo), min(b, hi), n) for a, b, n in named_intervals(rec)
             if b > lo and a < hi]
    cuts = sorted({lo, hi} | {a for a, _b, _n in spans} | {b for _a, b, _n in spans})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(s0, n) for s0, s1, n in spans if s0 <= a and s1 >= b]
        name = max(open_)[1] if open_ else "unnamed"
        out[name] = out.get(name, 0.0) + (b - a)
    return out
