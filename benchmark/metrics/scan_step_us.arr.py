"""Kernels: device time of ONE step of the scan's loop: the scan programs'
device seconds in the traced slice over the ``n_steps`` of the slice's
dispatch records (the bound the device's loop ran: the wave's longest eval).
A record is paired with the run that overlaps its [t_stack, t_host] most,
so every record of the slice counts (harness/spans.py:join keeps only runs
that lie wholly inside [t_stack, t_ready], which the clock tie's error
denies most short runs). Comparable between a 1x64 run of 50 steps and a
64x1024 run of 1,000. A program whose records carry no ``n_steps`` reports
nothing. layer: kernels; moves submit_commit_p50_ms."""
import bisect

from harness import scan, spans


def read(ctx):
    t = ctx.get("trace")
    runs = sorted(ev for rec in scan.scan_programs(ctx) for ev in rec["events"])
    if not t or not runs or "to_trace_ns" not in t:
        return None
    to_ns = t["to_trace_ns"]
    ends = [b for _a, b in runs]
    paired = {}
    for d in spans.dispatches(ctx):
        if not (d.get("n_steps") and d.get("t_host") is not None
                and d["t_stack"] >= ctx["profile_t0"]
                and d["t_host"] <= ctx["profile_t1"]):
            continue
        lo, hi = to_ns(d["t_stack"]), to_ns(d["t_host"])
        best, k = None, bisect.bisect_right(ends, lo)
        while k < len(runs) and runs[k][0] < hi:
            shared = min(hi, runs[k][1]) - max(lo, runs[k][0])
            if shared > 0 and (best is None or shared > best[0]):
                best = (shared, k)
            k += 1
        if best is not None and best[1] not in paired:
            paired[best[1]] = d["n_steps"]
    spans.log("scan_step_us.arr", len(paired))
    steps = sum(paired.values())
    if not steps:
        return None
    return sum(runs[k][1] - runs[k][0] for k in paired) / 1e3 / steps
