"""What several metric readers share: finding the batched placement scan in
a reduced trace, and the phase unions per 1,000 placements."""
from __future__ import annotations

# The names XLA prints today for the program the batcher dispatches
# (engine._build_batched_scan jits a function called ``body``). Stable names
# are the tracing issue's; until then a program matches by these prefixes.
SCAN_PROGRAM_PREFIXES = ("jit_body", "jit(body)")
RETRY_STEPS = 16


def scan_programs(ctx) -> list:
    t = ctx.get("trace")
    if not t:
        return []
    return [rec for name, rec in t["programs"].items()
            if name.startswith(SCAN_PROGRAM_PREFIXES)]


def scan_stretch(ctx):
    """The stretch of the traced slice between the first and the last
    finished dispatch the sampler saw: the evaluations dispatched in it, as
    (nodes, placements, stanzas), and the scan programs' device seconds
    inside it. None where there is nothing to read.

    The batcher counts evaluations and dispatches, not placements. A
    dispatch of two or more evaluations is a cohort of first passes, each
    of its job's whole count. A dispatch of one is a first pass or the
    few-placement tail of a partially committed plan: the run's counters
    say how many tails the device served (pipeline redispatches and small
    evals sent to the device), and that share of the run's lone dispatches
    is counted as RETRY_STEPS steps each, the rest as first passes. The
    sizes and the stanza share of the first passes are those of the jobs
    that committed in the stretch."""
    t, series = ctx.get("trace"), ctx.get("sampler")
    runs = scan_programs(ctx)
    if not t or not series or not runs:
        return None
    inside = [(ts, d, e) for ts, d, e in series
              if ctx["profile_t0"] <= ts <= ctx["profile_t1"]]
    if len(inside) < 2:
        return None
    a_s, b_s = inside[0][0], inside[-1][0]
    cohort = lone = 0
    for (_t0, d0, e0), (_t1, d1, e1) in zip(inside, inside[1:]):
        if d1 - d0 == 1 and e1 - e0 >= 2:
            cohort += e1 - e0
        elif d1 - d0 == 1:
            lone += 1
        else:                      # the sampler missed a step between two
            cohort += max(0, (e1 - e0) - (d1 - d0))
            lone += d1 - d0
    a_ns, b_ns = t["to_trace_ns"](a_s), t["to_trace_ns"](b_s)
    device_s = sum((min(end, b_ns) - max(start, a_ns)) / 1e9
                   for rec in runs for start, end in rec["events"]
                   if end > a_ns and start < b_ns)
    recs = [r for r in ctx["window"]["records"] if r.get("t_commit") is not None]
    near = [r for r in recs if a_s <= r["t_commit"] <= b_s] or recs
    if not near or cohort + lone <= 0:
        return None
    mean_p = sum(r["count"] for r in near) / len(near)
    stanza = sum(1 for r in near if r["spec"].get("spread")
                 or r["spec"].get("affinity")) / len(near)
    lone_run = sum(1 for (_a, d0, e0), (_b, d1, e1) in zip(series, series[1:])
                   if d1 - d0 == 1 and e1 - e0 == 1)
    c = ctx["counters"]
    tails = (c.get("nomad.pipeline.redispatch", 0.0)
             + c.get("nomad.tpu_engine.small_eval_device_retry", 0.0))
    lone_tails = int(round(lone * min(1.0, tails / max(1, lone_run))))
    first = cohort + lone - lone_tails
    n_st = int(round(first * stanza))
    n = ctx["n_nodes"]
    evals = ([(n, mean_p, True)] * n_st + [(n, mean_p, False)] * (first - n_st)
             + [(n, RETRY_STEPS, False)] * lone_tails)
    return {"evals": evals, "device_s": device_s, "cohort": cohort, "lone": lone}


def device_idle_pct(ctx):
    """1 - union of the device's operation intervals over the traced slice;
    nothing where no operation was traced."""
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def phase_seconds_per_kp(ctx, names):
    shares = ctx.get("phases")
    w = ctx["window"]
    placed = w["placed1"] - w["placed0"]
    if not shares or placed <= 0:
        return None
    found = [shares[n] for n in names if n in shares]
    if not found:
        return None
    return sum(found) / (placed / 1000.0)
