#!/usr/bin/env python3
"""One traced run of a cell, and what the program's span records say of it.

    python benchmark/tools/spans_report.py --workload <cell> --seed <n> \\
        [--seconds 51] [--out chiprun_out/spans_report.json]

Runs the cell as ``run.py --trace 1`` does, then reads trace/lifecycle.py's
eval and dispatch records and prints one JSON object: the median eval's
path (per stage the median self time: the seconds of enqueue -> commit in
which the stage was the innermost open interval), the intervals of the
path no stage covers, by the stamps they lie between, the parts of a
dispatch, how the gathers closed, the traffic's longest arrival gap beside
the named idle gaps, and the flight recorder's duty cycle over the window.
It also gives the traced run's own median of due -> commit, whole and by
whether the profiler was running when a job was due: what tracing costs
when it is on. PERF.md's section 5 tables are made from it. Not the benchmark's command:
BENCHMARK.json never names this file. Refuses any platform but ``tpu``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import loadgen, spans, system, traffic  # noqa: E402


def _summary(values: list) -> dict:
    values = sorted(values)
    if not values:
        return {"n": 0}
    return {"n": len(values), "median": statistics.median(values),
            "mean": sum(values) / len(values),
            "p95": loadgen.percentile(values, 0.95)}


def _uncovered(rec: dict) -> list:
    """[(what ended before -> what starts after, seconds)] for each stretch
    of enqueue -> commit that no stage or stamped wait covers."""
    lo, hi = rec["enqueue_t"], rec["commit_t"]
    out, at, before = [], lo, "enqueue"
    for a, b, n in sorted(spans.named_intervals(rec)):
        if a > at and a <= hi:
            out.append((f"{before} -> {n}", a - at))
        if b > at:
            at, before = b, n
    if hi > at:
        out.append((f"{before} -> commit", hi - at))
    return out


def report(manifest: dict, repo: str, workload: str, seed: int,
           seconds: float, device: dict) -> dict:
    from nomad_tpu.trace import lifecycle

    seen: dict = {}
    real_window = loadgen.run_window

    def run_window(server, *args, **kw):
        o0 = server.flight.overhead()
        window = real_window(server, *args, **kw)
        o1 = server.flight.overhead()
        tick_s = (o1["ticks"] * o1["tick_ms_avg"] - o0["ticks"] * o0["tick_ms_avg"]) / 1e3
        seen["flight"] = {
            "interval_s": server.flight.interval_s,
            "ticks": o1["ticks"] - o0["ticks"], "tick_s": tick_s,
            "over_s": window["t_drained"] - window["t0"],
            "duty_cycle": tick_s / (window["t_drained"] - window["t0"]),
            "tick_ms_max_since_start": o1["tick_ms_max"]}
        seen["window"] = window
        return window

    loadgen.run_window = run_window
    try:
        result = run.run_cell(manifest, repo, workload, seed, seconds, True,
                              device)
    finally:
        loadgen.run_window = real_window
    window = seen["window"]
    ctx = {"window": window, "lifecycle": lifecycle.raw_records()}
    evals = spans.evals(ctx)
    dispatches = spans.dispatches(ctx)

    stages: dict = {}
    gaps: dict = {}
    for rec in evals:
        for name, s in spans.self_times(rec).items():
            stages.setdefault(name, []).append(s * 1e3)
        for label, s in _uncovered(rec):
            gaps.setdefault(label, []).append(s * 1e3)
    # the device wait of an eval, cut at its dispatch's stamps
    by_wave = {d["wave"]: d for d in dispatches}
    cuts = (("queued_and_gather", None, "t_start"), ("pad_stack", "t_start", "t_stack"),
            ("h2d_launch", "t_stack", "t_called"), ("kernel_wait", "t_called", "t_ready"),
            ("d2h", "t_ready", "t_host"), ("wake", "t_host", None))
    wait_parts: dict = {name: [] for name, _a, _b in cuts}
    for rec in evals:
        for name, a, b in rec["stages"]:
            d = by_wave.get((rec.get("waves") or [None])[0])
            if name != "device_wait" or d is None or not a <= d["t_host"] <= b:
                continue
            for part, t0, t1 in cuts:
                wait_parts[part].append(
                    ((d[t1] if t1 else b) - (d[t0] if t0 else a)) * 1e3)
    n = max(1, len(evals))
    path = {name: dict(_summary(v), per_eval_mean=sum(v) / n)
            for name, v in sorted(stages.items(),
                                  key=lambda kv: -sum(kv[1]))}
    parts = {
        "gather": ("t_first_enqueue", "t_start"),
        "pad_stack": ("t_start", "t_stack"),
        "h2d_launch": ("t_stack", "t_called"),
        "kernel_wait": ("t_called", "t_ready"),
        "d2h": ("t_ready", "t_host"),
        "hand_back": ("t_host", "t_handed"),
        "t_start_to_t_host": ("t_start", "t_host"),
    }
    cell, _cfg = run.find_cell(manifest, workload)
    mix = traffic.load(traffic.find(os.path.join(repo, manifest["paths"][0]),
                                    cell["traffic"]))
    due = traffic.due_times(mix, seed, seconds) if mix["loop"] == "open" else []
    # the median by when a job was due: before, inside and after the
    # slice in which the profiler ran (run.py puts it in the window's middle)
    length = min(float(mix.get("trace_s", seconds)), seconds)
    lo = window["t0"] + (seconds - length) / 2
    thirds: dict = {"before_profiler": [], "profiler_on": [], "after_profiler": []}
    for job in window["records"]:
        if job["t_commit"] is not None:
            key = ("before_profiler" if job["t_due"] < lo else
                   "profiler_on" if job["t_due"] < lo + length else
                   "after_profiler")
            thirds[key].append((job["t_commit"] - job["t_due"]) * 1e3)
    closed_by: dict = {}
    fill: dict = {}
    for d in dispatches:
        closed_by[d["closed_by"]] = closed_by.get(d["closed_by"], 0) + 1
        key = f"{d['b']} of {d['b_pad']} x {d['p_pad']}"
        fill[key] = fill.get(key, 0) + 1
    return {
        "workload": workload, "seed": seed, "correct": result["correct"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "device": result["device"],
        "breakdown": result.get("breakdown"),
        "longest_arrival_gap_s": max(
            (b - a for a, b in zip(due, due[1:])), default=None),
        "evals": len(evals),
        "enqueue_to_commit_ms": _summary(
            [(r["commit_t"] - r["enqueue_t"]) * 1e3 for r in evals]),
        "covered_share": _summary(
            [s for s in map(spans.covered_share, evals) if s is not None]),
        "submit_commit_p50_ms_traced": loadgen.percentile(
            loadgen.latencies_ms(window), 0.50),
        "due_to_commit_ms_by_profiler": {k: _summary(v) for k, v in thirds.items()},
        "path_self_ms": path,
        "device_wait_parts_ms": {k: _summary(v) for k, v in wait_parts.items()},
        "uncovered_ms": {k: dict(_summary(v), per_eval_mean=sum(v) / n)
                         for k, v in sorted(gaps.items(),
                                            key=lambda kv: -sum(kv[1]))},
        "dispatches": len(dispatches),
        "dispatch_parts_ms": {
            name: _summary([(d[b] - d[a]) * 1e3 for d in dispatches])
            for name, (a, b) in parts.items()},
        "closed_by": closed_by, "fill": fill,
        "flight_recorder": seen["flight"],
        "checks_failing": [k for k, c in result["checks"].items()
                           if not c["ok"]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    repo = os.path.dirname(BENCH)
    manifest = run.load_manifest(repo)
    cell, _ = run.find_cell(manifest, args.workload)
    system.import_program()
    device = system.require_tpu(int(cell["chips"]))
    out = report(manifest, repo, args.workload, args.seed, args.seconds, device)
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
