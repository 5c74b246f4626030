#!/usr/bin/env python3
"""Find a cell's knee: the cell at each of a list of rates or client counts.

    python benchmark/tools/sweep.py --workload <cell> --key rate_per_s \\
        --values 20,40,80 --seconds 15 --seed 7 [--drain 20]

One process; each value is a whole run of the cell (its own server, the same
warm-up) with that key of its traffic file overridden, so only the first
pays the cache loads. One JSON line per value on stdout: jobs due, failed,
the backlog (jobs due and not committed) at half and at the end of the
window, the end-to-end metrics, how late the generator ran. It stops after
the first value at which a job is not committed when the drain ends: above
the knee the program thrashes for minutes. The highest rate whose backlog
does not grow from half to end, and whose jobs all commit, is the sustained
rate; the cell's traffic file then states 0.8 of it as a number. Not the benchmark's
command: BENCHMARK.json never names this file. Refuses any platform but
``tpu``, as run.py does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import system  # noqa: E402


def sweep(manifest: dict, repo: str, workload: str, key: str, values: list,
          seed: int, seconds: float, drain_s: float, device: dict) -> list:
    rows = []
    for i, value in enumerate(values):
        result = run.run_cell(
            manifest, repo, workload, seed + i, seconds, False, device,
            mix_changes={key: value, "drain_s": drain_s})
        m = result["metrics"]
        rows.append({
            key: value, "seed": seed + i,
            "due": result["attempted"], "failed": result["failed"],
            "backlog_half_end": result["notes"]["backlog_half_end"],
            "late_commits": result["notes"]["late_commits"],
            "generator_late_p95_ms": result["notes"]["generator_late_p95_ms"],
            **{k: v["value"] for k, v in m.items()},
            "correct": result["correct"],
            "checks_failing": [k for k, c in result["checks"].items()
                               if not c["ok"]],
        })
        print(json.dumps(rows[-1]), flush=True)
        if result["failed"]:
            break
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", default="rate_per_s")
    ap.add_argument("--values", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--drain", type=float, default=20.0)
    args = ap.parse_args(argv)
    repo = os.path.dirname(BENCH)
    manifest = run.load_manifest(repo)
    cell, _ = run.find_cell(manifest, args.workload)
    system.import_program()
    device = system.require_tpu(int(cell["chips"]))
    values = [float(v) if "." in v or args.key == "rate_per_s" else int(v)
              for v in args.values.split(",")]
    sweep(manifest, repo, args.workload, args.key, values, args.seed,
          args.seconds, args.drain, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
