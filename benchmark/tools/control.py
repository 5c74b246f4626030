#!/usr/bin/env python3
"""The control of the comparison, at a cell's own size, on several seeds.

    python benchmark/tools/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 10 [--rate R]

One process; each seed is a whole run of the cell with a short window. After
the run's own comparison (the program's reading, float64) the same replayed
jobs are followed once more with the plain reference changed as
compare.control_variants says (bfloat16, float32, the ring's start ignored,
the spread ignored), put in the program's place. One JSON line per seed: the
program's two numbers (mismatches, held to 0; widest score gap, the lower
reading) and each control's (the upper readings). Not the benchmark's command.
Refuses any platform but ``tpu``, as run.py does.
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


def readings(manifest: dict, repo: str, workload: str, seeds: list,
             seconds: float, device: dict, mix_changes: dict = None) -> list:
    rows = []
    for seed in seeds:
        result = run.run_cell(manifest, repo, workload, seed, seconds, False,
                              device, mix_changes=mix_changes,
                              with_control=True)
        rows.append({
            "seed": seed, "correct": result["correct"],
            "program_mismatches": result["checks"][
                "placements_mismatching_reference"]["value"],
            "program_widest_score_gap": result["checks"][
                "widest_score_gap"]["value"],
            "placements_compared": result["checks"][
                "placements_compared"]["value"],
            "evals_by_host_stack_pct": result["checks"][
                "evals_by_host_stack_pct"]["value"],
            "due": result["attempted"], "failed": result["failed"],
            "control": result["control"],
            "checks_failing": [k for k, c in result["checks"].items()
                               if not c["ok"]],
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rate", type=float, default=0.0)
    args = ap.parse_args(argv)
    repo = os.path.dirname(BENCH)
    manifest = run.load_manifest(repo)
    cell, _ = run.find_cell(manifest, args.workload)
    system.import_program()
    device = system.require_tpu(int(cell["chips"]))
    readings(manifest, repo, args.workload,
             [int(s) for s in args.seeds.split(",")], args.seconds, device,
             {"rate_per_s": args.rate} if args.rate else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
