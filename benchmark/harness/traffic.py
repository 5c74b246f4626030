"""One general generator for every traffic mix.

A mix is a data file ``benchmark/traffic/<name>.json``:

    {"loop": "closed", "clients": 64, "drain_s": 30, "sample_jobs": 6,
     "limits": {"widest_score_gap": 1e-5, "evals_by_host_stack_pct": 0}}
    {"loop": "open", "rate_per_s": 5.0, "drain_s": 30, "sample_jobs": 6,
     "limits": {...}}

An open loop's arrivals are Poisson at ``rate_per_s``. ``drain_s`` is how
long after the window a job that was due inside it may still commit before
it counts as failed. ``sample_jobs`` is how many of the finished jobs the
comparison replays through the plain reference. ``limits`` are the two
limits of the comparison that are set from a cell's own readings (PERF.md
has the readings); every other limit is 0. ``trace_s``, where given, is
how much of the window a traced run profiles (default: all of it).
Imports nothing of the program.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

BLOCK = 64


def load(path: str, changes: dict = None) -> dict:
    with open(path) as f:
        mix = json.load(f)
    mix.update(changes or {})
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    if mix["loop"] == "closed" and int(mix.get("clients", 0)) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if mix["loop"] == "open" and float(mix.get("rate_per_s", 0)) <= 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    for key in ("drain_s", "sample_jobs", "limits"):
        if key not in mix:
            raise ValueError(f"{path}: {key} is missing")
    for key in ("widest_score_gap", "evals_by_host_stack_pct"):
        if key not in mix["limits"]:
            raise ValueError(f"{path}: limits.{key} is missing")
    return mix


def find(root: str, name: str) -> str:
    path = os.path.join(root, "traffic", name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no traffic file {path}")
    return path


def due_times(mix: dict, seed: int, seconds: float) -> list:
    """Offsets from the window's start at which an open loop's jobs are due.

    Poisson arrivals at a fixed rate: the gaps of a block of 64 are the 64
    mid-quantiles of the exponential law, in an order drawn from the seed.
    Every seed so offers the same gaps, and with them the same number of
    jobs to within the last block, in another order."""
    rate = float(mix["rate_per_s"])
    gaps = [-math.log(1.0 - (i + 0.5) / BLOCK) / rate for i in range(BLOCK)]
    # the mid-quantiles' mean is a little under 1/rate: put it right
    scale = (1.0 / rate) / (sum(gaps) / BLOCK)
    gaps = [g * scale for g in gaps]
    rng = np.random.default_rng([int(seed), 0xA221])
    out, t = [], 0.0
    while True:
        for i in rng.permutation(BLOCK):
            t += gaps[int(i)]
            if t >= seconds:
                return out
            out.append(t)
