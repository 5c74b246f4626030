"""A scratch checkout for the rehearsal: BENCHMARK.json and benchmark/ copied
whole, plus what a later PR would add as data: one tiny configuration, two
tiny traffic mixes (a closed and an open loop), one end-to-end metric and
one per-layer metric with their readers, and the entries for them; and a
second configuration that brings its own job kind, starting state and
reference (``tiny-sys-64``: deployment_tiny_sys.py, copied as
``deployments/tiny-sys-64.py``) with its open loop. No file that exists is
edited."""
import copy
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "tiny-64",
    "source": "benchmark/tests: svc-spread-5k's shapes and a plain and a batch job at 64 nodes, jobs of 30 tasks, for the CPU rehearsal only",
    "cluster": {"nodes": 64, "cpu_mhz": [4000, 8000, 16000],
                "memory_mb": [8192, 16384, 32768],
                "disk_mb": {"51200": 0.2, "102400": 0.5, "204800": 0.3},
                "windows_share": 0.05,
                "datacenters": {"dc1": 0.6, "dc2": 0.4},
                "reserved": {"cpu_mhz": 100, "memory_mb": 256, "disk_mb": 4096}},
    "server": {"num_schedulers": 8, "device_batch": 4, "deterministic": True,
               "device_min_placements": 0,
               "scheduler_algorithm": "tpu_binpack"},
    "jobs": {"templates": [
        {"kind": "service", "cpu": 20, "mem": 32, "disk": 50, "count": 30,
         "datacenters": ["dc1", "dc2"], "linux_only": True,
         "spread": {"attribute": "${node.datacenter}", "weight": 100,
                    "targets": {"dc1": 60, "dc2": 40}},
         "affinity": {"linux": True, "weight": 50}},
        {"kind": "service", "cpu": 16, "mem": 24, "disk": 50, "count": 28,
         "datacenters": ["dc1", "dc2"], "linux_only": True},
        {"kind": "batch", "cpu": 12, "mem": 16, "disk": 50, "count": 30,
         "datacenters": ["dc1"], "linux_only": False},
    ], "warm": [{"template": 1, "counts": [28], "scale_by": 6}, {"template": 0, "counts": [30], "scale_by": 6}]},
    "guarantees": ["as svc-spread-5k"], "reduced": [], "assumed": ["everything"],
}
# a priority-20 system job over every linux node and a generic service job
# placed in set-up; the window mixes system jobs (small: they fit on every
# node, nothing is evicted) with tiny-64's plain service jobs
_SYS = {"kind": "system", "cpu": 20, "mem": 16, "disk": 10, "count": 1,
        "datacenters": ["dc1", "dc2"], "linux_only": True, "priority": 50}
TINY_SYS_CONFIG = dict(
    TINY_CONFIG, name="tiny-sys-64",
    source="benchmark/tests: tiny-64's fleet filled in set-up by a priority-20 system job and a service job, then system and service jobs arriving; CPU rehearsal only",
    jobs={"templates": [_SYS, TINY_CONFIG["jobs"]["templates"][1]],
          "warm": [{"template": 0, "counts": [1]},
                   {"template": 1, "counts": [28], "scale_by": 6}],
          "setup": [{"id": "sys-low", "template": dict(
                         _SYS, cpu=100, mem=64, disk=300, priority=20)},
                    {"id": "svc-base", "template": dict(
                         TINY_CONFIG["jobs"]["templates"][1], count=40,
                         cpu=500, mem=512)}]})
DEPLOYMENT_FILE = os.path.join(HERE, "deployment_tiny_sys.py")
EXTRA_METRIC = '''"""Added by the rehearsal: jobs due in the window."""


def read(ctx):
    return float(len(ctx["window"]["records"]))
'''
EXTRA_END_TO_END = '''"""Added by the rehearsal: placements committed between the window's
start and end, over its seconds."""


def read(ctx):
    w = ctx["window"]
    return (w["placed1"] - w["placed0"]) / ctx["seconds"]
'''
LIMITS = {"widest_score_gap": 1e-5, "evals_by_host_stack_pct": 0}


def scratch_checkout(tmp: str) -> tuple:
    """(repo dir, manifest) of a scratch checkout under ``tmp``."""
    repo = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(repo, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest = copy.deepcopy(manifest)
    bench = os.path.join(repo, "benchmark")
    with open(os.path.join(bench, "configs", "tiny-64.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(bench, "traffic", "tiny-closed.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 4, "drain_s": 30,
                   "sample_jobs": 4, "trace_s": 0.5, "limits": LIMITS}, f)
    with open(os.path.join(bench, "traffic", "tiny-open.json"), "w") as f:
        json.dump({"loop": "open", "rate_per_s": 4.0, "drain_s": 30,
                   "sample_jobs": 4, "trace_s": 0.5, "limits": LIMITS}, f)
    with open(os.path.join(bench, "configs", "tiny-sys-64.json"), "w") as f:
        json.dump(TINY_SYS_CONFIG, f)
    with open(os.path.join(bench, "traffic", "tiny-sys-open.json"), "w") as f:
        json.dump({"loop": "open", "rate_per_s": 4.0, "drain_s": 30,
                   "sample_jobs": 4, "trace_s": 0.5, "limits": LIMITS}, f)
    os.makedirs(os.path.join(bench, "deployments"), exist_ok=True)
    shutil.copy(DEPLOYMENT_FILE, os.path.join(bench, "deployments", "tiny-sys-64.py"))
    with open(os.path.join(bench, "metrics", "jobs_due.tiny.py"), "w") as f:
        f.write(EXTRA_METRIC)
    with open(os.path.join(bench, "metrics", "placements_per_s.py"), "w") as f:
        f.write(EXTRA_END_TO_END)
    manifest["configs"].append({
        "name": "tiny-64", "source": TINY_CONFIG["source"],
        "file": "benchmark/configs/tiny-64.json", "reduced": [],
        "why": "rehearsal"})
    manifest["configs"].append({
        "name": "tiny-sys-64", "source": TINY_SYS_CONFIG["source"],
        "file": "benchmark/configs/tiny-sys-64.json", "reduced": [],
        "why": "rehearsal of a deployment module"})
    cells = ["tiny-64.closed", "tiny-64.open", "tiny-sys-64.open"]
    manifest["workloads"] += [
        {"name": "tiny-64.closed", "config": "tiny-64",
         "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-64.open", "config": "tiny-64", "traffic": "tiny-open",
         "chips": 1, "why": "rehearsal"},
        {"name": "tiny-sys-64.open", "config": "tiny-sys-64",
         "traffic": "tiny-sys-open", "chips": 1, "why": "rehearsal"}]
    for m in manifest["end_to_end"]:
        if m["name"].startswith("submit_commit"):
            m["workloads"] += cells[1:]
    manifest["end_to_end"].append({
        "name": "placements_per_s", "unit": "placements/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": [cells[0]]})
    for m in manifest["per_layer"]:
        m["workloads"] += cells[1:]
    manifest["per_layer"].append({
        "name": "jobs_due.tiny", "unit": "jobs", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "placements_per_s", "workloads": [cells[0]]})
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return repo, manifest
