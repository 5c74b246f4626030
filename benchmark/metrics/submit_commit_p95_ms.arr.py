"""Served path: 95th percentile (nearest rank), over all jobs due in the
window, of due -> last placement committed; jobs that failed sort beyond
every percentile. A per-layer metric because it swings too widely from run
to run for a bound (PERF.md, section 2). layer: served path; moves
submit_commit_p50_ms."""
from harness.loadgen import percentile


def read(ctx):
    return percentile(ctx["latencies_ms"], 0.95) if ctx["latencies_ms"] else None
