"""Median, over all jobs due in the window, of due -> last placement
committed. Jobs that failed sort beyond every percentile."""
from harness.loadgen import percentile


def read(ctx):
    return percentile(ctx["latencies_ms"], 0.50) if ctx["latencies_ms"] else None
