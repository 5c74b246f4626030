"""Kernels: device time of one run of the batched placement scan, from the
trace. layer: kernels; moves submit_commit_p50_ms."""
from harness.scan import scan_programs


def read(ctx):
    runs = scan_programs(ctx)
    if not runs:
        return None
    return 1000.0 * sum(r["seconds"] for r in runs) / sum(r["runs"] for r in runs)
