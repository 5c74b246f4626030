"""Worker: seconds of the window in which some thread was inside
snapshot or reconcile (utils/phases.py unions, summed), per 1,000 placements
committed in the window. layer: worker; moves submit_commit_p50_ms."""
from harness.scan import phase_seconds_per_kp


def read(ctx):
    return phase_seconds_per_kp(ctx, ('snapshot', 'reconcile'))
