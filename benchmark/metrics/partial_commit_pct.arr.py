"""Plan applier: plans the applier committed only in part (another plan made
on the same snapshot took the capacity first), per 100 jobs due in the
window (nomad.pipeline.partial_commit): what the re-dispatch, the refresh
and the nack then each serve a share of.
layer: plan applier; moves submit_commit_p50_ms."""


def read(ctx):
    due = len(ctx["window"]["records"])
    if not due:
        return None
    return 100.0 * ctx["counters"].get("nomad.pipeline.partial_commit", 0.0) / due
