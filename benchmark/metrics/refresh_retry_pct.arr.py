"""Plan applier: evals whose plan was committed only in part and that went
back to a worker at once, to be run again on the refreshed snapshot, per
100 jobs due in the window (nomad.pipeline.refresh_retry: no broker delay,
no delivery counted). The applier publishes the counter at 0 when it
starts; a program that never publishes it (a parent commit nacks such an
eval: plan_nacked_pct.arr) reports nothing.
layer: plan applier; moves submit_commit_p50_ms."""


def read(ctx):
    due = len(ctx["window"]["records"])
    if not due or "nomad.pipeline.refresh_retry" not in ctx["counters"]:
        return None
    return 100.0 * ctx["counters"]["nomad.pipeline.refresh_retry"] / due
