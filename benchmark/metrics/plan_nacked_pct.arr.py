"""Plan applier: evals nacked because their plan was committed only in part
and could not be re-dispatched, per 100 jobs due in the window
(nomad.pipeline.nacked). A nacked eval is redelivered by the broker after
1 s, then 20 s: once more than 5 in 100 are nacked the 95th percentile of
the latency is a redelivered job's. layer: plan applier; moves submit_commit_p50_ms."""


def read(ctx):
    due = len(ctx["window"]["records"])
    if not due:
        return None
    return 100.0 * ctx["counters"].get("nomad.pipeline.nacked", 0.0) / due
