"""Plan applier: nodes the applier rejected plus device re-dispatches, per
100 placements committed in the window (nomad.plan.dense_nodes_rejected,
nomad.pipeline.redispatch). layer: plan applier; moves submit_commit_p50_ms."""


def read(ctx):
    w = ctx["window"]
    placed = w["placed1"] - w["placed0"]
    if placed <= 0:
        return None
    c = ctx["counters"]
    return 100.0 * (c.get("nomad.plan.dense_nodes_rejected", 0.0)
                    + c.get("nomad.pipeline.redispatch", 0.0)) / placed
