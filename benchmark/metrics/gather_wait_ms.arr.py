"""Batcher: mean wait of an eval in the gather, enqueue -> dispatch start
(gather_wait_ms_total is summed per eval, so it is divided by evals).
layer: batcher; moves submit_commit_p50_ms."""


def read(ctx):
    s = ctx["stats"]
    return s["gather_wait_ms_total"] / s["evals"] if s.get("evals") else None
