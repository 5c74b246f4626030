"""Batcher: evals per dispatch over the window (DeviceBatcher.stats).
layer: batcher; moves submit_commit_p50_ms."""


def read(ctx):
    s = ctx["stats"]
    return s["evals"] / s["dispatches"] if s.get("dispatches") else None
