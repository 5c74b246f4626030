"""Batcher: placement steps the evals asked for per 100 steps the padded
batches ran (``steps`` / ``padded_steps`` of DeviceBatcher.stats, b_pad x
p_pad a dispatch), over the window and its drain: what the batch and step
buckets pad. layer: batcher; moves submit_commit_p50_ms."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("padded_steps"):
        return None
    return 100.0 * s["steps"] / s["padded_steps"]
