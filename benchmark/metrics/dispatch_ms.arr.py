"""Batcher: host wall of one dispatch, pad/stack + (H2D + kernel behind a
host fence) + D2H. Named for what it is: not kernel time.
layer: batcher; moves submit_commit_p50_ms."""


def read(ctx):
    s = ctx["stats"]
    if not s.get("dispatches"):
        return None
    return (s["pad_stack_ms_total"] + s["compute_ms_total"]
            + s["transfer_ms_total"]) / s["dispatches"]
