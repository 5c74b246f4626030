"""Batcher: share of gathers that waited for at least one announced eval
(DeviceBatcher.stats gathers_held / gathers): how often closing on
announced demand holds a dispatch back. A program without the counter
reports nothing.
layer: batcher; moves submit_commit_p50_ms."""


def read(ctx):
    s = ctx["stats"]
    if "gathers_held" not in s or not s.get("gathers"):
        return None
    return 100.0 * s["gathers_held"] / s["gathers"]
