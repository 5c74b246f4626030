"""Kernels: placement steps whose runner-up lay inside the near-tie band of
the winner (intscore.NEAR_TIE_BAND_Q30), which the host's referee then
scored in float64, per 1,000 steps the evals asked for (``near_ties`` and
``steps`` of DeviceBatcher.stats, over the window and its drain). A program
whose step names no runner-up keeps no such counter and reports nothing.
layer: kernels; moves submit_commit_p50_ms."""


def read(ctx):
    s = ctx["stats"]
    if "near_ties" not in s or not s.get("steps"):
        return None
    return 1000.0 * s["near_ties"] / s["steps"]
