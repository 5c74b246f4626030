"""Served path: per acknowledged eval, the share of enqueue -> commit that
the union of its stages, the broker wait and the plan-queue wait covers.
Median over the window's evals; less than 90 names a wait nobody stamps.
layer: served path; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    values = [s for s in map(spans.covered_share, spans.evals(ctx))
              if s is not None]
    spans.log("eval_path_covered_pct.arr", len(values))
    m = spans.median(values)
    return None if m is None else 100.0 * m
