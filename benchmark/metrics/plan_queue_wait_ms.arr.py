"""Plan applier: from the worker's ``submit_plan`` to the applier beginning
``evaluate_plan`` for that plan (``submit_t`` -> ``evaluate_start_t`` of the
eval's record), median over the window's evals.
layer: plan applier; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    values = [(r["evaluate_start_t"] - r["submit_t"]) * 1000.0
              for r in spans.evals(ctx)
              if r.get("submit_t") is not None
              and r.get("evaluate_start_t") is not None]
    spans.log("plan_queue_wait_ms.arr", len(values))
    return spans.median(values)
