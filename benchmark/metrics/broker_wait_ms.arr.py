"""Eval broker: p95 of enqueue -> dequeue from trace/lifecycle.py's records
(the last 2,048 evals). layer: eval broker; moves submit_commit_p50_ms."""
from harness.loadgen import percentile


def read(ctx):
    recs = ctx["lifecycle"] or []
    waits = sorted((r["dequeue_t"] - r["enqueue_t"]) * 1000.0 for r in recs
                   if r.get("dequeue_t") is not None
                   and r.get("enqueue_t") is not None)
    return percentile(waits, 0.95) if waits else None
