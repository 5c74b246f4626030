"""Batcher: host time to pad and stack one dispatch's evals, ``t_stack -
t_start`` of each dispatch record (trace/lifecycle.py's ring), median over
the window's dispatches. layer: batcher; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    values = [(d["t_stack"] - d["t_start"]) * 1000.0
              for d in spans.dispatches(ctx)]
    spans.log("pad_stack_ms.arr", len(values))
    return spans.median(values)
