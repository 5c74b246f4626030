"""Batcher: from the scan being called to its program starting on the
device, ``t_stack`` of a dispatch record -> start of the one scan program
run inside its [t_stack, t_ready] in the device trace: H2D of the stacked
planes and the launch. Median over the traced slice's dispatches.
layer: batcher; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    j = spans.join(ctx)
    if j is None:
        return None
    values = [row[1] * 1000.0 for row in j["joined"]]
    spans.log("dispatch_pre_kernel_ms.arr", len(values), spans.join_note(j))
    return spans.median(values)
