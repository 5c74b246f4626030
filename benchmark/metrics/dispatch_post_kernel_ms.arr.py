"""Batcher: from the scan program's end on the device (the one run inside
a dispatch record's [t_stack, t_ready]) to ``t_host``, the five outputs as
numpy arrays: the fence's wake and D2H. Median over the traced slice's
dispatches. layer: batcher; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    j = spans.join(ctx)
    if j is None:
        return None
    values = [row[3] * 1000.0 for row in j["joined"]]
    spans.log("dispatch_post_kernel_ms.arr", len(values), spans.join_note(j))
    return spans.median(values)
