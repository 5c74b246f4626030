"""Kernels: share of the HBM roofline reached by the batched placement scan.

Least time: harness/work.py's bytes for the evals the batcher dispatched,
from their shapes (nodes, placements, stanzas), over the table's HBM peak.
Time: summed device time of the scan program's runs in the trace. Both over
the same stretch of the traced slice: from the first to the last moment at
which the batcher counted a finished dispatch (the sampler's series), so
that no run is counted on one side only.
layer: kernels; moves submit_commit_p50_ms."""
from harness.scan import scan_stretch


def read(ctx):
    st = scan_stretch(ctx)
    if st is None or st["device_s"] <= 0 or not st["evals"]:
        return None
    peaks = ctx["work"].load_peaks(ctx["device_kind"])
    least, _bound = ctx["work"].least_seconds(st["evals"], peaks)
    return 100.0 * least / st["device_s"]
