"""Batcher: from the results landing on the host (``t_host`` of the
dispatch that served an eval, joined on its ``waves``) to the parked worker
running again (the end of the eval's ``device_wait`` stage around it).
Median over the window's evals. layer: batcher; moves submit_commit_p50_ms."""
from harness import spans


def read(ctx):
    by_wave = {d["wave"]: d for d in spans.dispatches(ctx)}
    values = []
    for rec in spans.evals(ctx):
        waits = [(a, b) for name, a, b in rec["stages"] if name == "device_wait"]
        for wave in rec.get("waves") or ():
            d = by_wave.get(wave)
            if d is None:
                continue
            values += [(b - d["t_host"]) * 1000.0 for a, b in waits
                       if a <= d["t_host"] <= b]
    spans.log("result_wake_ms.arr", len(values))
    return spans.median(values)
