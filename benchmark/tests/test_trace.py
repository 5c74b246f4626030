"""The reduction from a trace to numbers, on a hand-made extract with known
answers and on the recorded one in harness/testdata/."""
import glob
import json
import os

import pytest

from harness import trace

MS = 1e6   # ns


def _extract():
    ops = [("%fusion.1 = f32[8]{0} fusion(...)", 0 * MS, 10 * MS),
           ("%while.2 = (s32[]) while(...)", 5 * MS, 10 * MS),    # overlaps
           ("%fusion.1 = f32[8]{0} fusion(...)", 40 * MS, 20 * MS),
           ("%copy.3 = f32[8]{0} copy(...)", 95 * MS, 10 * MS)]   # leaves the window
    programs = [("jit_body(17)", 0 * MS, 15 * MS), ("jit_body(17)", 40 * MS, 20 * MS),
                ("jit_other(3)", 95 * MS, 10 * MS)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "programs": programs, "lines": ["XLA Ops", "XLA Modules"]}],
            "mark_ns": 0.0}


def test_busy_union_idle_and_gaps():
    red = trace.reduce(_extract(), 0.0, 100 * MS)
    assert red["window_s"] == pytest.approx(0.100)
    # [0,15] + [40,60] + [95,100] = 40 ms busy, overlap counted once
    assert red["busy_s"] == pytest.approx(0.040)
    assert 100 * (1 - red["busy_s"] / red["window_s"]) == pytest.approx(60.0)
    assert red["gaps"] == [(60 * MS, 95 * MS), (15 * MS, 40 * MS)]   # longest first


def test_per_program_and_per_op_device_time():
    red = trace.reduce(_extract(), 0.0, 100 * MS)
    body = red["programs"]["jit_body"]
    assert body["runs"] == 2 and body["seconds"] == pytest.approx(0.035)
    assert red["programs"]["jit_other"]["seconds"] == pytest.approx(0.005)  # clipped
    assert red["ops"] == {"fusion.1": pytest.approx(0.030), "while.2": pytest.approx(0.010),
                   "copy.3": pytest.approx(0.005)}


def test_gap_attribution_by_what_the_host_was_doing():
    red = trace.reduce(_extract(), 0.0, 100 * MS)
    host0 = 1000.0   # the host clock at the mark

    def to_host_s(ns):
        return host0 + ns / 1e9

    def shares(t0, t1):
        # the host encoded through the long gap, and sat in a wait in both
        if t0 >= host0 + 0.059:
            return {"encode": 0.030, "device_wait": 0.035, "busy": 0.035,
                    "window": t1 - t0}
        return {"device_wait": 0.025, "window": t1 - t0}

    named = trace.name_gaps(red["gaps"], to_host_s, shares)
    assert named == [["encode", pytest.approx(0.035)],
                     ["waiting:device_wait", pytest.approx(0.025)]]


def test_window_that_saw_no_operation():
    red = trace.reduce({"devices": [], "mark_ns": None}, 0.0, MS)
    assert red["busy_s"] == 0.0 and red["programs"] == {}


def test_recorded_extract():
    """A cut of a real traced run on the v5e, against a check of its own:
    busy time by counting 1-us ticks covered by any operation."""
    paths = glob.glob(os.path.join(os.path.dirname(trace.__file__), "testdata",
                                   "extract_*.json"))
    assert paths, "no recorded extract under harness/testdata/"
    for path in paths:
        with open(path) as f:
            ex = json.load(f)
        span = ex["span_ns"]
        red = trace.reduce(ex, 0.0, span)
        ticks = bytearray(int(span // 1000) + 1)
        for _n, s, d in ex["devices"][0]["ops"]:
            a, b = max(0, int(s // 1000)), min(len(ticks), int((s + d) // 1000) + 1)
            ticks[a:b] = b"\x01" * max(0, b - a)
        assert red["busy_s"] == pytest.approx(sum(ticks) / 1e6, rel=0.02)
        assert 0 < red["busy_s"] <= red["window_s"]
        gaps = sum(b - a for a, b in red["gaps"]) / 1e9
        assert gaps + red["busy_s"] == pytest.approx(red["window_s"])
        scan = [r for n, r in red["programs"].items() if n.startswith("jit_body")]
        assert scan and sum(r["runs"] for r in scan) >= 1
        # the programs' time covers the operations' union: ops run inside them
        assert sum(r["seconds"] for r in red["programs"].values()) >= 0.98 * red["busy_s"]


def test_roofline_share_from_a_stretch_of_dispatches():
    """scan_roofline.arr's arithmetic on a made-up stretch: two cohorts of
    16 first passes and one lone tail between three moments at which the
    batcher counted a finished dispatch."""
    import importlib.util

    from harness import scan, work

    host0 = 500.0
    red = trace.reduce({"devices": [{
        "name": "/device:TPU:0", "lines": [], "ops": [],
        "programs": [("jit_body(1)", 100 * MS, 300 * MS),     # cohort
                     ("jit_body(2)", 450 * MS, 10 * MS),      # the tail
                     ("jit_body(1)", 500 * MS, 300 * MS)]}],  # cohort
        "mark_ns": 0.0}, 0.0, 1000 * MS)
    red["to_trace_ns"] = lambda t: (t - host0) * 1e9
    spec = {"spread": None, "affinity": None}
    ctx = {"trace": red, "profile_t0": host0, "profile_t1": host0 + 1.0,
           "sampler": [(host0 + 0.05, 10, 100), (host0 + 0.41, 11, 116),
                       (host0 + 0.47, 12, 117), (host0 + 0.81, 13, 133)],
           "window": {"records": [{"t_commit": host0 + 0.5, "count": 950,
                                   "spec": spec}]},
           "counters": {"nomad.pipeline.redispatch": 1.0}, "n_nodes": 5000,
           "work": work, "device_kind": "TPU v5 lite"}
    st = scan.scan_stretch(ctx)
    assert (st["cohort"], st["lone"]) == (32, 1)
    assert st["device_s"] == pytest.approx(0.300 + 0.010 + 0.300)
    assert sorted(set(st["evals"])) == [(5000, 16, False), (5000, 950.0, False)]
    path = os.path.join(os.path.dirname(os.path.dirname(trace.__file__)),
                        "metrics", "scan_roofline.arr.py")
    loader = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(mod)
    least = (32 * work.scan_bytes(5000, 950, False)
             + work.scan_bytes(5000, 16, False)) / 819e9
    assert mod.read(ctx) == pytest.approx(100 * least / 0.610)
    # nothing to read: nothing said
    assert mod.read(dict(ctx, trace=None)) is None
