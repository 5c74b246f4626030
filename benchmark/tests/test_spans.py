"""The readers of the program's span records: the join of dispatch records
to scan program runs on the recorded extract, the arithmetic over an
evaluation's path, a program that keeps no such record, and the traced tiny
cell of test_rehearsal.py's kind."""
import glob
import json
import os

import pytest

import tiny
from harness import spans, trace

MS = 1e-3
HOST0 = 2000.0     # the host clock at the mark
NEW = ["pad_stack_ms.arr", "dispatch_pre_kernel_ms.arr",
       "dispatch_post_kernel_ms.arr", "result_wake_ms.arr",
       "scan_useful_steps_pct.arr", "plan_queue_wait_ms.arr",
       "commit_seen_lag_ms.arr", "eval_path_covered_pct.arr"]
NEED_DEVICE_PLANE = {"dispatch_pre_kernel_ms.arr", "dispatch_post_kernel_ms.arr"}


def _reader(name):
    import run

    return run.load_reader(tiny.BENCH, name)


def _dispatch(wave, t_stack, t_ready, eval_ids=(), t_start=None, steps=50):
    """A dispatch record as the batcher writes it, times in ms after HOST0."""
    t_start = t_stack - 1.5 if t_start is None else t_start
    return dict(wave=wave, source="batcher", batcher=1, eval_ids=list(eval_ids),
                b=1, b_pad=1, p_pad=64, n_pad=5120, steps=steps,
                padded_steps=64, closed_by="idle_gap", d2h_bytes=1280,
                t_first_enqueue=HOST0 + (t_start - 4.0) * MS,
                t_start=HOST0 + t_start * MS, t_stack=HOST0 + t_stack * MS,
                t_called=HOST0 + (t_stack + 0.5) * MS,
                t_ready=HOST0 + t_ready * MS,
                t_host=HOST0 + (t_ready + 1.0) * MS,
                t_handed=HOST0 + (t_ready + 1.1) * MS)


@pytest.fixture
def recorded(monkeypatch):
    """ctx over the recorded v5e extract (two runs of jit_body: 4.596 ->
    10.798 ms and 40.957 -> 47.160 ms) with synthetic dispatch records."""
    from nomad_tpu.trace import lifecycle

    path = glob.glob(os.path.join(os.path.dirname(trace.__file__), "testdata",
                                  "extract_*.json"))[0]
    with open(path) as f:
        ex = json.load(f)
    red = trace.reduce(ex, 0.0, ex["span_ns"])
    red["to_trace_ns"] = lambda t: (t - HOST0) * 1e9
    records = [
        _dispatch(1, 1.0, 11.5),              # the first run inside
        _dispatch(2, 12.0, 30.0),             # none inside: left out, counted
        _dispatch(3, 1.0, 50.0),              # both inside: left out, counted
        _dispatch(4, 40.0, 47.5),             # the second run inside
        _dispatch(5, 70.0, 80.0),             # past the traced slice
        _dispatch(6, -80.0, -70.0, t_start=-90.0),   # before the window
    ]
    monkeypatch.setattr(lifecycle, "dispatch_records", lambda: records)
    return {"trace": red, "profile_t0": HOST0, "profile_t1": HOST0 + 0.060,
            "window": {"t0": HOST0 - 0.050, "t1": HOST0 + 1.0, "records": []},
            "lifecycle": [], "stats": {}}


def test_join_on_the_recorded_extract(recorded):
    assert [d["wave"] for d in spans.dispatches(recorded)] == [1, 2, 3, 4, 5]
    j = spans.join(recorded)
    assert (j["slice"], j["none_inside"], j["several_inside"]) == (4, 1, 1)
    assert [d["wave"] for d, *_ in j["joined"]] == [1, 4]
    d, pre, kernel, post = j["joined"][0]
    assert pre == pytest.approx((4.5964245 - 1.0) * MS)
    assert kernel == pytest.approx(6.201432 * MS)
    assert post == pytest.approx((12.5 - 10.7978565) * MS)
    # the four parts are the host's own t_start -> t_host, cut at the run
    assert (d["t_stack"] - d["t_start"]) + pre + kernel + post == pytest.approx(
        d["t_host"] - d["t_start"])
    assert "1 with no scan run inside" in spans.join_note(j)
    assert _reader("dispatch_pre_kernel_ms.arr")(recorded) == pytest.approx(
        ((4.5964245 - 1.0) + (40.9567685 - 40.0)) / 2)
    assert _reader("dispatch_post_kernel_ms.arr")(recorded) == pytest.approx(
        ((12.5 - 10.7978565) + (48.5 - 47.1597615)) / 2)
    assert _reader("pad_stack_ms.arr")(recorded) == pytest.approx(1.5)


def test_join_says_nothing_without_a_trace_or_a_record(recorded, monkeypatch):
    from nomad_tpu.trace import lifecycle

    assert spans.join(dict(recorded, trace=None)) is None
    assert _reader("dispatch_pre_kernel_ms.arr")(dict(recorded, trace=None)) is None
    # a program that keeps no dispatch record (a parent commit)
    monkeypatch.delattr(lifecycle, "dispatch_records")
    bare = {k: v for k, v in recorded.items() if k != "_span_dispatches"}
    assert spans.dispatches(bare) == []
    for name in NEW:
        assert _reader(name)(dict(bare)) is None, name


def _eval_record(job_id="job-7", wave=1):
    e = HOST0
    stages = [("wait_index", e + 2 * MS, e + 2.1 * MS),
              ("snapshot", e + 2.2 * MS, e + 3 * MS),
              ("reconcile", e + 3.5 * MS, e + 5 * MS),
              ("encode", e + 5.5 * MS, e + 9 * MS),
              ("device_wait", e + 9 * MS, e + 14.5 * MS),
              ("apply", e + 15 * MS, e + 18 * MS),
              ("engine_gate", e + 5.2 * MS, e + 18.2 * MS),
              ("plan_evaluate", e + 21 * MS, e + 23 * MS),
              ("raft_fsm", e + 24 * MS, e + 26 * MS)]
    return {"eval_id": "ev-" + job_id, "job_id": job_id, "outcome": "ack",
            "enqueue_t": e, "dequeue_t": e + 1.5 * MS, "submit_t": e + 19 * MS,
            "evaluate_start_t": e + 21 * MS, "commit_t": e + 26 * MS,
            "end_t": e + 27 * MS, "wave": wave, "waves": [wave],
            "stages": stages}


def test_an_evals_path(recorded):
    rec = _eval_record()
    # not covered: 1.5-2, 2.1-2.2, 3-3.5, 5-5.2, 18.2-19, 23-24 ms of 26
    assert spans.covered_share(rec) == pytest.approx(1 - 3.1 / 26)
    st = spans.self_times(rec)
    assert sum(st.values()) == pytest.approx(26 * MS)
    assert st["unnamed"] == pytest.approx(3.1 * MS)
    assert st["broker_wait"] == pytest.approx(1.5 * MS)
    assert st["plan_queue_wait"] == pytest.approx(2 * MS)
    assert st["device_wait"] == pytest.approx(5.5 * MS)
    # the gate's own time: what encode, the wait and apply leave of it
    assert st["engine_gate"] == pytest.approx((0.3 + 0.5 + 0.2) * MS)
    ctx = dict(recorded, lifecycle=[rec, dict(_eval_record("job-8"), outcome="nack")])
    ctx["window"] = dict(ctx["window"], records=[
        {"id": "job-7", "t_commit": HOST0 + 27.8 * MS},
        {"id": "job-9", "t_commit": None}])
    assert _reader("eval_path_covered_pct.arr")(ctx) == pytest.approx(100 * (1 - 3.1 / 26))
    assert _reader("plan_queue_wait_ms.arr")(ctx) == pytest.approx(2.0)
    # wave 1's results were on the host at 12.5 ms; the worker ran at 14.5
    assert _reader("result_wake_ms.arr")(ctx) == pytest.approx(2.0)
    assert _reader("commit_seen_lag_ms.arr")(ctx) == pytest.approx(1.8)
    ctx["stats"] = {"steps": 100, "padded_steps": 400}
    assert _reader("scan_useful_steps_pct.arr")(ctx) == pytest.approx(25.0)


def test_manifest_names_the_eight(recorded):
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    for m in (by_name[name] for name in NEW):
        assert "svc-spread-5k.arrivals" in m["workloads"]
        assert m["moves"] == "submit_commit_p50_ms"
        assert (m["source"] == "device_trace") == (m["name"] in NEED_DEVICE_PLANE)


def test_traced_tiny_cell_reports_the_six_that_need_no_device_plane(tmp_path):
    import run
    from harness import system

    system.import_program()
    repo, manifest = tiny.scratch_checkout(str(tmp_path))
    result = run.run_cell(manifest, repo, "tiny-64.open", 4242424243, 3.0,
                          True, system.device_facts(), out_dir=str(tmp_path))
    assert result["correct"], result["checks"]
    m = result["metrics"]
    assert set(NEW) - NEED_DEVICE_PLANE <= set(m), sorted(m)
    assert not NEED_DEVICE_PLANE & set(m)
    assert 0 < m["scan_useful_steps_pct.arr"]["value"] <= 100
    assert 50 <= m["eval_path_covered_pct.arr"]["value"] <= 100
    assert m["pad_stack_ms.arr"]["value"] > 0
    assert m["commit_seen_lag_ms.arr"]["value"] > 0
    assert m["result_wake_ms.arr"]["value"] >= 0
    assert m["plan_queue_wait_ms.arr"]["value"] >= 0
