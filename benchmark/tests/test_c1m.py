"""The cell ``c1m-5k.arrivals`` through ``run_cell`` at a tiny size on the
CPU: the real manifest, traffic file and readers in a scratch checkout whose
copy of the configuration is cut to 64 nodes and 45-50-task jobs (the
committed file is not touched). Open loop, the result line, and the four
readers this cell brought."""
import copy
import json
import os
import shutil

import pytest

import tiny

CELL = "c1m-5k.arrivals"
NEW_READERS = ["refresh_retry_pct.arr", "partial_commit_pct.arr",
               "near_tie_steps_per_kp.arr", "scan_step_us.arr"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import run
    from harness import system

    system.import_program()
    tmp = str(tmp_path_factory.mktemp("c1m"))
    repo = os.path.join(tmp, "checkout")
    shutil.copytree(tiny.BENCH, os.path.join(repo, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), repo)
    path = os.path.join(repo, "benchmark", "configs", "c1m-5k.json")
    with open(path) as f:
        config = json.load(f)
    small = copy.deepcopy(config)
    small["cluster"]["nodes"] = 64
    small["server"] = dict(config["server"], num_schedulers=8, device_batch=4,
                           device_min_placements=0)
    for t in small["jobs"]["templates"]:
        t["count"] //= 20
    small["jobs"]["warm"] = [
        {"template": w["template"], "counts": [50, 12], "scale_by": 2}
        for w in config["jobs"]["warm"]]
    with open(path, "w") as f:
        json.dump(small, f)
    return {"run": run, "repo": repo, "tmp": tmp, "config": config,
            "manifest": run.load_manifest(repo),
            "device": system.device_facts()}


def _cell(checkout, traced, seed=2 ** 31 + 11):
    return checkout["run"].run_cell(
        checkout["manifest"], checkout["repo"], CELL, seed, 4.0, traced,
        checkout["device"], out_dir=checkout["tmp"],
        mix_changes={"rate_per_s": 3.0})


def test_the_config_is_the_forty_rows_in_the_harness_keys(checkout):
    from harness import jobs

    config = checkout["config"]
    rows = config["jobs"]["templates"]
    assert len(rows) == 40
    assert [r["kind"] for r in rows] == ["service"] * 28 + ["batch"] * 12
    for i, r in enumerate(rows):
        spec = jobs.job_spec(r, f"j{i}")      # every key the harness reads
        assert spec["count"] in (900, 950, 1000) and spec["disk"] == 50
        assert spec["datacenters"] == ["dc1"]
        assert (spec["spread"] is not None) == (i < 10)
        assert (spec["affinity"] is not None) == (i < 10)
    assert config["cluster"]["datacenters"] == {"dc1": 1.0}
    with open(os.path.join(tiny.BENCH, "configs", "svc-spread-5k.json")) as f:
        svc = json.load(f)
    for key in ("nodes", "cpu_mhz", "memory_mb", "disk_mb", "windows_share",
                "reserved"):
        assert config["cluster"][key] == svc["cluster"][key], key
    assert config["server"] == svc["server"]
    # every step bucket, plain and stanza, service and batch
    warm = config["jobs"]["warm"]
    assert {(rows[w["template"]]["kind"], "spread" in rows[w["template"]])
            for w in warm} == {("service", True), ("service", False),
                               ("batch", False)}
    for w in warm:
        assert sorted(w["counts"]) == [50, 200, 1000] and 0 < w["scale_by"] < 4


def test_open_loop_cell_reports_the_latency(checkout):
    result = _cell(checkout, traced=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"submit_commit_p50_ms", "setup_s"}
    assert result["metrics"]["submit_commit_p50_ms"]["value"] > 0
    assert result["failed"] == 0 and 4 <= result["attempted"] <= 24
    checks = result["checks"]
    assert checks["jobs_not_committed"]["value"] == 0
    assert checks["placements_mismatching_reference"]["value"] == 0
    assert checks["evals_by_host_stack_pct"]["limit"] == 0


def test_traced_run_reports_the_cells_new_readers(checkout):
    result = _cell(checkout, traced=True, seed=2 ** 31 + 12)
    assert result["correct"], result["checks"]
    m = result["metrics"]
    for name in NEW_READERS[:3]:
        assert name in m and m[name]["value"] >= 0, name
    assert m["near_tie_steps_per_kp.arr"]["unit"] == "steps/kplacement"
    # no device plane on the CPU: the trace's reader says nothing, never 0
    assert "scan_step_us.arr" not in m
    assert {"wave_fill.arr", "plan_nacked_pct.arr",
            "scan_useful_steps_pct.arr"} <= set(m)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_finds_nothing_on_a_program_without_the_counter(name):
    """Laid over a parent commit, a new reader returns None and raises
    nothing."""
    import run

    read = run.load_reader(tiny.BENCH, name)
    ctx = {"window": {"records": [], "t0": 0.0, "t1": 1.0}, "counters": {},
           "stats": {"steps": 10}, "trace": None, "lifecycle": None}
    assert read(ctx) is None


def test_refresh_reader_tells_none_from_no_such_path():
    """The applier publishes the counter at 0: a run with no refresh reads
    0, a program that never names the counter reads nothing."""
    import run

    read = run.load_reader(tiny.BENCH, "refresh_retry_pct.arr")
    window = {"records": [{}] * 50, "t0": 0.0, "t1": 1.0}
    assert read({"window": window, "counters": {}}) is None
    assert read({"window": window, "counters": {
        "nomad.pipeline.refresh_retry": 0.0}}) == 0.0
    assert read({"window": window, "counters": {
        "nomad.pipeline.refresh_retry": 2.0}}) == 4.0


def test_scan_step_reader_pairs_every_record_of_the_slice():
    """Each record of the slice is paired with the run that overlaps its
    [t_stack, t_host] most, also where the clock tie puts the run's start
    before ``t_stack`` (harness/spans.py:join leaves those out); a record
    outside the slice or without ``n_steps`` does not count."""
    import run

    read = run.load_reader(tiny.BENCH, "scan_step_us.arr")
    # three runs of 2 ms, 50 steps each; the tie is 0.3 ms late
    runs = [(int(1e9 * t), int(1e9 * t) + 2_000_000) for t in (1.0, 2.0, 3.0)]
    records = [{"t_stack": t + 0.0003, "t_ready": t + 0.0022,
                "t_host": t + 0.0024, "n_steps": 50} for t in (1.0, 2.0, 3.0)]
    records.append({"t_stack": 9.0, "t_ready": 9.1, "t_host": 9.2,
                    "n_steps": 50})
    records.append({"t_stack": 3.5, "t_ready": 3.6, "t_host": 3.7})
    ctx = {"trace": {"programs": {"jit_body(1)": {"events": runs}},
                     "to_trace_ns": lambda t: int(1e9 * t)},
           "_span_dispatches": records, "profile_t0": 0.5, "profile_t1": 5.0}
    assert read(ctx) == pytest.approx(3 * 2000.0 / 150)
    for d in records:
        d.pop("n_steps", None)
    assert read(ctx) is None
