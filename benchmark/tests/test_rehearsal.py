"""run.py's functions at a tiny size on the CPU, in a scratch checkout that
adds one configuration, two traffic mixes, an end-to-end and a per-layer
metric and the entries for them, and edits nothing that exists."""
import hashlib
import json
import os
import subprocess
import sys

import pytest

import tiny

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _digest(root: str) -> dict:
    out = {}
    for base, _dirs, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import run
    from harness import system

    system.import_program()
    tmp = str(tmp_path_factory.mktemp("bench"))
    repo, manifest = tiny.scratch_checkout(tmp)
    return {"run": run, "repo": repo, "manifest": manifest, "tmp": tmp,
            "device": system.device_facts()}


def _cell(checkout, name, seed=12345678901 % (2 ** 31 + 5), traced=False,
          seconds=3.0):
    return checkout["run"].run_cell(
        checkout["manifest"], checkout["repo"], name, seed, seconds, traced,
        checkout["device"], out_dir=checkout["tmp"])


def test_closed_loop_cell_result_line(checkout, capsys):
    result = _cell(checkout, "tiny-64.closed")
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert set(result["metrics"]) == {"placements_per_s", "setup_s"}
    assert result["metrics"]["placements_per_s"]["value"] > 0
    checkout["run"].print_result(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS + ["checks"]
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    for c in line["checks"].values():
        assert {"value", "limit"} <= set(c)
    assert err.strip().splitlines()[-1] == "correct: True"
    assert "check placements_mismatching_reference: 0" in err


def test_open_loop_cell_reports_the_latency(checkout):
    result = _cell(checkout, "tiny-64.open")
    assert result["correct"], result["checks"]
    m = result["metrics"]
    assert set(m) == {"submit_commit_p50_ms", "setup_s"}
    assert m["submit_commit_p50_ms"]["value"] > 0
    # 4 jobs/s for 3 s: a part of one block of 64 gaps, so the count swings
    assert 5 <= result["attempted"] <= 24


def test_traced_run_reports_per_layer_metrics(checkout):
    result = _cell(checkout, "tiny-64.open", traced=True)
    assert result["correct"], result["checks"]
    m = result["metrics"]
    assert {"wave_fill.arr", "worker_s_per_kp.arr", "plan_evaluate_s_per_kp.arr",
            "raft_fsm_s_per_kp.arr", "encode_apply_s_per_kp.arr",
            "register_ms.arr", "generator_late_ms.arr", "gather_wait_ms.arr",
            "dispatch_ms.arr", "plan_nacked_pct.arr",
            "submit_commit_p95_ms.arr"} <= set(m)
    # no device plane on the CPU: the trace readers find nothing and say
    # nothing, never 0
    assert "scan_roofline.arr" not in m and "device_idle_pct.arr" not in m
    assert "breakdown" in result and "window_s" in result["device"]


def test_traced_run_reports_the_metric_that_was_added_as_a_file(checkout):
    result = _cell(checkout, "tiny-64.closed", traced=True)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"jobs_due.tiny"}
    assert result["metrics"]["jobs_due.tiny"]["value"] == result["attempted"]


def test_nothing_that_exists_was_edited(checkout):
    ours = _digest(tiny.BENCH)
    theirs = _digest(os.path.join(checkout["repo"], "benchmark"))
    assert {k: theirs[k] for k in ours} == ours
    added = sorted(set(theirs) - set(ours))
    assert added == ["configs/tiny-64.json", "configs/tiny-sys-64.json",
                     "deployments/tiny-sys-64.py", "metrics/jobs_due.tiny.py",
                     "metrics/placements_per_s.py",
                     "traffic/tiny-closed.json", "traffic/tiny-open.json",
                     "traffic/tiny-sys-open.json"]


def test_seed_changes_the_stream_and_repeats_it():
    from harness import cluster, jobs, traffic

    cfg = tiny.TINY_CONFIG
    t = cfg["jobs"]["templates"]

    def stream(seed):
        s = jobs.JobStream(t, seed)
        return [s.next()["template"] for _ in range(30)]

    big = 2 ** 31 + 7
    assert stream(big) == stream(big)
    assert stream(big) != stream(big + 1)
    # every seed offers the same sizes, in another order
    assert sorted(stream(big)) == sorted(stream(big + 1))
    mix = {"loop": "open", "rate_per_s": 50.0}
    a, b, c = (traffic.due_times(mix, s, 10.0) for s in (big, big, big + 1))
    assert a == b and a != c
    assert abs(len(a) - 500) <= 16 and abs(len(a) - len(c)) <= 16
    f1, f2, f3 = (cluster.make_fleet(cfg["cluster"], s) for s in (big, big, 3))
    assert f1.ids == f2.ids and f1.ids != f3.ids
    assert sorted(f1.cpu) == sorted(f3.cpu) and sorted(f1.dc) == sorted(f3.dc)


def test_cli_refuses_a_platform_that_is_not_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "svc-spread-5k.arrivals", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_cli_alone_in_a_directory_exits_nonzero_with_no_result(tmp_path):
    import shutil

    shutil.copytree(tiny.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "svc-spread-5k.arrivals",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
