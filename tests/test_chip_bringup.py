"""Chip bring-up behaviour that can be checked without a chip: where the
compile cache goes, that chip_smoke.py refuses a CPU, that its phases run
end to end (CPU dry run at a tiny size), and that no launcher hands the
parent's accelerator platform to a child process."""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache placement --------------------------------------------

def _cache_dir_updates(monkeypatch):
    """Re-run the engine's one-shot cache setup against a spied
    jax.config.update; returns the (name, value) updates it made."""
    import jax

    from nomad_tpu.tpu import engine

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(engine, "_cache_enabled", False)
    engine._enable_persistent_compile_cache()
    return calls


def test_cache_dir_from_environment_is_not_overridden(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _cache_dir_updates(monkeypatch)
    assert "jax_compilation_cache_dir" not in [name for name, _ in calls]


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch):
    from nomad_tpu.tpu import engine

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _cache_dir_updates(monkeypatch)
    assert ("jax_compilation_cache_dir",
            os.path.join(REPO, ".jax_cache")) in calls
    assert engine.DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert os.path.isdir(engine.DEFAULT_COMPILE_CACHE_DIR)


# -- the C1M job mix (benchmark/configs/c1m-5k.json's jobs.templates, the
# one table of it: read by chip_smoke.c1m_job and by the benchmark) -------


def _c1m_templates():
    sys.path.insert(0, REPO)
    import chip_smoke

    return chip_smoke.c1m_templates()


# (kind, cpu, mem, count, stanzas) of the 40 templates, in order, as the
# parent's bench.c1m_mixed_jobs built them
C1M_TABLE = [
    ("service", 8, 16, 900, True), ("service", 12, 16, 950, True),
    ("service", 16, 16, 1000, True), ("service", 20, 16, 900, True),
    ("service", 8, 24, 950, True), ("service", 12, 24, 1000, True),
    ("service", 16, 24, 900, True), ("service", 20, 24, 950, True),
    ("service", 8, 32, 1000, True), ("service", 12, 32, 900, True),
    ("service", 16, 32, 950, False), ("service", 20, 32, 1000, False),
    ("service", 8, 48, 900, False), ("service", 12, 48, 950, False),
    ("service", 16, 48, 1000, False), ("service", 20, 48, 900, False),
    ("service", 8, 16, 950, False), ("service", 12, 16, 1000, False),
    ("service", 16, 16, 900, False), ("service", 20, 16, 950, False),
    ("service", 8, 24, 1000, False), ("service", 12, 24, 900, False),
    ("service", 16, 24, 950, False), ("service", 20, 24, 1000, False),
    ("service", 8, 32, 900, False), ("service", 12, 32, 950, False),
    ("service", 16, 32, 1000, False), ("service", 20, 32, 900, False),
    ("batch", 8, 16, 950, False), ("batch", 12, 24, 1000, False),
    ("batch", 16, 32, 950, False), ("batch", 20, 48, 1000, False),
    ("batch", 8, 16, 950, False), ("batch", 12, 24, 1000, False),
    ("batch", 16, 32, 950, False), ("batch", 20, 48, 1000, False),
    ("batch", 8, 16, 950, False), ("batch", 12, 24, 1000, False),
    ("batch", 16, 32, 950, False), ("batch", 20, 48, 1000, False),
]


def test_c1m_templates_are_the_parents_forty_in_order():
    got = [(t["kind"], t["cpu"], t["mem"], t["count"],
            "spread" in t and "affinity" in t) for t in _c1m_templates()]
    assert got == C1M_TABLE
    assert [k for k, *_ in got] == ["service"] * 28 + ["batch"] * 12
    assert [st for *_, st in got] == [True] * 10 + [False] * 30
    # every job pads into the p=1024 scan bucket (batcher._batch_dims)
    assert all(256 < c <= 1024 and c in (900, 950, 1000)
               for _, _, _, c, _ in got)


def test_c1m_job_carries_its_template():
    from nomad_tpu import mock

    for tpl in _c1m_templates():
        j = mock.c1m_job(tpl, "c1m-x")
        tg = j.task_groups[0]
        res = tg.tasks[0].resources
        assert (j.type, res.cpu, res.memory_mb, tg.count) == (
            tpl["kind"], tpl["cpu"], tpl["mem"], tpl["count"])
        assert (j.id, tg.ephemeral_disk.size_mb) == ("c1m-x", 50)
        if "spread" in tpl:
            (sp,), (af,) = tg.spreads, tg.affinities
            assert (sp.attribute, sp.weight) == ("${node.datacenter}", 50)
            assert [(t.value, t.percent) for t in sp.spread_target] == [
                ("dc1", 100)]
            assert (af.ltarget, af.rtarget, af.operand, af.weight) == (
                "${attr.kernel.name}", "linux", "=", 50)
        else:
            assert not tg.spreads and not tg.affinities


def test_chip_smoke_jobs_equal_the_parents_field_for_field():
    """sha256 over the canonical JSON of chip_smoke.c1m_job(i), i in
    0..79 (two rounds of the table), taken at the parent commit where the
    jobs came from bench.c1m_mixed_jobs."""
    import hashlib

    sys.path.insert(0, REPO)
    import chip_smoke
    from nomad_tpu.agent import jsonapi

    sizes = chip_smoke.Sizes()
    h = hashlib.sha256()
    for i in range(80):
        job = chip_smoke.c1m_job(sizes, i, f"c1m-{i}")
        h.update(json.dumps(jsonapi.to_json_obj(job), sort_keys=True).encode())
    assert h.hexdigest() == (
        "dba6de9d3fa41b07c579c31fea5f5d70bea665d2f1716c7423e9385eea8912c6")
    small = chip_smoke.Sizes(count_scale=0.05)
    assert chip_smoke.c1m_job(small, 3, "x").task_groups[0].count == 45
    assert chip_smoke.c1m_job(small, 3, "x", 7).task_groups[0].count == 7


# -- chip_smoke.py --------------------------------------------------------

def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "'cpu'" in proc.stderr  # names the platform it found
    assert '"ok"' not in proc.stdout  # prints no result


def test_chip_smoke_phases_run_end_to_end_on_cpu_at_tiny_size():
    """The CPU dry run: every phase of chip_smoke.run_smoke (served run,
    convergence and fallback checks, four-kind plan parity, B=1 dispatch
    reading, thread-clean teardown) at a size XLA:CPU compiles in seconds.
    Says nothing about speed; keeps the smoke from rotting between chip
    runs."""
    sys.path.insert(0, REPO)
    import chip_smoke

    result = chip_smoke.run_smoke(chip_smoke.Sizes(
        n_nodes=96, device_batch=4, workers=8, jobs_per_tranche=4,
        tranches=2, count_scale=0.03, small_eval_count=8, dispatch_reps=2,
        phase_timeout_s=120.0,
    ), seed=0)
    assert result["ok"], result["failures"]
    assert result["top_bucket_dispatched"]
    assert result["evicted"] > 0
    assert set(result["parity"]) == {
        "stanza_service", "plain_batch", "system", "preempting"}
    assert result["parity"]["preempting"]["evicted"] > 0
    assert not any(result["fallbacks"].values())
    # stdout ends with the summary line, then the verdict the chip check
    # reads: exactly ok + device{platform, kind, count}, nothing else
    ok = result.pop("ok")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary, verdict = map(json.loads,
                           chip_smoke.result_lines(ok, device, result))
    assert verdict == {"ok": True, "device": device}
    assert list(summary)[:2] == ["ok", "device"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["placements"] == result["placements"]


# -- one process for each chip --------------------------------------------

def test_crash_harness_children_never_get_the_parents_platform(
        monkeypatch, tmp_path):
    from nomad_tpu.chaos import crash

    seen = {}

    class _Proc:
        def poll(self):
            return None

    def fake_popen(cmd, **kw):
        seen["env"] = kw["env"]
        return _Proc()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(crash.subprocess, "Popen", fake_popen)
    sp = crash.ServerProcess("s1", 1, {}, str(tmp_path))
    sp.spawn()
    sp._logf.close()
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_plugin_subprocesses_are_pinned_off_the_chip(monkeypatch):
    from nomad_tpu.plugins import catalog

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert catalog._plugin_env()["JAX_PLATFORMS"] == "cpu"


def test_device_mesh_failure_raises(monkeypatch):
    """device_mesh=True must shard or fail: never a silent unsharded
    batcher."""
    import nomad_tpu.parallel as parallel
    from nomad_tpu.server.server import Server, ServerConfig

    def boom(*_a, **_kw):
        raise RuntimeError("mesh refused")

    monkeypatch.setattr(parallel, "make_mesh", boom)
    with pytest.raises(RuntimeError, match="mesh refused"):
        Server(ServerConfig(num_schedulers=0, device_mesh=True))


# -- the system preempt pass hands a subset to the host: counted ----------

def test_system_preempt_subset_to_host_is_counted(monkeypatch):
    """A preempting system eval with device asks is host-only for its
    capacity-failed subset (preempt_for_device); the handoff moves the
    engine's fallback counter instead of passing silently."""
    import copy

    from nomad_tpu import mock
    from nomad_tpu.structs.structs import RequestedDevice
    from tests.test_system_engine import _CounterSpy, assert_parity, run_pair

    spy = _CounterSpy(monkeypatch)
    nodes = []
    for i in range(4):
        n = mock.nvidia_node()
        n.name = f"gpu-{i}"
        n.node_resources.cpu_shares = 1200
        n.compute_class()
        nodes.append(n)
    low = mock.system_job()
    low.id = "sys-low"
    low.priority = 20
    low.task_groups[0].tasks[0].resources.cpu = 900
    high = copy.deepcopy(low)
    high.id = "sys-high"
    high.priority = 80
    high.task_groups[0].tasks[0].resources.cpu = 600
    high.task_groups[0].tasks[0].resources.devices = [
        RequestedDevice(name="gpu", count=1)]
    plans = run_pair(nodes, [low, high], preemption=True)
    assert_parity(plans)
    assert "nomad.tpu_engine.system_preempt_pass" in spy.calls
    assert "nomad.tpu_engine.fallback" in spy.calls
