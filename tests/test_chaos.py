"""Chaos harness tests: trace determinism, disarmed no-ops, leader-kill
replay invariants, and device-fault host-fallback parity (ISSUE 9)."""
import copy
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.chaos import (
    POINTS,
    ChaosFault,
    ChaosInjector,
    ChurnReplay,
    SLOGate,
    SLOThresholds,
    fire,
    generate_trace,
    trace_to_jsonable,
)
from nomad_tpu.chaos.injector import active
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.structs.structs import ALLOC_DESIRED_RUN


def wait_for(cond, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# trace determinism
# ---------------------------------------------------------------------------


def test_trace_deterministic_by_seed():
    a = generate_trace(seed=42, duration_s=20.0, n_nodes=50, n_jobs=12)
    b = generate_trace(seed=42, duration_s=20.0, n_nodes=50, n_jobs=12)
    c = generate_trace(seed=43, duration_s=20.0, n_nodes=50, n_jobs=12)
    assert a == b, "same seed must yield the identical event trace"
    assert trace_to_jsonable(a) == trace_to_jsonable(b)
    assert a != c, "different seeds should diverge"
    # sorted by time, disruption paired and cleared before the tail
    assert all(x.t <= y.t for x, y in zip(a, a[1:]))
    kinds = [ev.kind for ev in a]
    assert kinds.count("drain_node") == kinds.count("undrain_node")
    assert kinds.count("mute_node") == kinds.count("unmute_node")
    assert kinds.count("arm_fault") == kinds.count("disarm_fault")
    assert kinds.count("leader_kill") == 1


def test_saturation_kinds_off_is_rng_neutral():
    """New trace kinds default OFF and, when off, consume no rng — every
    existing seed keeps producing a byte-identical trace (replay
    artifacts recorded before the saturation kinds existed stay
    reproducible)."""
    for seed in (0, 7, 42):
        base = generate_trace(seed=seed, duration_s=20.0, n_nodes=50,
                              n_jobs=12)
        explicit_off = generate_trace(seed=seed, duration_s=20.0,
                                      n_nodes=50, n_jobs=12,
                                      n_saturate_waves=0, saturate_jobs=99,
                                      release_nodes=99)
        assert base == explicit_off, \
            "zero saturation waves must not perturb the rng stream"
        assert not any(ev.kind in ("saturate", "capacity_release")
                       for ev in base)


def test_saturation_waves_paired_and_bounded():
    # leader_kill off on both sides: its jitter draws AFTER the
    # saturation block, so the shared-prefix comparison below would
    # otherwise see a shifted kill time
    trace = generate_trace(seed=3, duration_s=20.0, n_nodes=50, n_jobs=12,
                           leader_kill=False,
                           n_saturate_waves=2, saturate_jobs=5,
                           release_nodes=9)
    sats = [ev for ev in trace if ev.kind == "saturate"]
    rels = [ev for ev in trace if ev.kind == "capacity_release"]
    assert len(sats) == len(rels) == 2
    by_wave = {ev.args["wave"]: ev for ev in sats}
    for rel in rels:
        sat = by_wave[rel.args["wave"]]
        assert sat.t < rel.t, "release must follow its wave's saturation"
        assert rel.t <= 20.0 * 0.8 * 0.9, \
            "release lands before the recovery tail"
        assert rel.args["node_count"] == 9
        assert sat.args["job_count"] == 5
    # the prefix shared with a saturation-free trace is unchanged: the
    # new kinds only APPEND rng draws
    base = generate_trace(seed=3, duration_s=20.0, n_nodes=50, n_jobs=12,
                          leader_kill=False)
    residue = [ev for ev in trace
               if ev.kind not in ("saturate", "capacity_release")]
    assert residue == base


# ---------------------------------------------------------------------------
# injector: strict no-op unless armed
# ---------------------------------------------------------------------------


def test_injection_points_noop_when_disarmed():
    # nothing armed: every point is a strict no-op
    assert active() is None
    for point in POINTS:
        fire(point)

    inj = ChaosInjector(seed=1)
    try:
        # armed then disarmed: no-op again
        inj.arm("device_dispatch", prob=1.0)
        inj.disarm("device_dispatch")
        assert active() is None
        for point in POINTS:
            fire(point)

        # armed with prob=1: deterministic fault
        inj.arm("broker_ack", prob=1.0)
        with pytest.raises(ChaosFault):
            fire("broker_ack")
        # a different point stays a no-op even while another is armed
        fire("raft_apply")
        assert inj.fires("broker_ack") == 1
    finally:
        inj.disarm_all()
    assert active() is None
    fire("broker_ack")

    with pytest.raises(ValueError):
        inj.arm("not_a_point")
    with pytest.raises(ValueError):
        inj.arm("heartbeat", mode="explode")


def test_injector_seeded_fire_sequence_is_deterministic():
    def sequence(seed):
        inj = ChaosInjector(seed=seed)
        out = []
        try:
            inj.arm("plan_apply", prob=0.5)
            for _ in range(32):
                try:
                    fire("plan_apply")
                    out.append(0)
                except ChaosFault:
                    out.append(1)
        finally:
            inj.disarm_all()
        return out

    assert sequence(7) == sequence(7)
    assert sequence(7) != sequence(8)


# ---------------------------------------------------------------------------
# leader kill mid-replay: zero lost/duplicated allocations
# ---------------------------------------------------------------------------


def test_leader_kill_mid_replay_zero_lost_allocs():
    trace = generate_trace(
        seed=5, duration_s=6.0, n_nodes=16, n_jobs=5, tg_count=4,
        stop_frac=0.2, rollout_frac=0.2, n_drains=1, n_expiries=1,
        n_hipri=1, n_fault_windows=2, leader_kill=True,
    )
    replay = ChurnReplay(
        seed=5, trace=trace, n_servers=3, n_nodes=16,
        config=ServerConfig(
            num_schedulers=2,
            heartbeat_min_ttl=1.2,
            heartbeat_max_ttl=2.0,
            eval_gc_interval=3600.0,
        ),
        settle_timeout_s=25.0,
    )
    result = replay.run()
    assert active() is None, "replay must disarm its injector"
    assert result["leader_kills"] == 1
    inv = result["invariants"]
    assert inv["lost"] == 0, inv["violations"]
    assert inv["duplicated"] == 0, inv["violations"]
    assert inv["orphaned"] == 0, inv["violations"]
    assert inv["converged"], inv["violations"]
    # the gate consumes exactly this result shape
    verdict = SLOGate(SLOThresholds(
        eval_ms_p99_max=None, slowest_inflight_ms_max=None,
        throughput_min_allocs_per_s=None,
    )).evaluate(result)
    assert verdict["passed"], verdict["checks"]


# ---------------------------------------------------------------------------
# device-dispatch fault -> host fallback, placement parity
# ---------------------------------------------------------------------------


def _placement_map(server, job):
    allocs = [
        a for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
        if a.desired_status == ALLOC_DESIRED_RUN
    ]
    return {a.name: a.node_id for a in allocs}


def test_device_fault_forces_host_fallback_with_parity(caplog, monkeypatch):
    """The same eval placed twice — once through the device batcher, once
    with every device dispatch failing (host-iterator fallback) — must
    land every task on the same node (the bit-parity contract). The
    degrade is loud: counted and logged at warning with the exception."""
    from tests.test_system_engine import _CounterSpy

    spy = _CounterSpy(monkeypatch)
    cfg = ServerConfig(
        num_schedulers=1,
        deterministic=True,
        ring_decorrelate=False,
        device_min_placements=0,  # always take the device path
        device_batch=8,
        heartbeat_min_ttl=3600.0,
        heartbeat_max_ttl=3601.0,
    )
    nodes = [mock.node() for _ in range(8)]
    job = mock.job()
    job.task_groups[0].count = 16
    job.task_groups[0].tasks[0].resources.networks = []

    def run_once(faulted):
        s = Server(copy.deepcopy(cfg), name="parity")
        s.start()
        inj = ChaosInjector(seed=2)
        try:
            if faulted:
                inj.arm("device_dispatch", mode="fail", prob=1.0)
            for n in nodes:
                s.register_node(copy.deepcopy(n))
            j = copy.deepcopy(job)
            s.register_job(j)
            wait_for(lambda: len(_placement_map(s, j)) == 16,
                     msg="16 allocs placed")
            assert s.drain_evals(timeout=10.0)
            return _placement_map(s, j), s.device_batcher.stats.copy()
        finally:
            inj.disarm_all()
            s.stop()

    device_map, device_stats = run_once(faulted=False)
    assert "nomad.tpu_engine.dispatch_fallback_host" not in spy.calls
    with caplog.at_level("WARNING", logger="nomad_tpu.tpu.engine"):
        host_map, host_stats = run_once(faulted=True)

    assert "nomad.tpu_engine.dispatch_fallback_host" in spy.calls
    warned = [r for r in caplog.records
              if "device dispatch failed" in r.getMessage()]
    assert warned and all(r.levelname == "WARNING" and r.exc_info
                          for r in warned)
    assert device_stats["dispatches"] > 0, "control run must use the device"
    assert host_stats["dispatches"] == 0, \
        "faulted run must never complete a device dispatch"
    assert len(host_map) == 16
    assert host_map == device_map, \
        "host fallback must place identically to the device path"


# ---------------------------------------------------------------------------
# nomad-lockdep: witness-armed churn replay
# ---------------------------------------------------------------------------


def test_witness_armed_churn_replay_sound_and_inversion_free():
    """A churn/chaos replay with the runtime lock witness armed: the run
    must finish with zero lock-order violations among the instrumented
    locks, and every witnessed acquisition-order edge must appear in the
    static analyzer's whole-program graph (the dynamic run is the
    soundness check for the static pass)."""
    from nomad_tpu.utils import lock_witness

    trace = generate_trace(
        seed=11, duration_s=3.0, n_nodes=12, n_jobs=3, tg_count=3,
        stop_frac=0.2, rollout_frac=0.2, n_drains=1, n_expiries=1,
        n_hipri=1, n_fault_windows=2,
    )
    replay = ChurnReplay(
        seed=11, trace=trace, n_servers=2, n_nodes=12,
        config=ServerConfig(
            num_schedulers=2,
            heartbeat_min_ttl=1.2,
            heartbeat_max_ttl=2.0,
            eval_gc_interval=3600.0,
        ),
        settle_timeout_s=25.0,
        lock_witness=True,
    )
    result = replay.run()
    assert lock_witness.active() is None, "replay must disarm its witness"
    lw = result["lock_witness"]
    assert lw["armed"] == 1
    assert lw["violations"] == 0
    # churn must actually drive nested acquisition or the check is vacuous
    assert lw["edges"] > 0, lw
    assert lw["missing_from_static"] == [], lw["missing_from_static"]
    inv = result["invariants"]
    assert inv["lost"] == 0, inv["violations"]
    assert inv["converged"], inv["violations"]


# ---------------------------------------------------------------------------
# nomad-race: race-witness-armed churn replay
# ---------------------------------------------------------------------------


def test_race_witness_armed_churn_replay_race_free_and_sound():
    """The same churn replay with the Eraser lockset witness armed: no
    tracked shared field's candidate lockset may empty during the run,
    and every field the runtime witnessed as cross-thread shared must be
    in the static analyzer's inferred-shared set (dynamic soundness
    check for shared-state-discipline's thread-root inventory)."""
    from nomad_tpu.utils import lock_witness, race_witness

    trace = generate_trace(
        seed=13, duration_s=3.0, n_nodes=12, n_jobs=3, tg_count=3,
        stop_frac=0.2, rollout_frac=0.2, n_drains=1, n_expiries=1,
        n_hipri=1, n_fault_windows=2,
    )
    replay = ChurnReplay(
        seed=13, trace=trace, n_servers=2, n_nodes=12,
        config=ServerConfig(
            num_schedulers=2,
            heartbeat_min_ttl=1.2,
            heartbeat_max_ttl=2.0,
            eval_gc_interval=3600.0,
        ),
        settle_timeout_s=25.0,
        race_witness=True,
    )
    result = replay.run()
    assert race_witness.active() is None, "replay must disarm its witness"
    assert lock_witness.active() is None, "auto-armed lock witness too"
    rw = result["race_witness"]
    assert rw["armed"] == 1
    assert rw["violations"] == 0
    # churn must actually drive the tracked hot fields cross-thread or
    # the race check is vacuous
    assert rw["shared_fields"] > 0, rw
    assert rw["missing_from_static"] == [], rw["missing_from_static"]
    inv = result["invariants"]
    assert inv["lost"] == 0, inv["violations"]
    assert inv["converged"], inv["violations"]
