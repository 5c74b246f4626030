"""Concurrency stress: the ``-race``-slot suite (reference
GNUmakefile:293 runs `go test -race`). Python has no race sanitizer, so
these tests hammer the heavily-threaded subsystems — eval broker, plan
queue/applier, device batcher, state store — from many threads and assert
the INVARIANTS races would break:

  * no eval is delivered-and-acked twice, none is lost
  * committed capacity never exceeds any node's resources, and the
    incremental usage mirror equals the ground-truth alloc sum
  * raft/store indexes only move forward
  * every batcher request gets exactly one result (or a definite error),
    bit-identical to the single-eval oracle
"""
import random
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.structs.structs import (
    EVAL_STATUS_PENDING,
    Evaluation,
    generate_uuid,
)


def spin_until(fn, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out: {msg}")


class TestBrokerStress:
    def test_no_lost_no_double_ack(self):
        """16 producers x 8 consumers with random nack/requeue noise:
        every eval ends acked EXACTLY once; none vanish."""
        broker = EvalBroker(nack_timeout=5.0, delivery_limit=1000,
                            initial_nack_delay=0.01,
                            subsequent_nack_delay=0.02)
        broker.set_enabled(True)
        n_per_producer = 50
        n_producers = 16
        total = n_per_producer * n_producers
        acked = {}
        acked_lock = threading.Lock()
        stop = threading.Event()
        errors = []

        def produce(pi):
            try:
                for k in range(n_per_producer):
                    ev = Evaluation(
                        job_id=f"stress-{pi}-{k}", type="service",
                        status=EVAL_STATUS_PENDING, priority=random.randint(1, 99),
                    )
                    broker.enqueue(ev)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def consume():
            rng = random.Random()
            while not stop.is_set():
                try:
                    ev, token = broker.dequeue(["service"], timeout=0.2)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return
                if ev is None:
                    continue
                if rng.random() < 0.2:
                    try:
                        broker.nack(ev.id, token)  # redelivery path
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)
                    continue
                try:
                    broker.ack(ev.id, token)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    continue
                with acked_lock:
                    acked[ev.id] = acked.get(ev.id, 0) + 1

        consumers = [threading.Thread(target=consume, daemon=True)
                     for _ in range(8)]
        for t in consumers:
            t.start()
        producers = [threading.Thread(target=produce, args=(pi,), daemon=True)
                     for pi in range(n_producers)]
        for t in producers:
            t.start()
        for t in producers:
            t.join()

        spin_until(lambda: len(acked) == total, msg=f"{total} evals acked")
        stop.set()
        for t in consumers:
            t.join(timeout=5)
        assert not errors, errors[:3]
        doubles = {k: v for k, v in acked.items() if v != 1}
        assert not doubles, f"double-acked: {list(doubles)[:5]}"
        stats = broker.stats()
        assert stats["total_ready"] == 0
        assert stats["total_unacked"] == 0

    def test_enable_disable_churn_never_wedges(self):
        """Leadership flaps (enable/disable) racing enqueues must neither
        deadlock nor strand evals when finally enabled."""
        broker = EvalBroker(nack_timeout=5.0)
        broker.set_enabled(True)
        stop = threading.Event()
        errors = []

        def flap():
            while not stop.is_set():
                broker.set_enabled(False)
                time.sleep(0.002)
                broker.set_enabled(True)
                time.sleep(0.002)

        enqueued = []

        def enqueue():
            for k in range(200):
                try:
                    ev = Evaluation(job_id=f"flap-{k}", type="batch")
                    broker.enqueue(ev)
                    enqueued.append(ev)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        f = threading.Thread(target=flap, daemon=True)
        f.start()
        eq = threading.Thread(target=enqueue, daemon=True)
        eq.start()
        eq.join(timeout=20)
        stop.set()
        f.join(timeout=5)
        assert not errors
        # Re-enqueue after the final enable (a disable flush legitimately
        # drops in-memory state — the reference restores from raft on
        # re-election, which the server does via restore_evals); then
        # EVERY eval must be deliverable: none wedged, none stranded.
        broker.set_enabled(True)
        for ev in enqueued:
            broker.enqueue(ev)
        seen = set()
        deadline = time.monotonic() + 20
        while len(seen) < len(enqueued) and time.monotonic() < deadline:
            got, token = broker.dequeue(["batch"], timeout=0.5)
            if got is None:
                continue
            broker.ack(got.id, token)
            seen.add(got.id)
        assert len(seen) == len(enqueued), (
            f"stranded {len(enqueued) - len(seen)} evals after churn"
        )


class TestPlanApplierStress:
    def test_concurrent_dense_plans_never_overcommit(self):
        """24 submitter threads flooding the plan queue with dense plans
        over a small overcommitted fleet: per-node committed usage must
        NEVER exceed capacity, the usage mirror must equal the alloc
        ground truth, and indexes must be monotone."""
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.structs.structs import (
            AllocatedResources,
            AllocatedSharedResources,
            AllocatedTaskResources,
            DenseTGPlacements,
            Plan,
            generate_uuids,
        )

        server = Server(ServerConfig(num_schedulers=0, device_batch=0,
                                     heartbeat_min_ttl=3600,
                                     heartbeat_max_ttl=7200))
        server.start()
        try:
            node_ids = []
            for i in range(16):
                n = mock.node()
                n.name = f"stress-{i}"
                n.node_resources.cpu_shares = 1000
                n.node_resources.memory_mb = 1024
                n.compute_class()
                server.raft_apply(NODE_REGISTER, n)
                node_ids.append(n.id)

            proto = AllocatedResources(
                tasks={"t": AllocatedTaskResources(cpu_shares=100, memory_mb=100)},
                shared=AllocatedSharedResources(disk_mb=10),
            )
            results = []
            res_lock = threading.Lock()
            indexes = []

            def submit(si):
                rng = random.Random(si)
                for k in range(12):
                    per = rng.randint(1, 6)
                    chosen = [rng.randrange(len(node_ids)) for _ in range(per)]
                    block = DenseTGPlacements(
                        namespace="default", job_id=f"sj-{si}",
                        task_group="t", eval_id=f"se-{si}-{k}",
                        resources_proto=proto,
                        ask_vec=(100.0, 100.0, 10.0, 0.0),
                        ids=generate_uuids(per),
                        names=[f"sj-{si}.t[{j}]" for j in range(per)],
                        node_ids=[node_ids[c] for c in chosen],
                        node_names=[f"stress-{c}" for c in chosen],
                        scores=[1.0] * per, nodes_evaluated=[1] * per,
                    )
                    plan = Plan(eval_id=block.eval_id,
                                dense_placements=[block])
                    pending = server.plan_queue.enqueue(plan)
                    r = pending.future.result(timeout=60)
                    with res_lock:
                        results.append(r)
                        indexes.append(r.alloc_index)

            threads = [threading.Thread(target=submit, args=(si,), daemon=True)
                       for si in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert all(not t.is_alive() for t in threads), "submitters wedged"

            state = server.fsm.state
            from nomad_tpu.structs.funcs import alloc_usage_vec

            # ground truth vs mirror, and capacity ceiling per node
            per_node = {}
            for a in state.allocs():
                if a.terminal_status():
                    continue
                u = alloc_usage_vec(a)
                row = per_node.setdefault(a.node_id, [0.0] * 4)
                for d in range(4):
                    row[d] += u[d]
            for nid, row in per_node.items():
                mrow = state._node_usage.get(nid, (0.0,) * 4)
                assert tuple(row) == tuple(mrow), f"mirror drift on {nid[:8]}"
                node = state.node_by_id(nid)
                assert row[0] <= node.node_resources.cpu_shares + 1e-9, (
                    f"cpu overcommit on {nid[:8]}: {row[0]}"
                )
                assert row[1] <= node.node_resources.memory_mb + 1e-9, (
                    f"mem overcommit on {nid[:8]}: {row[1]}"
                )
            committed = sum(
                len(b.ids) for r in results for b in r.dense_placements
            )
            assert committed == state.count_allocs_desired_run()
            # committed plans carry positive indexes; fully-rejected plans
            # MUST carry a refresh index or their workers re-plan blind
            # against the same stale snapshot forever
            for r in results:
                if r.dense_placements:
                    assert r.alloc_index > 0
                else:
                    assert r.refresh_index > 0, "rejected plan without refresh"
            assert state.latest_index >= max(
                r.alloc_index for r in results if r.dense_placements
            )
        finally:
            server.stop()


class TestBatcherStress:
    def test_random_shapes_random_timing_all_answered(self):
        """48 submissions of random shapes from 12 threads with jittered
        arrival: every request gets exactly one result, each bit-equal to
        its single-eval oracle (sampled)."""
        from nomad_tpu.tpu.batcher import DeviceBatcher
        from nomad_tpu.tpu.engine import TpuPlacementEngine

        from tests.test_device_batcher import synthetic_enc

        engine = TpuPlacementEngine.shared()
        rng = random.Random(0)
        shapes = [(rng.choice([8, 16, 24]), rng.choice([1, 2]),
                   rng.choice([2, 4, 6]), rng.choice([0, 1]))
                  for _ in range(48)]
        encs = [synthetic_enc(n, g, p, n_spreads=s, seed=i)
                for i, (n, g, p, s) in enumerate(shapes)]
        oracle_idx = rng.sample(range(len(encs)), 6)
        oracle = {i: engine.run_scan_single(encs[i]) for i in oracle_idx}

        batcher = DeviceBatcher(max_batch=8, window_ms=10.0)
        results = [None] * len(encs)
        errors = []

        def submit(i):
            time.sleep(random.random() * 0.05)
            try:
                results[i] = batcher.run(encs[i])
            except BaseException as e:  # noqa: BLE001
                errors.append((i, e))

        threads = [threading.Thread(target=submit, args=(i,), daemon=True)
                   for i in range(len(encs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        batcher.stop()
        assert not errors, errors[:3]
        assert all(r is not None for r in results)
        for i, want in oracle.items():
            for k in range(4):
                np.testing.assert_array_equal(
                    np.asarray(results[i][k]), np.asarray(want[k]),
                    err_msg=f"eval {i} diverged under stress batching",
                )


class TestStateStoreStress:
    def test_snapshots_internally_consistent_under_writers(self):
        """4 writer threads churning allocs while 4 readers snapshot:
        every snapshot's usage mirror must equal the alloc sum VISIBLE IN
        THAT SNAPSHOT (copy-on-write isolation), and latest_index must
        never move backwards within a reader."""
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs.funcs import alloc_usage_vec
        from nomad_tpu.structs.structs import (
            ALLOC_CLIENT_COMPLETE,
            Allocation,
            AllocatedResources,
            AllocatedSharedResources,
            AllocatedTaskResources,
        )

        store = StateStore()
        node_ids = [generate_uuid() for _ in range(8)]
        idx_lock = threading.Lock()
        idx = [0]

        def next_index():
            with idx_lock:
                idx[0] += 1
                return idx[0]

        stop = threading.Event()
        errors = []

        def writer(wi):
            rng = random.Random(wi)
            mine = []
            try:
                while not stop.is_set():
                    if mine and rng.random() < 0.4:
                        victim = mine.pop(rng.randrange(len(mine)))
                        upd = victim.copy_skip_job()
                        upd.client_status = ALLOC_CLIENT_COMPLETE
                        store.upsert_allocs(next_index(), [upd])
                    else:
                        a = Allocation(
                            job_id=f"w{wi}", task_group="t",
                            node_id=rng.choice(node_ids),
                            allocated_resources=AllocatedResources(
                                tasks={"t": AllocatedTaskResources(
                                    cpu_shares=10, memory_mb=10)},
                                shared=AllocatedSharedResources(disk_mb=1),
                            ),
                        )
                        store.upsert_allocs(next_index(), [a])
                        mine.append(a)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            last = 0
            try:
                for _ in range(40):
                    snap = store.snapshot()
                    assert snap.latest_index >= last
                    last = snap.latest_index
                    per_node = {}
                    for a in snap.allocs():
                        if a.terminal_status():
                            continue
                        u = alloc_usage_vec(a)
                        row = per_node.setdefault(a.node_id, [0.0] * 4)
                        for d in range(4):
                            row[d] += u[d]
                    for nid, row in per_node.items():
                        mrow = snap._node_usage.get(nid, (0.0,) * 4)
                        assert tuple(row) == tuple(mrow), "snapshot mirror drift"
                    for nid, mrow in snap._node_usage.items():
                        if nid not in per_node:
                            assert max(mrow) <= 1e-9, "mirror ghost usage"
                    time.sleep(0.005)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        writers = [threading.Thread(target=writer, args=(wi,), daemon=True)
                   for wi in range(4)]
        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(4)]
        for t in writers + readers:
            t.start()
        for t in readers:
            t.join(timeout=60)
        stop.set()
        for t in writers:
            t.join(timeout=10)
        assert not errors, errors[:3]


class TestPhaseCoverage:
    def test_tracked_phases_cover_worker_busy(self):
        """ISSUE 4 acceptance: at stress scale, the fine phases must
        explain >= 90% of measured worker busy wall time — the self-check
        against round 5's blindness, where the host iterator stack burned
        wall no phase accounted for (coverage ~0.17)."""
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.utils import phases

        server = Server(ServerConfig(
            num_schedulers=4, device_batch=0,
            heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        ))
        server.start()
        try:
            for i in range(32):
                n = mock.node()
                n.name = f"cov-{i}"
                n.compute_class()
                server.raft_apply(NODE_REGISTER, n)

            jobs = []
            for i in range(12):
                j = mock.job()
                j.id = f"cov-{i}"
                j.task_groups[0].count = 20
                j.task_groups[0].tasks[0].resources.cpu = 20
                j.task_groups[0].tasks[0].resources.memory_mb = 32
                jobs.append(j)
            expected = sum(tg.count for j in jobs for tg in j.task_groups)

            phases.enable()
            t0 = phases.now()
            for j in jobs:
                server.register_job(j)
            spin_until(
                lambda: server.fsm.state.count_allocs_desired_run() >= expected,
                timeout=120, msg=f"{expected} placements",
            )
            t1 = phases.now()
            cov = phases.coverage(t0, t1)
            phases.disable()

            assert cov["worker_busy"] > 0, cov
            assert cov["coverage"] >= 0.9, (
                f"fine phases explain only {cov['coverage']:.1%} of worker "
                f"busy wall time: {cov}"
            )
        finally:
            server.stop()


class TestEvalLivenessStress:
    """ISSUE 5 satellite: while the cluster HAS capacity, no eval may sit
    unacked longer than N x the broker's nack timeout — the starvation
    shape where an eval is stuck behind a wedged worker or a batcher that
    never flushes, while nodes idle. The bound is observed through the
    production surface (``nomad.trace.slowest_inflight_ms``, published by
    lifecycle.publish_gauges on the server's stats sweep), not a test-only
    probe: if the gauge can't see the starvation, operators can't either."""

    N_TIMEOUTS = 2  # liveness bound: no eval unacked > N x nack_timeout

    def test_no_eval_starves_while_capacity_exists(self):
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.trace import lifecycle
        from nomad_tpu.utils import metrics

        lifecycle.reset()
        metrics.global_sink().reset()

        server = Server(ServerConfig(
            num_schedulers=4, device_batch=0,
            heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        ))
        # tighten the redelivery clock so the liveness bound bites at test
        # scale (timers read this at dequeue time, so pre-start is safe)
        server.eval_broker.nack_timeout = 5.0
        bound_ms = self.N_TIMEOUTS * server.eval_broker.nack_timeout * 1000.0
        server.start()
        try:
            for i in range(24):
                n = mock.node()
                n.name = f"live-{i}"
                n.compute_class()
                server.raft_apply(NODE_REGISTER, n)

            # 16 jobs x 12 small allocs: comfortably inside 24 mock
            # nodes, so "the cluster has capacity" holds for the whole
            # flood — any gauge spike past the bound is pure starvation
            jobs = []
            for i in range(16):
                j = mock.job()
                j.id = f"live-{i}"
                j.task_groups[0].count = 12
                j.task_groups[0].tasks[0].resources.cpu = 20
                j.task_groups[0].tasks[0].resources.memory_mb = 32
                jobs.append(j)
            expected = sum(tg.count for j in jobs for tg in j.task_groups)

            stop = threading.Event()
            observed = {"max_ms": 0.0, "samples": 0, "busy_samples": 0}

            def sample():
                # the operator's view: publish the sweep gauges and read
                # the slowest-in-flight age back out of the metrics sink
                while not stop.is_set():
                    lifecycle.publish_gauges()
                    g = {g_["Name"]: g_["Value"]
                         for g_ in metrics.global_sink().summary()["Gauges"]}
                    slow = g.get("nomad.trace.slowest_inflight_ms", 0.0)
                    observed["samples"] += 1
                    if g.get("nomad.trace.inflight", 0) > 0:
                        observed["busy_samples"] += 1
                    observed["max_ms"] = max(observed["max_ms"], slow)
                    time.sleep(0.05)

            sampler = threading.Thread(target=sample, daemon=True)
            sampler.start()
            for j in jobs:
                server.register_job(j)
            spin_until(
                lambda: server.fsm.state.count_allocs_desired_run() >= expected,
                timeout=120, msg=f"{expected} placements",
            )
            # drain the tail: placements landed, but acks may still be in
            # flight — the liveness claim covers them too
            spin_until(
                lambda: lifecycle.summary()["inflight"] == 0,
                timeout=60, msg="all evals acked",
            )
            stop.set()
            sampler.join(timeout=10)

            assert observed["busy_samples"] > 0, (
                "gauge sampler never saw an in-flight eval — the test "
                "observed nothing (flood too fast or gauges broken)"
            )
            assert observed["max_ms"] < bound_ms, (
                f"an eval sat unacked {observed['max_ms']:.0f}ms "
                f"(> {self.N_TIMEOUTS} x nack_timeout = {bound_ms:.0f}ms) "
                f"while the cluster had capacity"
            )
            # quiesced: the gauge returns to zero once the flood drains
            lifecycle.publish_gauges()
            g = {g_["Name"]: g_["Value"]
                 for g_ in metrics.global_sink().summary()["Gauges"]}
            assert g["nomad.trace.inflight"] == 0
            assert g["nomad.trace.slowest_inflight_ms"] == 0.0
        finally:
            server.stop()


class TestFlightRecorderOverhead:
    """ISSUE 12 gate, the part a CPU can hold the recorder to: armed at
    its production cadence (250ms) it keeps ticking while the server is
    flooded with evals, every frame carries every probe and its own
    tick_ms, and the critical-path attribution over the same window
    still clears its coverage floor. The share of wall time it costs is
    a host-clock number and belongs to the benchmark's traced run, which
    prints it (PERF.md §5 "Flight recorder")."""

    def test_recorder_ticks_whole_frames_during_eval_flood(self):
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.trace import attribution, lifecycle

        lifecycle.reset()
        server = Server(ServerConfig(
            num_schedulers=4, device_batch=0,
            flight_interval_s=0.25,
            heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        ))
        server.start()
        try:
            spin_until(lambda: server.flight.armed, msg="flight armed")
            for i in range(24):
                n = mock.node()
                n.name = f"fr-{i}"
                n.compute_class()
                server.raft_apply(NODE_REGISTER, n)

            jobs = []
            for i in range(12):
                j = mock.job()
                j.id = f"fr-{i}"
                j.task_groups[0].count = 16
                j.task_groups[0].tasks[0].resources.cpu = 20
                j.task_groups[0].tasks[0].resources.memory_mb = 32
                jobs.append(j)
            expected = sum(tg.count for j in jobs for tg in j.task_groups)
            for j in jobs:
                server.register_job(j)
            spin_until(
                lambda: server.fsm.state.count_allocs_desired_run() >= expected,
                timeout=120, msg=f"{expected} placements",
            )
            # make sure the gate judges LOADED ticks, not just idle ones
            spin_until(lambda: server.flight.overhead()["ticks"] >= 4,
                       timeout=30, msg="flight recorder ticks")
            ov = server.flight.overhead()
            frames = server.flight.frames()
            assert len(frames) >= ov["ticks"] >= 4
            probes = set(frames[-1]["probes"])
            assert {"broker", "plan_queue", "trace", "state"} <= probes
            for f in frames:
                assert set(f["probes"]) == probes, f["seq"]
                errors = {k: v for k, v in f["probes"].items()
                          if isinstance(v, dict) and "error" in v}
                assert not errors, (f["seq"], errors)
                assert f["tick_ms"] > 0, f["seq"]
            assert ov["tick_ms_max"] >= ov["tick_ms_avg"] > 0
            # the window it recorded must also be attributable: a
            # recorder that loses track of the wall is no gate at all
            rep = attribution.bottleneck_report()
            assert rep["makespan_s"] > 0
            assert rep["coverage"] >= 0.9, (
                f"attribution covers only {rep['coverage']:.1%} of the "
                f"flood makespan: {rep['top']}"
            )
        finally:
            server.stop()


class TestBlockingQueryFanout:
    """Fleet-scale client fan-out — hundreds of
    simulated clients holding Node.GetClientAllocs blocking queries
    (state_store.blocking_query, the reference's
    state_store.go:188 / client.go:1873 watch path) while a C1M-shaped
    dense commit storm runs through the plan queue. Asserts bounded
    memory (dense placement blocks are shared + lazily materialized,
    never inflated per watcher) and timely diff delivery (p99 notify
    latency), and RECORDS both."""

    @staticmethod
    def _rss_mb() -> float:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
        return 0.0

    def test_watcher_fanout_under_commit_storm(self):
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.structs.structs import (
            AllocatedResources,
            AllocatedSharedResources,
            AllocatedTaskResources,
            DenseTGPlacements,
            Plan,
            generate_uuids,
        )

        n_nodes = 200
        n_watchers = 1000
        n_plans = 64
        per_plan = 160

        server = Server(ServerConfig(
            num_schedulers=0, device_batch=0,
            heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        ))
        server.start()
        state = server.fsm.state
        try:
            rng = np.random.default_rng(3)
            node_ids = []
            for i in range(n_nodes):
                n = mock.node()
                n.name = f"fan-{i}"
                n.compute_class()
                server.raft_apply(NODE_REGISTER, n)
                node_ids.append(n.id)

            # record commit timestamps: _bump runs under the store lock,
            # so a dict insert is safe and cheap
            bump_times = {}
            orig_bump = state._bump

            def bump_spy(index=None):
                idx = orig_bump(index)
                bump_times[idx] = time.monotonic()
                return idx

            state._bump = bump_spy

            base_index = state.latest_index
            stop = threading.Event()
            latencies = []
            lat_lock = threading.Lock()
            errors = []
            reached = [0] * n_watchers
            target_index = [None]  # set after the storm

            def watcher(wi):
                node_id = node_ids[wi % n_nodes]

                def run(s):
                    # the Node.GetClientAllocs read: the node's allocs,
                    # jobs attached (endpoints.py get_client_allocs)
                    return len(s.allocs_by_node(node_id))

                last = base_index
                try:
                    while not stop.is_set():
                        _n, idx = state.blocking_query(run, last, timeout=1.0)
                        if idx > last:
                            t = bump_times.get(idx)
                            if t is not None and idx > base_index:
                                with lat_lock:
                                    latencies.append(time.monotonic() - t)
                            last = idx
                        reached[wi] = last
                        tgt = target_index[0]
                        if tgt is not None and last >= tgt:
                            return
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            rss_before = self._rss_mb()
            threads = [
                threading.Thread(target=watcher, args=(i,), daemon=True)
                for i in range(n_watchers)
            ]
            for t in threads:
                t.start()

            proto = AllocatedResources(
                tasks={"web": AllocatedTaskResources(cpu_shares=15, memory_mb=30)},
                shared=AllocatedSharedResources(disk_mb=10),
            )

            def mk_plan(k):
                chosen = rng.choice(n_nodes, size=per_plan, replace=True)
                block = DenseTGPlacements(
                    namespace="default", job_id=f"fan-job-{k}",
                    task_group="web", eval_id=f"fan-eval-{k}",
                    resources_proto=proto, ask_vec=(15.0, 30.0, 10.0, 0.0),
                    ids=generate_uuids(per_plan),
                    names=[f"fan-job-{k}.web[{i}]" for i in range(per_plan)],
                    node_ids=[node_ids[j] for j in chosen],
                    node_names=[f"fan-{j}" for j in chosen],
                    scores=[1.0] * per_plan,
                    nodes_evaluated=[1] * per_plan,
                )
                return Plan(eval_id=f"fan-eval-{k}", dense_placements=[block])

            futures = [server.plan_queue.enqueue(mk_plan(k)).future
                       for k in range(n_plans)]
            for f in futures:
                f.result(timeout=120)
            target_index[0] = state.latest_index

            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if all(r >= target_index[0] for r in reached):
                    break
                time.sleep(0.05)
            stop.set()
            for t in threads:
                t.join(timeout=30)
            rss_after = self._rss_mb()

            assert not errors, errors[:3]
            laggards = sum(1 for r in reached if r < target_index[0])
            assert laggards == 0, f"{laggards} watchers never saw the final index"
            assert latencies, "no notify latencies recorded"
            lat_sorted = sorted(latencies)
            p50 = lat_sorted[len(lat_sorted) // 2]
            p99 = lat_sorted[int(len(lat_sorted) * 0.99)]
            grow = rss_after - rss_before
            print(
                f"fanout: {n_watchers} watchers, {n_plans * per_plan} dense "
                f"placements committed; notify p50 {p50*1000:.0f}ms "
                f"p99 {p99*1000:.0f}ms; RSS {rss_before:.0f} -> "
                f"{rss_after:.0f}MB (+{grow:.0f}MB)"
            )
            # timely delivery: diffs reach every watcher well under the
            # blocking-query re-poll interval
            assert p99 < 5.0, f"p99 notify latency {p99:.2f}s"
            # bounded memory: 10K dense placements shared across 1000
            # watchers must not inflate per watcher (a per-watcher copy
            # of materialized allocs would be ~GBs)
            assert grow < 400, f"RSS grew {grow:.0f}MB under fan-out"
        finally:
            state._bump = orig_bump
            server.stop()


class TestLockWitnessStress:
    """nomad-lockdep's dynamic side under full scheduler pressure: arm
    the witness, flood a real server, and require (a) no order
    inversion among the instrumented locks and (b) every witnessed
    acquisition-order edge to be present in the static analyzer's
    whole-program graph — the run is the soundness proof for the static
    pass, and the static pass covers orders the flood didn't hit."""

    def test_witness_armed_flood_is_inversion_free_and_sound(self):
        from nomad_tpu.analysis.lock_order import build_static_graph
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.trace import lifecycle
        from nomad_tpu.utils import lock_witness, metrics

        lifecycle.reset()
        metrics.global_sink().reset()
        witness = lock_witness.arm()
        try:
            # constructed AFTER arming, so every factory-created lock in
            # the server tree is instrumented
            server = Server(ServerConfig(
                num_schedulers=4, device_batch=0,
                heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
            ))
            server.start()
            try:
                for i in range(12):
                    n = mock.node()
                    n.name = f"witness-{i}"
                    n.compute_class()
                    server.raft_apply(NODE_REGISTER, n)
                jobs = []
                for i in range(8):
                    j = mock.job()
                    j.id = f"witness-{i}"
                    j.task_groups[0].count = 8
                    j.task_groups[0].tasks[0].resources.cpu = 20
                    j.task_groups[0].tasks[0].resources.memory_mb = 32
                    jobs.append(j)
                expected = sum(tg.count for j in jobs for tg in j.task_groups)
                for j in jobs:
                    server.register_job(j)
                spin_until(
                    lambda: server.fsm.state.count_allocs_desired_run()
                    >= expected,
                    timeout=120, msg=f"{expected} witnessed placements",
                )
            finally:
                server.stop()

            stats = witness.stats()
            assert stats["violations"] == 0
            # the flood must actually exercise nested acquisition — a
            # zero-edge run would vacuously "prove" soundness
            assert stats["acquisitions"] > 1000, stats
            assert stats["edges"] > 0, stats
            missing = witness.cross_check(build_static_graph())
            assert not missing, (
                "runtime lock orders the static lock-order graph never "
                f"derived (static-analysis unsoundness): {missing}"
            )
        finally:
            lock_witness.disarm()

    def test_race_witness_armed_flood_is_race_free_and_sound(self):
        """nomad-race's dynamic side under the same eval flood: arm the
        Eraser lockset witness, flood a real server, and require (a) no
        empty-lockset violation on any tracked hot field and (b) every
        field RUNTIME-witnessed as cross-thread shared to be in the
        static analyzer's inferred-shared set — the soundness proof for
        shared-state-discipline's thread-root inventory."""
        from nomad_tpu.analysis.shared_state import build_static_shared
        from nomad_tpu.rpc import transport
        from nomad_tpu.server.fsm import NODE_REGISTER
        from nomad_tpu.server.server import Server, ServerConfig
        from nomad_tpu.trace import lifecycle
        from nomad_tpu.utils import lock_witness, metrics, race_witness

        metrics.global_sink().reset()
        witness = race_witness.arm()  # auto-arms the lock witness
        try:
            # module tables re-mint through the tracked factories only
            # AFTER arming — the import-time ones predate the witness
            lifecycle.reset()
            transport.reset_rpc_stats()
            server = Server(ServerConfig(
                num_schedulers=4, device_batch=0,
                heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
            ))
            server.start()
            try:
                for i in range(12):
                    n = mock.node()
                    n.name = f"race-{i}"
                    n.compute_class()
                    server.raft_apply(NODE_REGISTER, n)
                jobs = []
                for i in range(8):
                    j = mock.job()
                    j.id = f"race-{i}"
                    j.task_groups[0].count = 8
                    j.task_groups[0].tasks[0].resources.cpu = 20
                    j.task_groups[0].tasks[0].resources.memory_mb = 32
                    jobs.append(j)
                expected = sum(tg.count for j in jobs for tg in j.task_groups)
                for j in jobs:
                    server.register_job(j)
                spin_until(
                    lambda: server.fsm.state.count_allocs_desired_run()
                    >= expected,
                    timeout=120, msg=f"{expected} raced placements",
                )
            finally:
                server.stop()

            stats = witness.stats()
            assert stats["violations"] == 0, witness.field_report()
            # the flood must actually drive the tracked hot fields from
            # concurrent threads — a zero-access run proves nothing
            assert stats["accesses"] > 100, stats
            assert stats["shared_fields"] > 0, stats
            missing = witness.cross_check(build_static_shared())
            assert not missing, (
                "runtime-witnessed shared fields the static root "
                f"inventory never inferred as concurrent: {missing}"
            )
        finally:
            race_witness.disarm()
