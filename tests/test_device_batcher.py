"""Eval-batched device scheduling (SURVEY §2.6 row 1).

Covers the production path the reference realizes as N scheduler workers
per server (nomad/server.go:1307): here, concurrent evals' placement scans
share ONE device dispatch through tpu.batcher.DeviceBatcher. Parity is the
bar: the batched scan must produce bit-identical selections to the
single-eval scan, and batcher-routed scheduling must produce identical
plans to the host pipeline.
"""
import copy
import random
import re
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    Affinity,
    Constraint,
    Evaluation,
    PreemptionConfig,
    SchedulerConfiguration,
    Spread,
    SpreadTarget,
)
from nomad_tpu.tpu import wire
from nomad_tpu.tpu.batcher import DeviceBatcher, pad_encoded, _pow2ceil
from nomad_tpu.tpu.engine import (
    EncodedEval,
    TpuPlacementEngine,
    example_scan_inputs,
)


def synthetic_enc(n_nodes, n_tgs, n_placements, n_spreads=1, seed=0,
                  dtype=np.float64):
    n_pad, static, carry, xs = example_scan_inputs(
        n_nodes=n_nodes, n_tgs=n_tgs, n_placements=n_placements,
        n_spreads=n_spreads, seed=seed, dtype=dtype,
    )
    return EncodedEval(
        n_real=n_nodes, n_pad=n_pad, g=n_tgs, s=static[9].shape[1],
        v=static[10].shape[2], p=n_placements, dtype=dtype,
        static=static, carry=carry, xs=xs,
        missing_list=[], nodes=[], table=None, start_ns=0,
    )


def run_concurrent(batcher, encs):
    results = [None] * len(encs)
    errors = []

    def submit(i):
        try:
            results[i] = batcher.run(encs[i])
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submit, args=(i,))
               for i in range(len(encs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    return results


class TestBatchedScanParity:
    def test_heterogeneous_batch_matches_single(self):
        """Evals of different node counts, TG counts, placement counts and
        spread shapes padded into one batch must each produce exactly the
        single-eval scan's output (padding is semantically inert)."""
        engine = TpuPlacementEngine.shared()
        encs = [
            synthetic_enc(17, 1, 3, n_spreads=0, seed=1),
            synthetic_enc(64, 3, 16, n_spreads=1, seed=2),
            synthetic_enc(33, 2, 7, n_spreads=2, seed=3),
            synthetic_enc(8, 1, 1, n_spreads=0, seed=4),
            synthetic_enc(50, 4, 11, n_spreads=1, seed=5),
        ]
        singles = [engine.run_scan_single(e) for e in encs]

        batcher = DeviceBatcher(max_batch=len(encs), window_ms=200.0)
        try:
            batched = run_concurrent(batcher, encs)
        finally:
            batcher.stop()

        assert batcher.stats["max_batch_seen"] == len(encs)
        assert batcher.stats["dispatches"] == 1
        for i, (single, batch_r) in enumerate(zip(singles, batched)):
            for k, name in enumerate(("chosen", "scores", "pulls", "skipped")):
                np.testing.assert_array_equal(
                    np.asarray(single[k]), np.asarray(batch_r[k]),
                    err_msg=f"eval {i} {name} diverged under batching",
                )

    def test_uneven_batch_padding(self):
        """3 evals -> batch padded to 4; the inert pad copy must not
        perturb real results."""
        engine = TpuPlacementEngine.shared()
        encs = [synthetic_enc(24, 2, 5, seed=s) for s in (7, 8, 9)]
        singles = [engine.run_scan_single(e) for e in encs]
        batcher = DeviceBatcher(max_batch=8, window_ms=200.0)
        try:
            batched = run_concurrent(batcher, encs)
        finally:
            batcher.stop()
        # multi-eval batches pad to max_batch (two compile buckets total:
        # b=1 and b=max — every intermediate pow2 was its own slow compile)
        assert batcher.stats["padded_evals"] == 5  # 3 -> max_batch 8
        for single, batch_r in zip(singles, batched):
            np.testing.assert_array_equal(single[0], batch_r[0])
            np.testing.assert_array_equal(single[1], batch_r[1])

    def test_pad_encoded_shapes(self):
        enc = synthetic_enc(10, 2, 4, n_spreads=1, seed=0)
        static, carry, xs, p_real = pad_encoded(
            enc, n_pad=32, g_pad=4, s_pad=2, v_pad=8, p_pad=8,
            dtype=np.float64,
        )
        d = enc.static[0].shape[1]  # per-job capacity dims (4 + devices)
        assert static[0].shape == (32, d)          # totals
        assert static[3].shape == (4, 32)          # feat_packed (uint8 lanes)
        assert static[9].shape == (4, 2, 32)       # spread_vids
        assert static[10].shape == (4, 2, 8)       # spread_desired
        assert carry[6].shape == (4,)              # failed
        assert carry[6][enc.g:].all()              # padded TGs pre-failed
        assert xs[0].shape == (8,)
        # padded steps point at no pre-failed slot: the device masks them
        # by index, from the eval's own count on
        assert (xs[0][enc.p:] == 0).all()
        assert p_real == enc.p and p_real.dtype == np.int32
        # remapped invalid vocab bucket
        assert (static[9] <= 7).all()
        assert (static[9][:, :, enc.n_pad:] == 7).all()

    def test_mixed_capacity_dims_batch(self):
        """A device job (6 capacity dims) co-batched with deviceless jobs
        (4 dims): D pads across the batch and results stay identical to
        the single-eval scans."""
        import numpy as np

        engine = TpuPlacementEngine.shared()
        lean = synthetic_enc(24, 2, 5, seed=31)
        assert lean.static[0].shape[1] == 4
        # widen one eval to 6 dims manually (as a device job encodes)
        from nomad_tpu.tpu.engine import example_scan_inputs

        n_pad, st, ca, xs = example_scan_inputs(
            n_nodes=24, n_tgs=2, n_placements=5, seed=32,
            dtype=np.float64, num_dims=6,
        )
        st = list(st)
        st[0][:, 4] = 2.0  # 2 free devices per node on dim 4
        st[2][:, 4] = 1.0  # each placement takes one
        wide = EncodedEval(
            n_real=24, n_pad=n_pad, g=2, s=st[9].shape[1],
            v=st[10].shape[2], p=5, dtype=np.float64,
            static=tuple(st), carry=ca, xs=xs,
            missing_list=[], nodes=[], table=None, start_ns=0,
        )
        singles = [engine.run_scan_single(e) for e in (lean, wide)]
        batcher = DeviceBatcher(max_batch=2, window_ms=200.0)
        try:
            batched = run_concurrent(batcher, [lean, wide])
        finally:
            batcher.stop()
        for single, batch_r in zip(singles, batched):
            np.testing.assert_array_equal(single[0], batch_r[0])
            np.testing.assert_array_equal(single[1], batch_r[1])

    def test_mesh_sharded_batch_matches_single(self):
        """The mesh-sharded dispatch (production multi-chip path) is
        bit-identical to the unsharded single scan."""
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs >=4 devices")
        from nomad_tpu.parallel import make_mesh

        engine = TpuPlacementEngine.shared()
        encs = [synthetic_enc(32, 2, 6, seed=s) for s in (11, 12)]
        singles = [engine.run_scan_single(e) for e in encs]
        mesh = make_mesh(4, eval_parallel=2)
        batcher = DeviceBatcher(max_batch=4, window_ms=200.0, mesh=mesh)
        try:
            batched = run_concurrent(batcher, encs)
        finally:
            batcher.stop()
        for single, batch_r in zip(singles, batched):
            np.testing.assert_array_equal(single[0], batch_r[0])
            np.testing.assert_array_equal(single[1], batch_r[1])

    @pytest.mark.parametrize("n_nodes", [32, 512],
                             ids=["unfolded", "folded"])
    def test_mesh_sharded_lone_wave_matches_single(self, n_nodes):
        """One eval alone on a mesh whose "evals" axis is 1 takes the
        program without a batch axis: the axis comes off (and 512 nodes
        fold to four lane rows) inside the program, after the shardings,
        and the result is the unsharded single scan's."""
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs >=4 devices")
        from nomad_tpu.parallel import make_mesh

        enc = synthetic_enc(n_nodes, 2, 12, n_spreads=1, seed=21,
                            dtype=np.int32)
        single = TpuPlacementEngine.shared().run_scan_single(enc)
        batcher = DeviceBatcher(max_batch=4, window_ms=200.0,
                                mesh=make_mesh(4, eval_parallel=1))
        try:
            (batch_r,) = run_concurrent(batcher, [enc])
        finally:
            batcher.stop()
        assert batcher.stats["lone_dispatches"] == 1
        for k, name in enumerate(("chosen", "scores", "pulls", "skipped")):
            np.testing.assert_array_equal(
                np.asarray(single[k]), np.asarray(batch_r[k]), err_msg=name)

    def test_mesh_sharded_c1m_slice_bit_identical(self):
        """A C1M-shaped slice — exact INT spec, DISTINCT
        per-eval inputs, batch sharded over the full ("evals","nodes")
        mesh — must be bitwise identical to the unsharded single-eval
        scans on one device. This is the correctness evidence for the
        production multi-chip dispatch: a shard permutation or wrong-axis
        bug cannot hide behind identical inputs or float tolerance."""
        import jax

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        from nomad_tpu.parallel import make_mesh

        engine = TpuPlacementEngine.shared()
        # C1M shape, scaled: many nodes relative to devices (node axis
        # shards 512/4 = 128 per device), 2 TGs, spreads active, int32
        encs = [
            synthetic_enc(512, 2, 48, n_spreads=1, seed=100 + s,
                          dtype=np.int32)
            for s in range(4)
        ]
        singles = [engine.run_scan_single(e) for e in encs]
        mesh = make_mesh(8, eval_parallel=2)  # ("evals": 2, "nodes": 4)
        batcher = DeviceBatcher(max_batch=4, window_ms=500.0, mesh=mesh)
        try:
            batched = run_concurrent(batcher, encs)
        finally:
            batcher.stop()
        assert batcher.stats["dispatches"] == 1
        for i, (single, batch_r) in enumerate(zip(singles, batched)):
            for k, name in enumerate(("chosen", "scores", "pulls", "skipped")):
                np.testing.assert_array_equal(
                    np.asarray(single[k]), np.asarray(batch_r[k]),
                    err_msg=(
                        f"eval {i} {name}: sharded dispatch diverged from "
                        "the single-device oracle"
                    ),
                )

    def test_stop_errors_parked_requests(self):
        """stop() must release requests already sitting in the queue (a
        worker parked in run()) with an error, not leave them hanging."""
        from nomad_tpu.tpu.batcher import _Request

        batcher = DeviceBatcher(max_batch=4, window_ms=50.0)
        # park a request WITHOUT a dispatcher thread running
        req = _Request(synthetic_enc(8, 1, 1, seed=0))
        batcher._pending.append(req)
        batcher.stop()
        assert req.event.is_set()
        assert isinstance(req.error, RuntimeError)

    def test_run_after_stop_restarts_lazily(self):
        batcher = DeviceBatcher(max_batch=4, window_ms=50.0)
        batcher._ensure_started()
        batcher.stop()
        # run() restarts the dispatcher lazily; never deadlocks
        out = batcher.run(synthetic_enc(8, 1, 1, seed=0))
        assert out[0].shape == (1,)
        batcher.stop()

    def test_failed_batch_falls_back_per_eval(self, caplog):
        """A poisoned co-batched eval must not fail its companions: the
        dispatcher retries each request through the single-eval scan —
        counted and logged at warning, never silently."""
        good = synthetic_enc(16, 1, 2, seed=0)
        bad = synthetic_enc(16, 1, 2, seed=1)
        # corrupt one eval so the batched dispatch raises (the packer
        # counts its arrays inside _run_batch)
        bad.static = bad.static[:-1]  # drop n_real -> unzips wrong
        batcher = DeviceBatcher(max_batch=2, window_ms=200.0)
        try:
            results = [None, None]
            errors = [None, None]

            def submit(i, enc):
                try:
                    results[i] = batcher.run(enc)
                except BaseException as e:  # noqa: BLE001
                    errors[i] = e

            t0 = threading.Thread(target=submit, args=(0, good))
            t1 = threading.Thread(target=submit, args=(1, bad))
            with caplog.at_level("WARNING", logger="nomad_tpu.tpu.batcher"):
                t0.start(); t1.start(); t0.join(); t1.join()
            assert results[0] is not None, f"good eval failed: {errors[0]}"
            assert errors[1] is not None, "corrupt eval should error"
            assert batcher.stats["batch_fallbacks"] == 1
            warned = [r for r in caplog.records
                      if "batched dispatch failed" in r.getMessage()]
            assert len(warned) == 1 and warned[0].levelname == "WARNING"
            assert warned[0].exc_info is not None
        finally:
            batcher.stop()

    def test_failed_prewarm_is_counted(self, caplog):
        """A sibling bucket whose background compile fails must not fail
        the dispatch that triggered it, and must not be forgotten: it is
        counted and logged at warning."""
        batcher = DeviceBatcher(max_batch=4, window_ms=5.0)
        real_scan = batcher._scan_fn()

        def scan(layout, *buffers):
            if layout.b_pad != 1:  # every sibling bucket (b=4)
                raise RuntimeError("compile refused")
            return real_scan(layout, *buffers)

        batcher._scan = scan
        try:
            with caplog.at_level("WARNING", logger="nomad_tpu.tpu.batcher"):
                out = batcher.run(synthetic_enc(8, 1, 1, seed=0))
                batcher.wait_warm(timeout=30)
            assert out[0].shape == (1,)
            assert batcher.stats["prewarm_failures"] == 1
            assert batcher.stats["batch_fallbacks"] == 0
            assert any("bucket prewarm failed" in r.getMessage()
                       and r.levelname == "WARNING" for r in caplog.records)
        finally:
            batcher.stop()


def make_nodes(num, seed):
    rng = random.Random(seed)
    nodes = []
    for i in range(num):
        n = mock.node()
        n.name = f"node-{i}"
        n.node_resources.cpu_shares = rng.choice([2000, 4000, 8000])
        n.node_resources.memory_mb = rng.choice([4096, 8192, 16384])
        n.attributes["rack"] = f"r{rng.randint(0, 3)}"
        n.compute_class()
        nodes.append(n)
    return nodes


def scheduler_plans(nodes, jobs, batcher=None):
    """Run jobs through the harness under tpu_binpack; return
    {(job, alloc name) -> node id} placements."""
    h = Harness()
    if batcher is not None:
        h.device_batcher = batcher
    h.state.scheduler_set_config(
        h.next_index(), SchedulerConfiguration(scheduler_algorithm="tpu_binpack")
    )
    for n in nodes:
        h.state.upsert_node(h.next_index(), copy.deepcopy(n))
    for job in jobs:
        h.state.upsert_job(h.next_index(), copy.deepcopy(job))
    for job in jobs:
        ev = Evaluation(
            priority=job.priority, type=job.type,
            triggered_by=EVAL_TRIGGER_JOB_REGISTER,
            job_id=job.id, namespace=job.namespace,
        )
        h.process("service", ev)
    out = {}
    for plan in h.plans:
        for node_id, allocs in plan.node_allocation.items():
            for a in allocs:
                out[(a.job_id, a.name)] = node_id
    return out


class TestSchedulerThroughBatcher:
    def test_real_scheduler_plans_identical_via_batcher(self):
        """Full scheduler pipeline routed through the DeviceBatcher yields
        the same plans as the direct single-dispatch engine path."""
        nodes = make_nodes(30, seed=42)
        jobs = []
        for i in range(4):
            job = mock.job()
            job.id = f"job-batch-{i}"
            job.task_groups[0].count = 3
            if i % 2:
                job.task_groups[0].spreads = [Spread(
                    attribute="${meta.rack}", weight=50,
                    spread_target=[SpreadTarget(value="r0", percent=50),
                                   SpreadTarget(value="r1", percent=50)],
                )]
            jobs.append(job)

        direct = scheduler_plans(nodes, jobs, batcher=None)
        batcher = DeviceBatcher(max_batch=4, window_ms=5.0)
        try:
            via_batcher = scheduler_plans(nodes, jobs, batcher=batcher)
        finally:
            batcher.stop()
        assert direct == via_batcher
        assert len(via_batcher) == sum(j.task_groups[0].count for j in jobs)
        assert batcher.stats["evals"] == len(jobs)


class TestServerBatchedScheduling:
    def test_concurrent_evals_share_device_dispatch(self):
        """N concurrent evals on a running server are placed via fewer
        device dispatches than evals (the production wiring of SURVEY
        §2.6 row 1), with every allocation placed."""
        from nomad_tpu.server.server import Server, ServerConfig

        server = Server(ServerConfig(
            num_schedulers=0, device_batch=8, device_batch_window_ms=25.0,
            device_min_placements=0,  # this test asserts device dispatch
        ))
        try:
            server.start()
            for i in range(12):
                n = mock.node()
                n.name = f"srv-node-{i}"
                n.compute_class()
                server.register_node(n)

            # enqueue all evals BEFORE workers exist so the flood hits the
            # broker at once (deterministic batching pressure)
            jobs = []
            for i in range(8):
                job = mock.job()
                job.id = f"batched-job-{i}"
                job.task_groups[0].count = 2
                jobs.append(job)
                server.register_job(job)

            from nomad_tpu.server.worker import Worker

            for i in range(4):
                w = Worker(server, i)
                server.workers.append(w)
                w.start()

            deadline = time.monotonic() + 30
            def placed():
                return sum(
                    1 for j in jobs
                    for a in server.fsm.state.allocs_by_job("default", j.id, True)
                )
            while time.monotonic() < deadline and placed() < 16:
                time.sleep(0.05)
            assert placed() == 16, f"only {placed()}/16 allocs placed"

            stats = server.device_batcher.stats
            assert stats["evals"] >= 8
            assert stats["max_batch_seen"] >= 2, (
                f"no eval batching observed: {stats}"
            )
            assert stats["dispatches"] < stats["evals"], stats
        finally:
            server.stop()


def _warm(batcher, announce=True):
    """Compile outside what a case times; arm the demand-aware gather
    the way a server's first eval does."""
    if announce:
        batcher.expect()
    batcher.run(synthetic_enc(32, 1, 4, seed=0), expected=announce)


def _submit_later(batcher, enc, delay_s, hold=None, errors=None):
    """A worker's thread: announced already, it arrives after ``delay_s``
    (under ``hold``, a permit, when given)."""
    def go():
        try:
            if hold is not None:
                with hold:
                    time.sleep(delay_s)
            else:
                time.sleep(delay_s)
            batcher.run(enc, expected=True)
        except BaseException as e:  # noqa: BLE001
            if errors is not None:
                errors.append(e)
            raise

    t = threading.Thread(target=go)
    t.start()
    return t


def _join_all(threads, timeout=60.0):
    for t in threads:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in threads)


def _case_lone_eval_with_nothing_announced_goes_at_once():
    batcher = DeviceBatcher(max_batch=8, window_ms=10_000.0)
    try:
        _warm(batcher)
        batcher.run(synthetic_enc(32, 1, 4, seed=1))   # e.g. the redispatcher
        batcher.expect()
        batcher.run(synthetic_enc(32, 1, 4, seed=2), expected=True)
        for d in _dispatches_of(batcher)[-2:]:
            assert (d["closed_by"], d["b"]) == ("nothing_announced", 1)
            # no wait at all: an order of magnitude under the window
            assert d["t_start"] - d["t_first_enqueue"] < 1.0
        with batcher._lock:
            assert batcher._expected == 0
            assert batcher.stats["gathers_held"] == 0
            assert batcher.stats["gather_held_ms_total"] == 0.0
    finally:
        batcher.stop()


def _first_of_a_held_gather(batcher, enc, left):
    """Submit ``enc`` (announced) from a thread and return once the
    dispatcher has taken it and holds the gather for the ``left`` evals
    still announced."""
    t = _submit_later(batcher, enc, 0.0)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        with batcher._lock:
            if batcher._expected == left and not batcher._pending:
                return t
        time.sleep(0.001)
    raise AssertionError("the first eval never reached the gather")


def _case_two_announced_50ms_apart_share_one_dispatch():
    batcher = DeviceBatcher(max_batch=8, window_ms=10_000.0)
    try:
        _warm(batcher)
        d0 = len(_dispatches_of(batcher))
        batcher.expect(2)
        first = _first_of_a_held_gather(
            batcher, synthetic_enc(32, 1, 4, seed=3), left=1)
        time.sleep(0.05)
        t0 = time.monotonic()
        batcher.run(synthetic_enc(32, 1, 4, seed=4), expected=True)
        waited = time.monotonic() - t0
        _join_all([first])
        (d,) = _dispatches_of(batcher)[d0:]
        assert (d["closed_by"], d["b"]) == ("demand_drained", 2)
        # held for the second eval, and not a moment after it arrived
        assert 0.04 <= d["t_start"] - d["t_first_enqueue"] < 5.0
        assert waited < 5.0                     # the window is 10 s
        with batcher._lock:
            assert batcher._expected == 0
            assert batcher.stats["gathers_held"] == 1
            assert 40.0 <= batcher.stats["gather_held_ms_total"] < 5000.0
    finally:
        batcher.stop()


def _case_cohort_staggered_by_a_permit_is_one_dispatch():
    """Eight workers announce together, then pass one permit holding it
    20 ms each (the snapshot's host-work permit, exaggerated): the first
    arrives 140 ms before the last and the gather holds for all."""
    batcher = DeviceBatcher(max_batch=8, window_ms=10_000.0)
    try:
        _warm(batcher)
        d0 = len(_dispatches_of(batcher))
        permit = threading.Semaphore(1)
        errors = []
        batcher.expect(8)
        _join_all([
            _submit_later(batcher, synthetic_enc(32, 1, 4, seed=10 + i), 0.02,
                          hold=permit, errors=errors)
            for i in range(8)
        ])
        assert not errors, errors
        (d,) = _dispatches_of(batcher)[d0:]
        assert (d["closed_by"], d["b"]) == ("full", 8)
        assert d["t_start"] - d["t_first_enqueue"] >= 0.1
        with batcher._lock:
            assert batcher._expected == 0
    finally:
        batcher.stop()


def _case_withdrawal_wakes_a_holding_gather():
    batcher = DeviceBatcher(max_batch=8, window_ms=10_000.0)
    try:
        _warm(batcher)
        batcher.expect(2)
        first = _first_of_a_held_gather(
            batcher, synthetic_enc(32, 1, 4, seed=5), left=1)
        time.sleep(0.05)
        t0 = time.monotonic()
        batcher.cancel_expected()
        _join_all([first])
        # closed by the wake: a small multiple of it, not a poll's or the
        # window's end (10 s)
        assert time.monotonic() - t0 < 5.0
        d = _dispatches_of(batcher)[-1]
        assert (d["closed_by"], d["b"]) == ("demand_drained", 1)
        assert 0.04 <= d["t_start"] - d["t_first_enqueue"] < 5.0
        with batcher._lock:
            assert batcher._expected == 0
    finally:
        batcher.stop()


def _case_a_later_announcement_rides_the_next_wave():
    """A gather waits for the evals announced before it took its first
    request, not for one announced since: otherwise, once evals take
    longer to arrive than they are apart, every arrival finds a newer
    announcement and one gather holds until the window's end."""
    batcher = DeviceBatcher(max_batch=8, window_ms=10_000.0)
    try:
        _warm(batcher)
        d0 = len(_dispatches_of(batcher))
        batcher.expect(2)
        first = _first_of_a_held_gather(
            batcher, synthetic_enc(32, 1, 4, seed=7), left=1)
        batcher.expect()                        # announced while it holds
        batcher.run(synthetic_enc(32, 1, 4, seed=8), expected=True)
        _join_all([first], timeout=5.0)         # the window is 10 s
        (d,) = _dispatches_of(batcher)[d0:]
        assert (d["closed_by"], d["b"]) == ("demand_drained", 2)
        with batcher._lock:
            assert batcher._expected == 1
        batcher.run(synthetic_enc(32, 1, 4, seed=9), expected=True)
        d = _dispatches_of(batcher)[-1]
        assert (d["closed_by"], d["b"]) == ("nothing_announced", 1)
        with batcher._lock:
            assert batcher._expected == 0
    finally:
        batcher.stop()


def _case_never_announced_to_keeps_the_fixed_window():
    batcher = DeviceBatcher(max_batch=8, window_ms=1000.0)
    try:
        _warm(batcher, announce=False)
        d0 = len(_dispatches_of(batcher))
        encs = [synthetic_enc(32, 1, 4, seed=20 + i) for i in range(3)]
        late = threading.Thread(
            target=lambda: (time.sleep(0.1), batcher.run(encs[2])))
        late.start()
        run_concurrent(batcher, encs[:2])
        _join_all([late])
        (d,) = _dispatches_of(batcher)[d0:]
        assert (d["closed_by"], d["b"]) == ("window", 3)
        assert d["t_start"] - d["t_first_enqueue"] >= 0.9
    finally:
        batcher.stop()


def _case_a_raise_in_run_releases_the_token():
    from nomad_tpu.chaos.injector import ChaosFault, ChaosInjector

    batcher = DeviceBatcher(max_batch=8, window_ms=10_000.0)
    try:
        _warm(batcher)
        batcher.expect()
        inj = ChaosInjector(seed=1)
        inj.arm("device_dispatch", mode="fail", prob=1.0)
        try:
            with pytest.raises(ChaosFault):
                batcher.run(synthetic_enc(32, 1, 4, seed=6), expected=True)
        finally:
            inj.disarm_all()
        with batcher._lock:
            assert batcher._expected == 0
    finally:
        batcher.stop()


@pytest.mark.parametrize("case", [
    _case_lone_eval_with_nothing_announced_goes_at_once,
    _case_two_announced_50ms_apart_share_one_dispatch,
    _case_cohort_staggered_by_a_permit_is_one_dispatch,
    _case_withdrawal_wakes_a_holding_gather,
    _case_a_later_announcement_rides_the_next_wave,
    _case_never_announced_to_keeps_the_fixed_window,
    _case_a_raise_in_run_releases_the_token,
], ids=lambda f: f.__name__.replace("_case_", ""))
def test_gather_closes_on_announced_demand(case):
    case()


class TestGatherGauges:
    def test_gather_wait_gauge_published(self):
        """The gather-wait latency gauge reaches /v1/metrics via the
        server's stats sweep (nomad.device_batcher.* namespace)."""
        from nomad_tpu.server.server import Server, ServerConfig

        server = Server(ServerConfig(
            num_schedulers=0, device_batch=4,
            heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        ))
        server.start()
        try:
            assert server.device_batcher is not None
            assert "gather_wait_ms_max" in server.device_batcher.stats
            from nomad_tpu.utils import metrics as m

            server.publish_stats_gauges()
            data = m.global_sink().summary()
            gauges = {g["Name"] for g in data.get("Gauges", [])}
            assert any(
                name.startswith("nomad.device_batcher.gather_wait_ms")
                for name in gauges
            ), sorted(n for n in gauges if "batcher" in n)
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# per-dispatch records (trace/lifecycle's ring), the gather's phase, the
# scan's scope names
# ---------------------------------------------------------------------------

DISPATCH_STAMPS = ("t_first_enqueue", "t_start", "t_stack", "t_called",
                   "t_ready", "t_host", "t_handed")


def _dispatches_of(batcher):
    from nomad_tpu.trace import lifecycle

    return [d for d in lifecycle.dispatch_records()
            if d["batcher"] == batcher._serial]


def _case_record_stamps_monotone_and_steps_padded():
    batcher = DeviceBatcher(max_batch=4, window_ms=200.0)
    try:
        encs = [synthetic_enc(40, 2, 5, seed=1), synthetic_enc(64, 1, 20, seed=2),
                synthetic_enc(16, 3, 9, seed=3)]
        run_concurrent(batcher, encs)
        recs = _dispatches_of(batcher)
        assert sum(d["b"] for d in recs) == 3
        for d in recs:
            stamps = [d[k] for k in DISPATCH_STAMPS]
            assert stamps == sorted(stamps), d
            # the device ran the wave's longest eval and no further
            assert d["n_steps"] <= d["p_pad"] == 64
            assert 0 < d["steps"] <= d["padded_steps"] == d["b_pad"] * d["n_steps"]
            assert d["b"] <= d["b_pad"] and d["n_pad"] >= 64
            assert d["d2h_bytes"] > 0 and d["closed_by"] in (
                "full", "window", "nothing_announced", "demand_drained")
        assert sum(d["steps"] for d in recs) == 5 + 20 + 9
        assert max(d["n_steps"] for d in recs) == 20
        if len(recs) == 1:
            assert recs[0]["padded_steps"] == 4 * 20
        with batcher._lock:
            stats = dict(batcher.stats)
        assert stats["steps"] == 34
        assert stats["padded_steps"] == sum(d["padded_steps"] for d in recs)
        waves = [d["wave"] for d in recs]
        assert waves == sorted(set(waves))
    finally:
        batcher.stop()


def _case_gather_phase_covers_a_held_gather():
    from nomad_tpu.utils import phases

    batcher = DeviceBatcher(max_batch=4, window_ms=60.0)
    try:
        batcher.run(synthetic_enc(8, 1, 1, seed=0))   # compile, thread up
        phases.enable()
        t0 = phases.now()
        batcher.run(synthetic_enc(8, 1, 1, seed=0))
        shares = phases.wall_shares(t0, phases.now())
        phases.disable()
        d = _dispatches_of(batcher)[-1]
        assert d["closed_by"] == "window" and d["b"] == 1
        assert d["t_start"] - d["t_first_enqueue"] >= 0.055
        assert shares["gather"] == pytest.approx(
            d["t_start"] - d["t_first_enqueue"], abs=0.002)
        # a wait: named, and neither busy nor host work
        assert shares["busy"] < shares["gather"]
        assert shares["any_host"] <= shares["busy"]
    finally:
        phases.disable()
        batcher.stop()


def _case_full_gather_closes_at_once():
    batcher = DeviceBatcher(max_batch=2, window_ms=2000.0)
    try:
        run_concurrent(batcher, [synthetic_enc(8, 1, 2, seed=4),
                                 synthetic_enc(8, 1, 3, seed=5)])
        d = _dispatches_of(batcher)[-1]
        assert (d["closed_by"], d["b"], d["b_pad"]) == ("full", 2, 2)
        assert d["t_start"] - d["t_first_enqueue"] < 1.0
    finally:
        batcher.stop()


def _case_waits_and_device_brackets_left_out_of_the_unions():
    from nomad_tpu.utils import phases

    phases.enable()
    try:
        t = phases.now()
        phases.record("no_ready_eval", t, t + 1.0)
        phases.record("gather", t + 1.0, t + 2.0)
        phases.record("h2d_launch", t + 2.0, t + 2.1)
        phases.record("kernel_wait", t + 2.1, t + 2.2)
        phases.record("d2h", t + 2.2, t + 2.3)
        phases.record("encode", t + 2.3, t + 2.5)
        shares = phases.wall_shares(t, t + 3.0)
    finally:
        phases.disable()
    assert shares["no_ready_eval"] == 1.0 and shares["gather"] == 1.0
    assert shares["busy"] == pytest.approx(0.5)       # three legs + encode
    assert shares["any_host"] == pytest.approx(0.2)   # encode alone
    assert shares["untracked"] == pytest.approx(2.5)
    # disabled: record keeps nothing
    phases.record("gather", t, t + 9.0)
    assert phases.wall_shares(t, t + 9.0)["gather"] == 1.0


def _case_scan_is_jit_body_and_carries_scope_names():
    from nomad_tpu.tpu.engine import _build_batched_scan

    enc = synthetic_enc(64, 2, 16, seed=7, dtype=np.float32)
    stacked = [tuple(np.stack([np.asarray(a)] * 2) for a in part)
               for part in (enc.static, enc.carry, enc.xs)]
    lowered = _build_batched_scan().lower(
        *stacked, np.full(2, enc.p, np.int32))
    # benchmark/harness/scan.py finds the program by this name
    assert lowered.as_text().splitlines()[0].startswith("module @jit_body")
    debug = lowered.as_text(debug_info=True)
    for scope in ("row_select", "feasibility", "affinity", "spread",
                  "binpack_score", "score_mean", "select", "carry_update"):
        # e.g. loc("jit(body)/while/body/vmap(row_select)/...")
        assert re.search(rf'loc\("[^"]*\b{scope}\b', debug), scope
    # metadata only: no scope name reaches the program text itself
    assert "binpack_score" not in lowered.as_text()


def _case_lone_dispatch_is_recorded_with_the_fields_it_has():
    from nomad_tpu.trace import lifecycle

    lifecycle.reset()
    enc = synthetic_enc(16, 1, 4, seed=9)
    TpuPlacementEngine.shared().run_scan_single(enc)
    d = lifecycle.dispatch_records()[-1]
    assert (d["source"], d["b"], d["steps"], d["eval_ids"]) == (
        "single", 1, 4, [])
    assert d["t_stack"] <= d["t_called"] <= d["t_host"]
    assert d["t_ready"] is None and d["t_first_enqueue"] is None


def _case_a_dispatch_crosses_the_boundary_once_each_way():
    """Unsharded: one upload per dtype group, ONE array down, all six
    stamps set and in order, and the counts in the record, in ``stats``
    and in ``dispatch_profile()``."""
    batcher = DeviceBatcher(max_batch=2, window_ms=200.0)
    try:
        run_concurrent(batcher, [synthetic_enc(24, 2, 5, seed=1, dtype=dt)
                                 for dt in (np.int32, np.float32)])
        run_concurrent(batcher, [synthetic_enc(24, 2, 5, seed=s)
                                 for s in (2, 3)])
        recs = _dispatches_of(batcher)
        assert [d["b"] for d in recs] == [1, 1, 2]
        for d in recs:
            assert 2 <= d["h2d_arrays"] <= 4 and d["d2h_arrays"] == 1, d
            stamps = [d[k] for k in DISPATCH_STAMPS]
            assert None not in stamps and stamps == sorted(stamps), d
            # a 5-step eval rides the 64 program at a bound of 5
            assert (d["p_pad"], d["n_steps"]) == (64, 5)
        # int32 mode: int32 + uint8; float modes add the float group
        assert sorted(d["h2d_arrays"] for d in recs) == [2, 3, 3]
        # the one array down: chosen, pulls, skipped, the near-tie rival
        # and the scores in int32 lanes (one for float32, two for int64),
        # or, for the float64 pair, five float64 lanes
        assert sorted(d["d2h_bytes"] for d in recs) == [
            64 * 5 * 4, 64 * 6 * 4, 2 * 64 * 5 * 8]
        with batcher._lock:
            stats = dict(batcher.stats)
        assert stats["h2d_arrays_total"] == 8 and stats["d2h_arrays_total"] == 3
        assert stats["d2h_bytes_total"] == sum(d["d2h_bytes"] for d in recs)
        prof = batcher.dispatch_profile()
        assert prof["d2h_arrays_avg"] == 1.0
        assert prof["h2d_arrays_avg"] == pytest.approx(8 / 3, abs=0.01)
        assert "in 2.7 arrays" in prof["note"] and "in 1.0 arrays" in prof["note"]
    finally:
        batcher.stop()


def _case_the_mesh_path_sends_49_up_and_five_down():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    from nomad_tpu.parallel import make_mesh

    batcher = DeviceBatcher(max_batch=4, window_ms=200.0,
                            mesh=make_mesh(4, eval_parallel=2))
    try:
        run_concurrent(batcher, [synthetic_enc(32, 2, 6, seed=s)
                                 for s in (11, 12)])
        (d,) = _dispatches_of(batcher)
        # the 48 stacked arrays and the evals' step counts
        assert (d["h2d_arrays"], d["d2h_arrays"]) == (49, 6)
        assert (d["n_steps"], d["padded_steps"]) == (6, d["b_pad"] * 6)
        stamps = [d[k] for k in DISPATCH_STAMPS]
        assert None not in stamps and stamps == sorted(stamps)
        assert batcher.dispatch_profile()["h2d_arrays_avg"] == 49.0
    finally:
        batcher.stop()


def _case_the_wire_scan_is_jit_body_too():
    from nomad_tpu.tpu import wire
    from nomad_tpu.tpu.engine import _build_wire_scan

    enc = synthetic_enc(64, 2, 16, seed=7, dtype=np.float32)
    dims = DeviceBatcher._batch_dims([enc])
    layout = wire.WireLayout(wire.shape_key(enc, dims, enc.dtype), 2, dims)
    bufs = wire.WireBuffers(layout)
    lowered = _build_wire_scan().lower(layout, *bufs.arrays)
    # benchmark/harness/scan.py finds the program by this name
    assert lowered.as_text().splitlines()[0].startswith("module @jit_body")
    assert re.search(r'loc\("[^"]*\bbinpack_score\b',
                     lowered.as_text(debug_info=True))
    # one flat buffer per dtype in, one int32 array out
    assert len(lowered.in_avals[0]) == len(layout.groups) == 3
    out = lowered.out_info
    assert out.dtype == np.int32 and out.shape == (2, 64 * 5)


@pytest.mark.parametrize("case", [
    _case_a_dispatch_crosses_the_boundary_once_each_way,
    _case_the_mesh_path_sends_49_up_and_five_down,
    _case_the_wire_scan_is_jit_body_too,
    _case_record_stamps_monotone_and_steps_padded,
    _case_gather_phase_covers_a_held_gather,
    _case_full_gather_closes_at_once,
    _case_waits_and_device_brackets_left_out_of_the_unions,
    _case_scan_is_jit_body_and_carries_scope_names,
    _case_lone_dispatch_is_recorded_with_the_fields_it_has,
], ids=lambda f: f.__name__.replace("_case_", ""))
def test_dispatch_record(case):
    case()


# ---------------------------------------------------------------------------
# the wire layout (tpu/wire.py): packer + unpacker against pad_encoded +
# np.stack, and run() against the single-eval scan, over synthetic evals
# and evals the real encoder made for every axis a batch can widen
# ---------------------------------------------------------------------------


class _Recorder(DeviceBatcher):
    """A batcher that keeps every eval the scheduler hands it."""

    def __init__(self) -> None:
        super().__init__(max_batch=1)
        self.seen = []

    def run(self, enc, expected=False):
        self.seen.append(enc)
        return super().run(enc, expected)


def _encoded_by_the_scheduler(n_nodes=30):
    """{name: EncodedEval} from the real encoder, in the exact integer
    spec (``int32-*``) and the float32 throughput mode (``float32-*``):
    a plain job, a spread, an affinity, a distinct_property constraint, a
    destructive update (eviction steps), and a system job that preempts
    (candidate tables, int64 ``pre_remaining``). ``n_nodes``: the fleet
    of all but the last, which keeps its four nodes."""
    rec = _Recorder()
    out = {}

    def harness(nodes, preemption=False):
        h = Harness()
        h.device_batcher = rec
        h.state.scheduler_set_config(h.next_index(), SchedulerConfiguration(
            scheduler_algorithm="tpu_binpack",
            preemption_config=PreemptionConfig(
                system_scheduler_enabled=preemption)))
        for n in nodes:
            h.state.upsert_node(h.next_index(), copy.deepcopy(n))
        return h

    def process(h, job, name, kind="service", deterministic=True):
        h.state.upsert_job(h.next_index(), copy.deepcopy(job))
        h.process(kind, Evaluation(
            priority=job.priority, type=job.type,
            triggered_by=EVAL_TRIGGER_JOB_REGISTER, job_id=job.id,
            namespace=job.namespace), deterministic=deterministic)
        out[name] = rec.seen[-1]

    def job(job_id, count):
        j = mock.job()
        j.id = job_id
        j.task_groups[0].count = count
        return j

    try:
        for det, mode in ((True, "int32"), (False, "float32")):
            h = harness(make_nodes(n_nodes, seed=42))
            process(h, job("plain", 5), f"{mode}-plain", deterministic=det)
            j = job("spread", 5)
            j.task_groups[0].spreads = [Spread(
                attribute="${meta.rack}", weight=50,
                spread_target=[SpreadTarget(value="r0", percent=50),
                               SpreadTarget(value="r1", percent=50)])]
            process(h, j, f"{mode}-spread", deterministic=det)
            j = job("aff", 5)
            j.affinities = [Affinity(ltarget="${attr.rack}", rtarget="r1",
                                     operand="=", weight=50)]
            process(h, j, f"{mode}-affinity", deterministic=det)
            j = job("dp", 6)
            j.constraints.append(Constraint(
                operand="distinct_property", ltarget="${attr.rack}",
                rtarget="2"))
            process(h, j, f"{mode}-distinct", deterministic=det)
            j = job("upd", 4)
            process(h, j, f"{mode}-before-update", deterministic=det)
            j = copy.deepcopy(j)
            j.version = 1
            j.task_groups[0].tasks[0].config = {"command": "/bin/new"}
            process(h, j, f"{mode}-evict", deterministic=det)
        nodes = make_nodes(4, seed=4)
        for n in nodes:
            n.node_resources.cpu_shares = 1000
            n.compute_class()
        h = harness(nodes, preemption=True)
        for job_id, prio in (("low", 20), ("high", 80)):
            j = mock.system_job()
            j.id, j.priority = job_id, prio
            j.task_groups[0].tasks[0].resources.cpu = 700
            process(h, j, f"int32-preempt-{job_id}", kind="system")
    finally:
        rec.stop()
    return out


_SYNTHETIC = {
    f"syn-{np.dtype(dt).name}-s{s}": dict(
        n_nodes=n, n_tgs=g, n_placements=p, n_spreads=s, seed=seed, dtype=dt)
    for seed, (dt, n, g, p, s) in enumerate([
        (np.int32, 17, 1, 3, 0), (np.int32, 64, 3, 16, 1),
        (np.int32, 200, 2, 40, 2), (np.float32, 33, 2, 7, 0),
        (np.float32, 50, 4, 11, 1), (np.float32, 8, 1, 1, 2),
        (np.float64, 24, 2, 5, 0), (np.float64, 40, 3, 17, 1),
        (np.float64, 130, 1, 9, 2),
    ], start=20)
}
_ENCODED = [f"{mode}-{what}" for mode in ("int32", "float32")
            for what in ("plain", "spread", "affinity", "distinct",
                         "before-update", "evict")] + ["int32-preempt-high"]
# (evals of one dtype, batch bucket): every eval alone, then mixed batches
# that widen each absent axis, at the buckets of max_batch=8 (1, 2, 8)
_WIRE_CASES = [([name], 1) for name in list(_SYNTHETIC) + _ENCODED] + [
    (["syn-int32-s0", "syn-int32-s2"], 2),
    (["syn-int32-s0", "syn-int32-s1", "syn-int32-s2"], 8),
    (["syn-float32-s0", "syn-float32-s1", "syn-float32-s2"], 8),
    (["syn-float64-s0", "syn-float64-s2"], 2),
    (["syn-float64-s2", "syn-float64-s1", "syn-float64-s0"], 8),
    (["int32-plain", "int32-spread"], 2),
    (["int32-affinity", "int32-distinct", "int32-evict"], 8),
    (["int32-plain", "int32-preempt-high"], 2),
    (["int32-preempt-high", "int32-evict", "int32-spread",
      "int32-affinity", "int32-distinct", "syn-int32-s1"], 8),
    (["float32-spread", "float32-plain"], 2),
    (["float32-evict", "float32-affinity", "float32-distinct",
      "syn-float32-s1"], 8),
]


@pytest.fixture(scope="module")
def wire_evals():
    evals = {name: synthetic_enc(**kw) for name, kw in _SYNTHETIC.items()}
    evals.update(_encoded_by_the_scheduler())
    # the encoder did put every axis on the wire
    assert evals["int32-affinity"].static[4].shape[0] > 0
    assert evals["int32-distinct"].static[17].shape[0] > 0
    assert (np.asarray(evals["float32-evict"].xs[2]) >= 0).any()
    assert evals["int32-preempt-high"].static[20].shape[1] > 0
    assert evals["int32-preempt-high"].carry[10].dtype == np.int64
    return evals


def _wire_id(case):
    names, b_pad = case
    return f"{'+'.join(names)}@{b_pad}"


@pytest.mark.parametrize("case", _WIRE_CASES, ids=_wire_id)
def test_wire_pack_then_unpack_is_pad_and_stack(wire_evals, case):
    """The packer followed by the unpacker yields, bit for bit, shape for
    shape and dtype for dtype, the 48 arrays and the step counts that
    ``pad_encoded`` + ``np.stack`` yield — into buffers that held another
    batch before."""
    names, b_pad = case
    encs = [wire_evals[n] for n in names]
    dims = DeviceBatcher._batch_dims(encs)
    dtype = encs[0].dtype
    layout = wire.WireLayout(wire.shape_key(encs[0], dims, dtype), b_pad, dims)
    assert 2 <= len(layout.groups) <= 4
    bufs = wire.WireBuffers(layout)
    for stale in bufs.arrays:   # what an earlier dispatch left behind
        stale[...] = 0x5A
    wire.pack(bufs, encs)
    got = wire.unpack(layout, bufs.arrays, np)
    padded = [pad_encoded(e, dtype=dtype, **dims) for e in encs]
    padded += [padded[0]] * (b_pad - len(padded))
    for part, part_name in enumerate(("static", "carry", "xs")):
        assert len(got[part]) == len(padded[0][part])
        for i, have in enumerate(got[part]):
            want = np.stack([p[part][i] for p in padded])
            where = f"{part_name}[{i}]"
            assert have.dtype == want.dtype, where
            np.testing.assert_array_equal(have, want, err_msg=where)
    assert len(got) == len(padded[0]) == 4
    want = np.stack([p[3] for p in padded])
    assert got[3].dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got[3], want, err_msg="p_real")
    np.testing.assert_array_equal(got[3][:len(encs)], [e.p for e in encs])


def _assert_same_bits(have, want, where):
    """Same dtype, same shape, same bits (a float's NaN or not)."""
    assert have.dtype == want.dtype, where
    assert have.shape == want.shape, where
    if want.dtype.kind == "f":
        want, have = (np.ascontiguousarray(a).view(f"i{a.dtype.itemsize}")
                      for a in (want, have))
    np.testing.assert_array_equal(have, want, err_msg=where)


@pytest.mark.parametrize("case", _WIRE_CASES, ids=_wire_id)
def test_wire_run_returns_what_the_single_scan_returns(wire_evals, case):
    """``run()`` through the packed program hands every eval of the batch
    the five arrays ``run_scan_single`` computes for it alone: same
    dtypes, same shapes, same bits."""
    names, b_pad = case
    encs = [wire_evals[n] for n in names]
    engine = TpuPlacementEngine.shared()
    singles = [engine.run_scan_single(e) for e in encs]
    batcher = DeviceBatcher(max_batch=1 if b_pad == 1 else 8,
                            window_ms=300.0)
    try:
        batched = run_concurrent(batcher, encs)
        (d,) = _dispatches_of(batcher)
        assert (d["b"], d["b_pad"], d["d2h_arrays"]) == (len(encs), b_pad, 1)
        assert batcher.stats["batch_fallbacks"] == 0
    finally:
        batcher.stop()
    for name, single, got in zip(names, singles, batched):
        for out_name, want, have in zip(
                ("chosen", "scores", "pulls", "skipped", "evict"),
                single, got):
            where = f"{name} {out_name}"
            if out_name == "evict" and have.shape[1] > want.shape[1]:
                # a preempting neighbour widened the candidate axis: the
                # eval's own columns first, then inert ones nobody evicts
                assert (have[:, want.shape[1]:] == -1).all(), where
                have = have[:, :want.shape[1]]
                if not want.size:
                    continue
            _assert_same_bits(have, want, where)


# ---------------------------------------------------------------------------
# the bounded loop (engine._batched_scan_fn): the device is told each eval's
# real step count, runs the wave's longest eval and no further, and needs no
# pre-failed task group for padded steps to point at. The plain reference is
# vmap(lax.scan) over the same step under the padding the batcher had before.
# ---------------------------------------------------------------------------


def _scan_under_the_old_padding(encs, dtype):
    """The five outputs ``[b, p_pad, ...]`` of ``vmap(lax.scan)`` over the
    one step, every eval padded as it was before the device knew
    ``p_real``: a group axis one slot wider than the widest eval, that
    slot born failed and every padded step pointing at it, in the
    16/64/256/1024 step buckets; every padded step runs."""
    import jax
    from jax import lax
    from nomad_tpu.tpu.engine import (
        _build_place_scan, _make_step, _step_layout)

    _build_place_scan()   # x64 on before any array is made
    dims = DeviceBatcher._batch_dims(encs)
    g_pad = _pow2ceil(max(e.g for e in encs) + 1)
    p_raw = max(e.p for e in encs)
    dims.update(
        g_pad=g_pad, aff_pad=g_pad if dims["aff_pad"] else 0,
        p_pad=(16 if p_raw <= 16 else 64 if p_raw <= 64
               else 256 if p_raw <= 256 else 1024))
    padded = []
    for e in encs:
        static, carry, xs, _p_real = pad_encoded(e, dtype=dtype, **dims)
        tg_idx = xs[0].copy()
        tg_idx[e.p:] = e.g   # the eval's first padded, pre-failed slot
        assert carry[6][e.g:].all()
        padded.append((static, carry, (tg_idx,) + tuple(xs[1:])))
    stacked = jax.tree_util.tree_map(lambda *a: np.stack(a), *padded)
    step = _make_step()

    def scan_one(static, carry, xs):
        static, carry = _step_layout(static, carry, fold=False)
        return lax.scan(lambda c, x: step(static, c, x), carry, xs)

    _carry, outs = jax.jit(jax.vmap(scan_one))(*stacked)
    return [np.asarray(o) for o in outs]


def _scan_as_dispatched(encs, dtype, b_pad):
    """The same evals through the program a dispatch runs: packed, the
    bounded loop, one array down, split."""
    from nomad_tpu.tpu.engine import _build_wire_scan

    dims = DeviceBatcher._batch_dims(encs)
    layout = wire.WireLayout(wire.shape_key(encs[0], dims, dtype), b_pad, dims)
    bufs = wire.WireBuffers(layout)
    wire.pack(bufs, encs)
    host = np.asarray(_build_wire_scan()(layout, *bufs.arrays))
    return dims, wire.split_outputs(layout, host)


def _random_wave(wire_evals, seed, mode, always=()):
    """1-6 evals of one dtype drawn from the seed: synthetic ones of 1-64
    steps, 1-3 task groups, 0-2 spreads and 8-200 nodes, and evals the
    real encoder made (an affinity, a distinct_property, a destructive
    update's evictions, preemption candidates); ``always`` names members
    the wave has whatever the draw."""
    rng = np.random.default_rng([seed, 0xB0D])
    dtype = np.dtype(mode).type
    pool = [n for n in _ENCODED if n.startswith(mode)]
    encs = [wire_evals[n] for n in always]
    for _ in range(int(rng.integers(1, 7)) - len(encs)):
        if rng.random() < 0.3:
            encs.append(wire_evals[pool[int(rng.integers(len(pool)))]])
        else:
            encs.append(synthetic_enc(
                int(rng.integers(8, 201)), int(rng.integers(1, 4)),
                int(rng.integers(1, 65)), n_spreads=int(rng.integers(0, 3)),
                seed=int(rng.integers(1 << 30)), dtype=dtype))
    order = rng.permutation(len(encs))
    return [encs[i] for i in order], dtype


_BOUND_CASES = [
    (1, "int32", ()), (2, "int32", ()), (3, "int32", ()),
    (4, "int32", ("int32-affinity",)), (5, "int32", ("int32-evict",)),
    (6, "int32", ("int32-preempt-high",)),
    (7, "int32", ("int32-distinct", "int32-spread")),
    (8, "int32", ("int32-plain", "int32-evict", "int32-preempt-high",
                  "int32-affinity")),
    (9, "float32", ()), (10, "float32", ("float32-evict",)),
    (11, "float32", ("float32-affinity", "float32-spread")),
]


@pytest.mark.parametrize(
    "case", _BOUND_CASES,
    ids=lambda c: f"wave{c[0]}-{c[1]}" + "".join(
        "+" + n.split("-", 1)[1] for n in c[2]))
def test_bounded_loop_equals_the_scan_it_replaces(wire_evals, case):
    """For random waves of mixed p, mixed G, with and without spreads,
    affinities, evictions and preemption candidates: on every row below
    its eval's own step count, the dispatched program's five outputs are,
    bit for bit, those of ``vmap(lax.scan)`` under the old padding."""
    seed, mode, always = case
    encs, dtype = _random_wave(wire_evals, seed, mode, always)
    b_pad = DeviceBatcher(max_batch=8)._bucket(len(encs))
    want = _scan_under_the_old_padding(encs, dtype)
    dims, have = _scan_as_dispatched(encs, dtype, b_pad)
    assert dims["g_pad"] == _pow2ceil(max(e.g for e in encs))
    assert dims["p_pad"] == 64
    names = ("chosen", "scores", "pulls", "skipped", "evict")
    for bi, enc in enumerate(encs):
        for name, w, h in zip(names, want, have):
            _assert_same_bits(h[bi, :enc.p], w[bi, :enc.p],
                              f"eval {bi} (p={enc.p}, g={enc.g}) {name}")
        # past its own count an eval's rows are skipped steps' or the fill
        assert have[3][bi, enc.p:].all() and (have[0][bi, enc.p:] == -1).all()


# ---------------------------------------------------------------------------
# the lone program (engine._batched_scan_fn at b_pad == 1): the step runs
# without a batch axis, its node planes folded to (n_pad // 128, 128). The
# plain reference, kept here, is the program it replaces: vmap(step) at
# b = 1 under the same bounded loop, every plane with its unit batch axis.
# ---------------------------------------------------------------------------


def _with_the_batch_axis(layout, *buffers):
    """``body`` as it was for every batch bucket: the vmapped step under
    the bounded loop, whatever the width."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from nomad_tpu.tpu.engine import _make_step, _step_layout

    static_b, carry_b, xs_b, p_real = wire.unpack(layout, buffers, jnp)
    static_b, carry_b = jax.vmap(
        lambda s, c: _step_layout(s, c, fold=False))(static_b, carry_b)
    vstep = jax.vmap(_make_step())
    xs_t = tuple(jnp.moveaxis(a, 1, 0) for a in xs_b)

    def at(i):
        return tuple(lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                     for a in xs_t)

    p_pad = xs_t[0].shape[0]
    zero = jnp.int32(0)
    _, shapes = jax.eval_shape(
        vstep, static_b, carry_b, at(zero), zero >= p_real)
    outs0 = tuple(jnp.full((p_pad,) + o.shape, fill, o.dtype)
                  for o, fill in zip(shapes, (-1, 0, 0, True, -1, -1)))

    def body(i, state):
        carry, outs = state
        carry, out = vstep(static_b, carry, at(i), i >= p_real)
        return carry, tuple(lax.dynamic_update_index_in_dim(buf, o, i, 0)
                            for buf, o in zip(outs, out))

    _, outs = lax.fori_loop(
        zero, jnp.minimum(jnp.max(p_real), p_pad), body, (carry_b, outs0))
    return wire.pack_outputs(
        layout, *(jnp.moveaxis(o, 0, 1) for o in outs))


# fleets of 128 nodes and more fold (n_pad is a multiple of 128); the
# preempting eval's candidate tables keep the node axis first, and it and
# the small fleets run the step unfolded
_LONE_SYNTHETIC = {
    f"syn-{np.dtype(dt).name}-n{n}-g{g}-s{s}-p{p}": dict(
        n_nodes=n, n_tgs=g, n_placements=p, n_spreads=s, seed=seed, dtype=dt)
    for seed, (dt, n, g, p, s) in enumerate([
        (np.int32, 130, 1, 1, 0), (np.int32, 200, 2, 50, 1),
        (np.int32, 300, 3, 64, 2), (np.int32, 17, 1, 3, 1),
        (np.float32, 130, 1, 1, 1), (np.float32, 200, 2, 50, 0),
        (np.float32, 260, 2, 64, 2),
    ], start=40)
}
_LONE_ENCODED = [f"{mode}-{what}" for mode in ("int32", "float32")
                 for what in ("plain", "spread", "affinity", "distinct",
                              "evict")] + ["int32-preempt-high"]


@pytest.fixture(scope="module")
def lone_evals():
    from nomad_tpu.tpu.engine import _build_place_scan

    _build_place_scan()   # x64 on before any array is made
    evals = {n: synthetic_enc(**kw) for n, kw in _LONE_SYNTHETIC.items()}
    evals.update(_encoded_by_the_scheduler(n_nodes=150))
    assert evals["int32-spread"].n_pad == 256
    assert evals["int32-preempt-high"].static[20].shape[1] > 0
    return evals


def _lone_layout(enc, b_pad=1):
    dims = DeviceBatcher._batch_dims([enc])
    layout = wire.WireLayout(
        wire.shape_key(enc, dims, enc.dtype), b_pad, dims)
    bufs = wire.WireBuffers(layout)
    wire.pack(bufs, [enc] * min(b_pad, 3))
    return layout, bufs


@pytest.mark.parametrize("name", list(_LONE_SYNTHETIC) + _LONE_ENCODED)
def test_lone_program_equals_the_batched_one(lone_evals, name):
    """The whole array a lone dispatch brings down, fill rows included, is
    bit for bit what the vmapped step at b = 1 writes."""
    import jax
    from nomad_tpu.tpu.engine import _build_wire_scan

    enc = lone_evals[name]
    layout, bufs = _lone_layout(enc)
    have = np.asarray(_build_wire_scan()(layout, *bufs.arrays))
    want = np.asarray(jax.jit(_with_the_batch_axis, static_argnums=0)(
        layout, *bufs.arrays))
    _assert_same_bits(have, want, name)
    chosen = wire.split_outputs(layout, have)[0]
    assert (chosen[0, :enc.p] >= 0).any(), "an eval that places nothing"


def _loop_body_shapes(fn, *args):
    """Every array shape inside the ``while`` of ``fn``'s jaxpr."""
    import jax

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            here = inside or eqn.primitive.name == "while"
            if inside:
                for v in list(eqn.invars) + list(eqn.outvars):
                    if hasattr(v.aval, "shape"):
                        yield tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, here)

    return set(walk(jax.make_jaxpr(fn, static_argnums=0)(*args).jaxpr,
                    False))


def test_lone_loop_body_has_no_unit_batch_axis(lone_evals):
    """Inside the 1-wide program's loop no plane is ``[1, n_pad, ...]``
    or ``[1, ..., n_pad]``: the node axis is there only folded, and flat
    for the cumsum. The 16-wide program's planes are ``[16, n_pad]``
    still, none folded."""
    from nomad_tpu.tpu.engine import _build_wire_scan

    enc = lone_evals["syn-int32-n300-g3-s2-p64"]
    n_pad, rows = enc.n_pad, enc.n_pad // 128
    assert (n_pad, rows) == (384, 3)
    scan = _build_wire_scan()
    layout, bufs = _lone_layout(enc)
    lone = _loop_body_shapes(scan, layout, *bufs.arrays)
    assert {(rows, 128), (4, rows, 128), (2, rows, 128)} <= lone
    assert {s for s in lone if n_pad in s} <= {(n_pad,)}
    wide, wide_bufs = _lone_layout(enc, b_pad=16)
    batched = _loop_body_shapes(scan, wide, *wide_bufs.arrays)
    assert {(16, n_pad), (16, 4, n_pad), (16, 2, n_pad)} <= batched
    assert not {s for s in batched if s[-2:] == (rows, 128)}
    # plane for plane the reference's
    assert {s for s in batched if n_pad in s} == {
        s for s in _loop_body_shapes(
            _with_the_batch_axis, wide, *wide_bufs.arrays) if n_pad in s}


def test_step_layout_knows_the_node_axes_the_wire_declares():
    """``engine._STATIC_NODE_AXIS`` / ``_CARRY_NODE_AXIS`` against
    ``wire.FIELDS``: every field with a node axis is listed at that axis,
    but the preemption tables, which stay node-first."""
    from nomad_tpu.tpu import engine

    for part, table in (("static", engine._STATIC_NODE_AXIS),
                        ("carry", engine._CARRY_NODE_AXIS)):
        fields = [f for f in wire.FIELDS if f.part == part]
        declared = {
            i: next(k for k, a in enumerate(f.axes) if a in ("n", "n?"))
            for i, f in enumerate(fields)
            if {"n", "n?"} & set(f.axes) and "prec" not in f.axes}
        assert table == declared, part
        assert all(f.name.startswith("pre_") for f in fields
                   if {"prec", "n_if_prec"} & set(f.axes))


@pytest.mark.parametrize("b", [1, 2], ids=["a-lone-wave", "a-pair"])
def test_lone_dispatches_counts_the_unbatched_program(b):
    """``stats["lone_dispatches"]``: the dispatches that took the program
    without a batch axis (b_pad == 1), beside ``dispatches``."""
    batcher = DeviceBatcher(max_batch=2, window_ms=200.0)
    try:
        run_concurrent(batcher, [synthetic_enc(24, 2, 5, seed=s)
                                 for s in range(b)])
        with batcher._lock:
            stats = dict(batcher.stats)
        assert [d["b_pad"] for d in _dispatches_of(batcher)] == [b]
        assert stats["dispatches"] == 1
        assert stats["lone_dispatches"] == (1 if b == 1 else 0)
    finally:
        batcher.stop()



def _case_padded_steps_are_the_batch_times_the_longest_eval():
    batcher = DeviceBatcher(max_batch=8, window_ms=300.0)
    try:
        encs = [synthetic_enc(24, 1, p, seed=p) for p in (3, 37, 9)]
        run_concurrent(batcher, encs)
        (d,) = _dispatches_of(batcher)
        assert (d["b"], d["b_pad"], d["p_pad"]) == (3, 8, 64)
        assert (d["steps"], d["n_steps"], d["padded_steps"]) == (49, 37, 8 * 37)
        run_concurrent(batcher, [synthetic_enc(24, 1, 50, seed=50)])
        d = _dispatches_of(batcher)[-1]
        # a lone dispatch pads nothing: useful steps read 100%
        assert (d["b_pad"], d["steps"], d["n_steps"], d["padded_steps"]) == (
            1, 50, 50, 50)
        with batcher._lock:
            stats = dict(batcher.stats)
        assert (stats["steps"], stats["padded_steps"]) == (99, 8 * 37 + 50)
    finally:
        batcher.stop()


def _case_one_group_wave_packs_without_a_group_axis():
    one = [synthetic_enc(24, 1, p, seed=p) for p in (5, 12)]
    dims = DeviceBatcher._batch_dims(one)
    assert (dims["g_pad"], dims["p_pad"]) == (1, 64)
    # one two-group eval widens the wave, as for every other axis, and the
    # narrower eval's padded slot stays failed: no step of it points there
    mixed = one + [synthetic_enc(24, 2, 7, seed=7)]
    dims = DeviceBatcher._batch_dims(mixed)
    assert dims["g_pad"] == 2
    layout = wire.WireLayout(
        wire.shape_key(mixed[0], dims, mixed[0].dtype), 8, dims)
    bufs = wire.WireBuffers(layout)
    wire.pack(bufs, mixed)
    _static, carry, xs, p_real = wire.unpack(layout, bufs.arrays, np)
    failed0, tg_idx = carry[6], xs[0]
    assert failed0.shape == (8, 2)
    assert failed0[:2, 1].all() and not failed0[:3, 0].any()
    assert not failed0[2].any()
    assert (tg_idx[:2] == 0).all()
    np.testing.assert_array_equal(p_real[:3], [5, 12, 7])
    np.testing.assert_array_equal(p_real[3:], 5)   # inert copies of slot 0
    _dims, (chosen, _s, _p, skipped, _e, _r) = _scan_as_dispatched(
        mixed, mixed[0].dtype, 8)
    engine = TpuPlacementEngine.shared()
    for bi, enc in enumerate(mixed):
        alone = engine.run_scan_single(enc)
        np.testing.assert_array_equal(chosen[bi, :enc.p], alone[0])
        np.testing.assert_array_equal(skipped[bi, :enc.p], alone[3])


@pytest.mark.parametrize("p", [1, 2, 15, 16, 17, 33, 50, 63, 64])
def test_batch_dims_has_no_step_bucket_under_64(p):
    assert DeviceBatcher._batch_dims([synthetic_enc(16, 1, p)])["p_pad"] == 64


@pytest.mark.parametrize("p, p_pad", [(65, 256), (256, 256), (257, 1024),
                                      (1024, 1024), (1025, 2048)])
def test_batch_dims_keeps_the_larger_step_buckets(p, p_pad):
    assert DeviceBatcher._batch_dims(
        [synthetic_enc(16, 1, p)])["p_pad"] == p_pad


@pytest.mark.parametrize("case", [
    _case_padded_steps_are_the_batch_times_the_longest_eval,
    _case_one_group_wave_packs_without_a_group_axis,
], ids=lambda f: f.__name__.replace("_case_", ""))
def test_device_learns_the_step_count(case):
    case()
