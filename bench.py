"""Benchmark: tpu_binpack placement throughput, SYSTEM headline + kernel.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Headline (r4+): the END-TO-END system rate at C1M shape — real jobs
through the real server (broker -> workers -> eval-batched engine -> plan
queue -> raft/FSM -> state store), 256K placements of identical containers
(the authentic Million Container Challenge workload) over 5K nodes with
exact int-spec deterministic scoring, on one chip. BASELINE.md bar: 1M in
<10s on v5e-8 = 100K placements/s; per-chip share 12.5K/s
(vs_baseline = measured / 12_500). The eval axis shards across chips with
zero cross-chip traffic (dryrun_multichip executes that sharding).

Diagnostics on stderr + the JSON line's "extra": the device-kernel rate
(the r1-r3 headline), plan-queue drain at 10K nodes (BASELINE metric #2),
chunked throughput mode, and the remaining BASELINE system configs.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time

import numpy as np


def log(*args):
    print(*args, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Crash-proof artifacts: every config's JSON lands on disk the moment it
# finishes, and the long headline window also writes periodic in-flight
# progress snapshots — so a later SIGSEGV/OOM/timeout in an unrelated
# diagnostic can never erase results already earned (the "parsed: null"
# failure mode: one crash at minute 40 used to lose the whole run).
# ---------------------------------------------------------------------------

_ARTIFACT_DIR = os.environ.get("NOMAD_BENCH_ARTIFACT_DIR", "bench_artifacts")


def write_artifact(name, payload):
    """Atomically persist one JSON artifact under ``_ARTIFACT_DIR``.

    Failures are logged, never raised — persistence must not be able to
    break the bench it is protecting."""
    try:
        os.makedirs(_ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(_ARTIFACT_DIR, f"{name}.json")
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, sort_keys=True, default=str)
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001
        log(f"artifact write failed for {name}: {e}")


# ---------------------------------------------------------------------------
# Flight recorder: every system config runs with the recorder armed and
# spilling {server}.flight.jsonl under the artifact dir (written+flushed
# every tick by the recorder itself, so a SIGKILL loses at most one frame).
# The derived ranked bottleneck report lands as {config}.bottleneck.json
# from the config's normal path, its finally, AND an atexit hook — a
# timed-out headline is still self-diagnosing from disk.
# ---------------------------------------------------------------------------

_PENDING_FLIGHT = {}


def _flush_flight(name, server):
    """Write the ranked critical-path bottleneck report (+ recorder
    overhead) for one system config. Idempotent and never raises."""
    try:
        from nomad_tpu.trace import attribution

        report = attribution.bottleneck_report()
        report["flight"] = dict(armed=server.flight.armed,
                                **server.flight.overhead())
        write_artifact(f"{name}.bottleneck", report)
        return report
    except Exception as e:  # noqa: BLE001
        log(f"flight flush failed for {name}: {e}")
        return None


@atexit.register
def _flush_pending_flight():
    for name, fn in list(_PENDING_FLIGHT.items()):
        fn()
    _PENDING_FLIGHT.clear()


# ---------------------------------------------------------------------------
# Headline: eval-batched C1M with exact parity semantics
# ---------------------------------------------------------------------------

def bench_batched_parity_c1m(total=1_000_000, n_nodes=5000, batch=512,
                             per_eval=200, budget_s=75.0):
    """C1M as independent evals: ``batch`` evals x ``per_eval`` placements
    per device dispatch, exact sequential parity semantics inside each
    eval (exact INTEGER scoring — tpu/intscore.py — and the ring-ordered
    limit iterator emulation; bit-identical selections on any backend).
    Jobs are C1M-shaped (1-2 task groups per job — the challenge scheduled
    simple single-container jobs) with a spread stanza active so the full
    rank stack runs."""
    import jax

    from nomad_tpu.tpu.engine import (
        _build_batched_scan,
        _build_place_scan,
        example_scan_inputs,
    )

    evals = [
        example_scan_inputs(
            n_nodes=n_nodes, n_tgs=2, n_placements=per_eval, seed=s % 16,
            dtype=np.int32,  # exact-integer parity spec (tpu/intscore.py)
        )
        for s in range(batch)
    ]
    n_pad = evals[0][0]
    static_b = tuple(
        np.stack([e[1][i] for e in evals]) for i in range(len(evals[0][1]))
    )
    carry_b = tuple(
        np.stack([e[2][i] for e in evals]) for i in range(len(evals[0][2]))
    )
    xs_b = tuple(
        np.stack([e[3][i] for e in evals]) for i in range(len(evals[0][3]))
    )

    scan = _build_batched_scan()
    # keep inputs resident: the loop measures device rate; host->device
    # transfer cost is covered by the system benches below
    static_b = jax.device_put(static_b)
    carry_b = jax.device_put(carry_b)
    xs_b = jax.device_put(xs_b)

    t0 = time.perf_counter()
    _carry, outs = jax.block_until_ready(scan(static_b, carry_b, xs_b))
    log(f"batched-parity compile+first dispatch: {time.perf_counter()-t0:.1f}s")

    # -- in-bench parity assertion: sampled evals must match the
    # single-eval exact scan bit-for-bit
    single = _build_place_scan()
    chosen_b = np.asarray(outs[0])
    for k in (0, batch // 2, batch - 1):
        ref_carry, ref_outs = single(n_pad, evals[k][1], evals[k][2], evals[k][3])
        if not (np.asarray(ref_outs[0]) == chosen_b[k]).all():
            raise AssertionError(
                f"PARITY VIOLATION: batched eval {k} diverged from the "
                "single-eval exact scan"
            )
    log(f"parity asserted: batched == single-eval scan on 3/{batch} sampled evals")

    placed_per_dispatch = batch * per_eval
    done = 0
    t0 = time.perf_counter()
    while done < total:
        # materialize to host: the timed unit is a dispatch whose result
        # the host can read
        np.asarray(scan(static_b, carry_b, xs_b)[1][0])
        done += placed_per_dispatch
        if time.perf_counter() - t0 > budget_s:
            break
    elapsed = time.perf_counter() - t0
    rate = done / elapsed
    eta_1m = 1_000_000 / rate
    log(
        f"C1M eval-batched PARITY: {done:,} placements / {n_nodes} nodes in "
        f"{elapsed:.2f}s -> {rate:,.0f} placements/s on ONE chip "
        f"(batch={batch} evals x {per_eval}; 1M ETA {eta_1m:.1f}s single-chip, "
        f"~{eta_1m/8:.1f}s projected v5e-8: the eval axis shards with zero "
        f"cross-chip traffic — dryrun_multichip executes that sharding)"
    )
    return rate


# ---------------------------------------------------------------------------
# Diagnostics: chunked throughput mode (non-parity) + single parity scan
# ---------------------------------------------------------------------------

def c1m_inputs(n_nodes=5000, n_tgs=8, seed=0):
    from nomad_tpu.tpu.engine import DIM_CPU, DIM_MEM, example_scan_inputs

    n_pad, static, carry, _ = example_scan_inputs(
        n_nodes=n_nodes, n_tgs=n_tgs, n_placements=64, seed=seed
    )
    static = list(static)
    asks = np.zeros_like(static[2])  # same capacity dims as the encode
    asks[:, DIM_CPU] = 15
    asks[:, DIM_MEM] = 30
    static[2] = asks
    static[3] = np.ones_like(static[3])  # no constraint filtering in C1M

    def f32(t):
        return tuple(
            np.asarray(a).astype(np.float32)
            if np.asarray(a).dtype.kind == "f" else np.asarray(a)
            for a in t
        )

    return n_pad, f32(static), f32(carry), None


BULK_K = 1024
TAIL_K = 256

# [B, N]-plane traffic model per scan step, in int32-equivalent passes —
# the roofline accounting PARITY.md §"Kernel roofline" documents. The
# parity step's pre-change count (~40 passes, ~210MB/step at B=256,
# N=5120) is kept as the baseline the packed-mask refactor is measured
# against: packing feasibility+affinity presence into one uint8 plane,
# fusing the two ring cumsums into one int32 lane-packed cumsum and
# collapsing the num_terms chain into one popcount removes ~13
# full-plane passes. The chunked tier touches far fewer planes per step
# (no ring machinery, one top_k) but each step covers up to K placements.
PARITY_PASSES_EQ_PRE = 40.0   # r5 baseline (PARITY.md)
PARITY_PASSES_EQ = 27.0       # post packed-mask fusion
CHUNKED_PASSES_EQ = 14.0


def step_traffic_bytes(tier, b, n):
    """Estimated [B, N]-plane bytes ONE scan step moves for a tier."""
    passes = PARITY_PASSES_EQ if tier == "parity" else CHUNKED_PASSES_EQ
    return passes * b * n * 4


def bench_c1m_chunked():
    """Chunked throughput tier (top-K chunks; sampled parity, NOT
    plan-identical to the host — reported as a diagnostic artifact with
    its divergence rate, never the headline)."""
    from nomad_tpu.tpu.engine import _build_chunk_scan, chunk_schedule

    scan_bulk = _build_chunk_scan(BULK_K)
    scan_tail = _build_chunk_scan(TAIL_K)
    total = 1_000_000
    n_tgs = 8
    per_tg = total // n_tgs
    bulk = int(per_tg * 0.88)
    xs_bulk = chunk_schedule([(g, bulk) for g in range(n_tgs)], chunk=BULK_K)
    xs_tail = chunk_schedule(
        [(g, per_tg - bulk) for g in range(n_tgs)], chunk=TAIL_K, retry_rounds=12
    )
    n_steps = len(xs_bulk[0]) + len(xs_tail[0])

    def run(seed):
        n_pad, static, carry, _ = c1m_inputs(seed=seed)
        t0 = time.perf_counter()
        mid_carry, deficit, out_b = scan_bulk(n_pad, static, carry, xs_bulk)
        _, _, out_t = scan_tail(n_pad, static, mid_carry, xs_tail, deficit)
        # materialize to host: the timed unit ends when the host holds
        # the result
        placed = int(np.asarray(out_b[3]).sum() + np.asarray(out_t[3]).sum())
        return time.perf_counter() - t0, placed, n_pad

    t, placed, n_pad = run(seed=0)
    best = float("inf")
    for r in range(2):
        t, placed, n_pad = run(seed=100 + r)
        best = min(best, t)
    rate = total / best
    bps = step_traffic_bytes("chunked", 1, n_pad)
    gbps = bps * n_steps / best / 1e9
    log(
        f"C1M chunked (throughput tier, sampled parity): {total:,} in {best:.2f}s "
        f"-> {rate:,.0f} placements/s ({placed:,} placed; "
        f"~{bps/1e6:.0f}MB/step x {n_steps} steps -> {gbps:.1f} GB/s effective)"
    )
    parity = _chunked_divergence_sample()
    write_artifact("c1m-chunked", {
        "tier": "tpu_binpack_chunked",
        "placements_per_s": round(rate, 1),
        "placed": placed,
        "wall_s": round(best, 3),
        "chunk_bulk": BULK_K,
        "chunk_tail": TAIL_K,
        "bytes_per_step": bps,
        "effective_gbps": round(gbps, 2),
        "parity_sample": parity,
    })
    # dict (not a bare rate) so main() can stamp the sampled-parity
    # divergence next to the tier's rate in the round record
    return {"placements_per_s": rate, "parity_sample": parity}


def _chunked_divergence_sample(n_evals=3, n_nodes=512, p=200):
    """Production-tier sampled parity: run a few evals through the REAL
    chunked path (engine.run_chunked) and re-run every one through the
    bit-parity scan, recording the per-TG multiset divergence rate the
    engine tallies (parity_sample_stats). This is the artifact-recorded
    bound on how far the throughput tier drifts from the host oracle."""
    from nomad_tpu.tpu import engine as _eng
    from nomad_tpu.tpu.engine import (
        EncodedEval,
        TpuPlacementEngine,
        example_scan_inputs,
    )

    engine = TpuPlacementEngine.shared()
    engine.reset_parity_samples()
    _eng._PARITY_SAMPLE_RNG.seed(0xBE7C)
    for s in range(n_evals):
        n_pad, static, carry, xs = example_scan_inputs(
            n_nodes=n_nodes, n_tgs=2, n_placements=p, seed=s
        )
        static = list(static)
        static[3] = np.ones_like(static[3])  # open feasibility (C1M shape)
        f32 = lambda t: tuple(  # noqa: E731
            np.asarray(a).astype(np.float32)
            if np.asarray(a).dtype.kind == "f" else np.asarray(a)
            for a in t
        )
        enc = EncodedEval(
            n_real=n_nodes, n_pad=n_pad, g=2, s=static[9].shape[1],
            v=static[10].shape[2], p=p, dtype=np.float32,
            static=f32(tuple(static)), carry=f32(carry), xs=xs,
            missing_list=[None] * p, nodes=[], table=None,
            start_ns=time.monotonic_ns(), dense_ok=True,
        )
        assert engine._chunk_eligible(enc) is None
        chosen, _scores, _pulls, _skipped, _evict = engine.run_chunked(enc)
        engine._maybe_sample_parity(enc, chosen, rate=1.0)
    stats = engine.parity_sample_stats()
    log(
        f"chunked sampled parity: {stats['evals_sampled']} evals, "
        f"{stats['placements_diverged']}/{stats['placements_checked']} "
        f"placements diverged (rate {stats['divergence_rate']:.4f})"
    )
    return stats


def bench_kernel_roofline(budget_s=150.0):
    """Roofline diagnostic sweep (PARITY.md §"Kernel roofline"): the
    p/B/N grids of the r5 measurement, re-run against the packed-mask
    step, with outputs materialized to host. Each row records wall, ms/step,
    placements/s and the modeled bytes/step -> effective GB/s so the
    pass-count claim in PARITY.md is checkable from the artifact. Rows
    land incrementally; configs skipped on budget overrun are LISTED in
    the artifact rather than silently dropped."""
    import jax

    from nomad_tpu.tpu.engine import _build_batched_scan, example_scan_inputs

    grids = (
        [("p", 256, 5000, p) for p in (50, 100, 200, 400)]
        + [("B", b, 5000, 200) for b in (32, 64, 128, 256, 512)]
        + [("N", 256, n, 200) for n in (1250, 2500, 5000, 10000)]
    )
    scan = _build_batched_scan()
    rows, skipped = [], []
    t_start = time.perf_counter()
    for sweep, b, n_nodes, p in grids:
        if time.perf_counter() - t_start > budget_s:
            skipped.append({"sweep": sweep, "B": b, "N": n_nodes, "p": p})
            continue
        evals = [
            example_scan_inputs(n_nodes=n_nodes, n_tgs=2, n_placements=p,
                                seed=s % 16, dtype=np.int32)
            for s in range(b)
        ]
        n_pad = evals[0][0]
        static_b = jax.device_put(tuple(
            np.stack([e[1][i] for e in evals]) for i in range(len(evals[0][1]))
        ))
        carry_b = jax.device_put(tuple(
            np.stack([e[2][i] for e in evals]) for i in range(len(evals[0][2]))
        ))
        xs_b = jax.device_put(tuple(
            np.stack([e[3][i] for e in evals]) for i in range(len(evals[0][3]))
        ))
        np.asarray(scan(static_b, carry_b, xs_b)[1][0])  # warm compile
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            np.asarray(scan(static_b, carry_b, xs_b)[1][0])
            best = min(best, time.perf_counter() - t0)
        bps = step_traffic_bytes("parity", b, n_pad)
        row = {
            "sweep": sweep, "B": b, "N": n_nodes, "p": p,
            "wall_s": round(best, 4),
            "ms_per_step": round(best / p * 1e3, 3),
            "placements_per_s": round(b * p / best, 1),
            "bytes_per_step": bps,
            "effective_gbps": round(bps * p / best / 1e9, 2),
        }
        rows.append(row)
        log(f"roofline {sweep}-sweep B={b} N={n_nodes} p={p}: "
            f"{row['wall_s']}s, {row['placements_per_s']:,} placements/s, "
            f"{row['effective_gbps']} GB/s effective")
        # incremental persistence: a later crash keeps earned rows
        write_artifact("kernel-roofline", _roofline_payload(rows, skipped))
    write_artifact("kernel-roofline", _roofline_payload(rows, skipped))
    return rows


def _roofline_payload(rows, skipped):
    return {
        "tier": "tpu_binpack (bit-parity, packed-mask step)",
        "passes_eq_per_step": PARITY_PASSES_EQ,
        "passes_eq_per_step_pre_packing": PARITY_PASSES_EQ_PRE,
        "rows": rows, "skipped_on_budget": skipped,
    }


def bench_parity_scan_single(n_nodes=5000, n_placements=10_000):
    from nomad_tpu.tpu.engine import _build_place_scan, example_scan_inputs

    scan = _build_place_scan()
    n_pad, static, carry, xs = example_scan_inputs(
        n_nodes=n_nodes, n_tgs=8, n_placements=n_placements, seed=0,
        dtype=np.int32,
    )
    np.asarray(scan(n_pad, static, carry, xs)[1][0])  # warm
    t0 = time.perf_counter()
    np.asarray(scan(n_pad, static, carry, xs)[1][0])
    dt = time.perf_counter() - t0
    log(
        f"single-eval parity scan: {n_placements:,} / {n_nodes} nodes in "
        f"{dt*1000:.0f}ms -> {n_placements/dt:,.0f} placements/s"
    )


# ---------------------------------------------------------------------------
# End-to-end SYSTEM benches: jobs -> broker -> workers -> engine -> plan
# queue -> raft/FSM (BASELINE benchmark configs, scaled for wall time)
# ---------------------------------------------------------------------------

def bench_system(name, n_nodes, jobs, workers=32, device_batch=16,
                 timeout=180.0, node_seed=0, warmup=None,
                 node_factory=None, expected=None, done=None,
                 deterministic=False, window_ms=None,
                 device_min_placements=None, tranches=0):
    """Run ``jobs`` through a real in-proc server; returns metrics dict.

    ``workers`` is 2x the device batch so the next wave encodes while the
    current batch is on the device. ``warmup`` (a job factory) runs one
    throwaway job through the full path first so jit compiles for this
    cluster's shape buckets land outside the timed wall (and the
    persistent XLA cache makes repeat runs cheap). ``node_factory`` and
    ``done``/``expected`` override the default cluster and completion
    check for shapes (system jobs, preemption) where per-TG counts don't
    describe the goal.

    Gather-cadence knobs (``window_ms``/``device_min_placements``)
    default to None = the PRODUCTION ServerConfig defaults, so what a bench row measures by default is
    what an operator actually gets; rows that pass explicit values are
    measuring a deliberate experiment and record it in batcher_config."""
    from nomad_tpu import mock
    from nomad_tpu.server.fsm import NODE_REGISTER
    from nomad_tpu.server.server import Server, ServerConfig

    if window_ms is None:
        window_ms = ServerConfig.device_batch_window_ms
    if device_min_placements is None:
        device_min_placements = ServerConfig.device_min_placements

    rng = np.random.default_rng(node_seed)
    server = Server(ServerConfig(
        num_schedulers=0, device_batch=device_batch,
        device_batch_window_ms=window_ms,
        deterministic=deterministic,
        device_min_placements=device_min_placements,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        flight_spill_dir=_ARTIFACT_DIR,
    ), name=name)
    server.start()
    # crash/timeout insurance: the bottleneck report flushes from the
    # normal path below, this config's finally, or process atexit —
    # whichever comes first
    _PENDING_FLIGHT[name] = lambda: _flush_flight(name, server)
    try:
        if node_factory is not None:
            node_factory(server, n_nodes, rng)
        else:
            for i in range(n_nodes):
                n = mock.node()
                n.name = f"bench-{i}"
                n.node_resources.cpu_shares = int(rng.choice([4000, 8000, 16000]))
                n.node_resources.memory_mb = int(rng.choice([8192, 16384, 32768]))
                n.compute_class()
                server.raft_apply(NODE_REGISTER, n)

        if expected is None:
            expected = sum(tg.count for job in jobs for tg in job.task_groups)

        from nomad_tpu.server.worker import Worker

        for i in range(workers):
            w = Worker(server, i)
            server.workers.append(w)
            w.start()

        if warmup is not None:
            wjobs = warmup()
            if not isinstance(wjobs, list):
                wjobs = [wjobs]
            for wjob in wjobs:
                server.register_job(wjob)
            deadline = time.perf_counter() + 120
            def warm_done():
                for wjob in wjobs:
                    allocs = server.fsm.state.allocs_by_job(
                        "default", wjob.id, True)
                    if sum(1 for a in allocs if a.desired_status == "run") \
                            < sum(tg.count for tg in wjob.task_groups):
                        return False
                return True
            while time.perf_counter() < deadline and not warm_done():
                time.sleep(0.05)
            for wjob in wjobs:
                server.deregister_job("default", wjob.id, purge=False)
            # wait until the stop evals actually land: lingering warmup
            # allocs would both hold capacity and pollute placed()
            deadline = time.perf_counter() + 60
            def warm_stopped():
                for wjob in wjobs:
                    allocs = server.fsm.state.allocs_by_job(
                        "default", wjob.id, True)
                    if any(a.desired_status == "run" for a in allocs):
                        return False
                return True
            while time.perf_counter() < deadline and not warm_stopped():
                time.sleep(0.05)
            for w in server.workers:
                w.stats["evals_processed"] = 0
            if server.device_batcher is not None:
                # background bucket compiles must not steal device time
                # from the measured window
                server.device_batcher.wait_warm(timeout=120)
                for k in server.device_batcher.stats:
                    server.device_batcher.stats[k] = 0

        from nomad_tpu.trace import attribution
        from nomad_tpu.trace import lifecycle as _lifecycle
        from nomad_tpu.utils import phases

        # attribution covers the MEASURED window: drop boot/warmup spans
        _lifecycle.reset()
        phases.enable()
        p_t0 = phases.now()
        t0 = time.perf_counter()

        def placed():
            # O(table + blocks): never materializes dense allocs — a
            # 50ms poll over state.allocs() would fight the workers for
            # the GIL and depress the number being measured
            return server.fsm.state.count_allocs_desired_run()

        if tranches and tranches > 1:
            # SUSTAINED ingest (the C1M challenge scheduled its million
            # containers as a continuous stream, not one atomic burst):
            # submit the job list in ``tranches`` groups, releasing the
            # next once the previous is ~placed. Keeps optimistic-
            # concurrency collision cohorts at tranche size — a big-bang
            # submission of ~1K evals makes every same-epoch eval replay
            # a near-identical greedy trajectory once score ties thin
            # out, and the rejected fraction cascades into retry storms
            # (measured: >50% of placements at 1M). The registration
            # thread streams during the timed window; the wall clock
            # covers full convergence of every tranche.
            per = (len(jobs) + tranches - 1) // tranches
            groups = [jobs[i:i + per] for i in range(0, len(jobs), per)]

            def feeder():
                cum = 0
                for gi, group in enumerate(groups):
                    with phases.track("register"):
                        for job in group:
                            server.register_job(job)
                    group_count = sum(
                        tg.count for job in group for tg in job.task_groups
                    )
                    cum += group_count
                    # overlap gate: release tranche k+1 once tranche k is
                    # ~half placed, so its snapshot/encode work overlaps
                    # tranche k's device+commit tail. The old ~99% settle
                    # gate serialized tranches — the pipeline drained dry
                    # during every commit tail and the workers sat in the
                    # gather, which is where r05's ~500s untracked idle
                    # came from. Collision cohorts stay tranche-sized:
                    # overlapping halves touch disjoint job sets.
                    gate = cum - max(50, group_count // 2)
                    g_deadline = time.perf_counter() + timeout
                    while (placed() < gate
                           and time.perf_counter() < g_deadline):
                        time.sleep(0.02)

            feeder_t = threading.Thread(target=feeder, daemon=True)
            feeder_t.start()
        else:
            with phases.track("register"):
                for job in jobs:
                    server.register_job(job)

        deadline = time.perf_counter() + timeout
        finished = done if done is not None else (
            lambda srv: placed() >= expected
        )
        completed = False
        next_snap = t0 + 5.0
        while time.perf_counter() < deadline:
            if finished(server) and server.plan_queue.stats()["depth"] == 0:
                completed = True
                break
            if time.perf_counter() >= next_snap:
                # in-flight progress snapshot: if the run dies mid-window
                # (360s headline), the artifact still shows how far it got
                # and where the wall time was going
                next_snap = time.perf_counter() + 5.0
                el = time.perf_counter() - t0
                got_now = placed()
                write_artifact(f"{name}.progress", {
                    "config": name,
                    "placements": got_now,
                    "expected": expected,
                    "elapsed_s": round(el, 2),
                    "placements_per_s": round(got_now / el, 1) if el else 0.0,
                    "phases": phases.wall_shares(p_t0, phases.now()),
                    # in-flight critical-path ledger: a run that dies
                    # mid-window still shows WHERE the wall was going
                    "bottleneck": attribution.bottleneck_report(top_n=5),
                })
            # 5ms poll: the completion check is O(table); at 50ms the poll
            # granularity itself dominates sub-second configs
            time.sleep(0.005)
        elapsed = time.perf_counter() - t0
        phase_shares = phases.wall_shares(p_t0, phases.now())
        phases.disable()
        got = placed()
        evals = sum(w.stats["evals_processed"] for w in server.workers)
        db = server.device_batcher.stats if server.device_batcher else {}
        out = {
            "config": name,
            "nodes": n_nodes,
            "placements": got,
            "expected": expected,
            # "ok" = completion predicate met inside the budget; "timeout"
            # = the window expired first (the artifact still carries
            # whatever was placed). The headline record surfaces this as
            # headline_status so a budget overrun is machine-readable
            # instead of inferable from placements < expected.
            "status": "ok" if completed else "timeout",
            "wall_s": round(elapsed, 2),
            "placements_per_s": round(got / elapsed, 1),
            "evals_per_s": round(evals / elapsed, 1),
            "device_dispatches": db.get("dispatches", 0),
            "device_evals": db.get("evals", 0),
            "max_eval_batch": db.get("max_batch_seen", 0),
            "workers": workers,
            # wave formation: did dispatches actually fill the eval
            # batch? fill_ratio near 1.0 means the broker/gather kept
            # max_eval_batch evals in flight per wave; near 1/batch
            # means the device ran single-eval waves (r05's failure
            # mode: 328 evals over 21 dispatches against a 64 cap).
            "wave_fill": {
                "device_batch": device_batch,
                "gathers": db.get("gathers", 0),
                "full_gathers": db.get("full_gathers", 0),
                "mean_eval_batch": round(
                    db.get("evals", 0) / db["dispatches"], 2
                ) if db.get("dispatches") else 0.0,
                "fill_ratio": round(
                    db.get("evals", 0) / db["dispatches"] / device_batch, 3
                ) if db.get("dispatches") and device_batch else 0.0,
            },
            # wall-clock share (interval UNION across threads, not a
            # thread-sum) each pipeline phase held during the window
            "phases": phase_shares,
            # gather/routing knobs this row ran with, so rows measuring
            # the PRODUCTION ServerConfig defaults are distinguishable
            # from bench-tuned gather windows
            "batcher_config": {
                "device_min_placements": device_min_placements,
                "window_ms": window_ms,
            },
        }
        if server.device_batcher:
            prof = server.device_batcher.dispatch_profile()
            out["dispatch_profile"] = prof
            # roofline companion to the pad_stack/compute/transfer split:
            # modeled [B, N]-plane traffic per step for this config's
            # average dispatch (estimate — n_pad rides close to n_nodes)
            evals_avg = (
                prof.get("evals", 0) / prof["dispatches"]
                if prof.get("dispatches") else 0.0
            )
            bps = step_traffic_bytes("parity", max(evals_avg, 1.0), n_nodes)
            out["roofline"] = {
                "tier": "tpu_binpack (bit-parity, packed-mask step)",
                "passes_eq_per_step": PARITY_PASSES_EQ,
                "bytes_per_step_est": int(bps),
                "evals_per_dispatch_avg": round(evals_avg, 1),
            }
        # chunked-tier sampled-parity tally, when this run exercised it
        from nomad_tpu.tpu.engine import TpuPlacementEngine

        if TpuPlacementEngine._shared is not None:
            stats = TpuPlacementEngine._shared.parity_sample_stats()
            if stats["evals_sampled"]:
                out["parity_sample"] = stats
        report = _flush_flight(name, server)
        _PENDING_FLIGHT.pop(name, None)
        if report is not None:
            # one-line bottleneck verdict rides the config record (the
            # full ranked ledger is the {name}.bottleneck artifact); the
            # ranked component list also rides along so BENCH_r06 can
            # embed it without re-reading artifacts
            out["bottleneck"] = report.get("top")
            out["bottleneck_ranked"] = report.get("entries")
            out["attribution_coverage"] = report.get("coverage")
        log(f"system[{name}]: {json.dumps(out)}")
        write_artifact(name, out)
        return out
    finally:
        # exception/timeout path: flush whatever the recorder has before
        # the server (and its flight thread) goes down
        fn = _PENDING_FLIGHT.pop(name, None)
        if fn is not None:
            fn()
        server.stop()


def c1m_mixed_jobs(total=1_000_000):
    """BASELINE config 5 AS WRITTEN (BASELINE.md line 30): mixed
    service+batch, heterogeneous asks and counts, affinity+spread
    stanzas on a meaningful fraction, 1M ACTUAL placements over 5K
    nodes, the full rank stack (the stack the reference always runs,
    scheduler/stack_oss.go:6-81: job anti-affinity, spread, affinity,
    binpack, limit). 40 job templates — 28 service (10 with
    spread+affinity stanzas, ~25%% of jobs) + 12 batch — instantiated
    round-robin until the placement count is exactly ``total``.
    Capacity is fleet-scale (~30%% util at 1M), matching the C1M
    challenge's 1M-containers-on-5K-hosts shape."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Affinity, Spread, SpreadTarget
    from nomad_tpu.structs.structs import Resources

    cpus = [8, 12, 16, 20]
    mems = [16, 24, 32, 48]
    counts_svc = [900, 950, 1000]   # all pad into the p=1024 scan bucket
    counts_batch = [950, 1000]
    templates = []
    for t in range(28):
        templates.append(dict(
            kind="service", cpu=cpus[t % 4], mem=mems[(t // 4) % 4],
            count=counts_svc[t % 3], stanzas=t < 10,
        ))
    for t in range(12):
        templates.append(dict(
            kind="batch", cpu=cpus[t % 4], mem=mems[t % 4],
            count=counts_batch[t % 2], stanzas=False,
        ))

    def mk_job(tpl, job_id, count):
        j = mock.job() if tpl["kind"] == "service" else mock.batch_job()
        j.id = job_id
        tg = j.task_groups[0]
        tg.count = count
        tg.ephemeral_disk.size_mb = 50
        tg.tasks[0].resources = Resources(cpu=tpl["cpu"], memory_mb=tpl["mem"])
        if tpl["stanzas"]:
            tg.spreads = [Spread(
                attribute="${node.datacenter}", weight=50,
                spread_target=[SpreadTarget(value="dc1", percent=100)],
            )]
            tg.affinities = [Affinity(
                ltarget="${attr.kernel.name}", rtarget="linux",
                operand="=", weight=50,
            )]
        return j

    jobs = []
    placed = 0
    i = 0
    while placed < total:
        tpl = templates[i % len(templates)]
        count = min(tpl["count"], total - placed)
        jobs.append(mk_job(tpl, f"c1m-{i}", count))
        placed += count
        i += 1
    return jobs, templates, mk_job


def bench_c1m_system():
    """The HEADLINE: BASELINE config 5 replayed IN FULL through the real
    system on one chip — 1M actual placements (no extrapolating from a
    smaller run), mixed service+batch with heterogeneous asks/counts and
    spread+affinity stanzas on ~25%% of jobs, over 5K heterogeneous
    nodes; deterministic int-spec scoring with per-eval ring
    decorrelation; ~1K evals ride eval-batched device dispatches (the
    demand-aware gather covers the single-flight encode phase); placements
    flow as dense arrays through plan apply and the FSM. The JSON's
    ``phases`` record the measured wall share of every pipeline phase —
    the v5e-8 extrapolation in main() is computed from THOSE, not from
    an assumed per-chip proration.

    NOMAD_BENCH_C1M_TOTAL scales the placement count down for CI/local
    validation of the mechanics (wave fill, coverage, BENCH_r06 shape);
    the default 1M is the measured headline."""
    total = int(os.environ.get("NOMAD_BENCH_C1M_TOTAL", "1000000"))
    jobs, templates, mk_job = c1m_mixed_jobs(total=total)

    def _warm():
        # one warm job per compiled SHAPE the measured run produces:
        # plain evals and spread+affinity evals (whose union shape also
        # covers mixed co-batched dispatches); prewarm compiles their
        # batch-bucket siblings before the timed window
        plain = mk_job(templates[12], "warm-plain", templates[12]["count"])
        stanza = mk_job(templates[0], "warm-stanza", templates[0]["count"])
        return [plain, stanza]

    # Sustained 16-tranche ingest (see bench_system): tranche-sized
    # collision cohorts keep the optimistic-concurrency rejection rate
    # near zero, every dispatch rides the warm (b=64, p=1024) compile
    # bucket, and the wall covers full convergence of all 1M
    # placements. Gather cadence is the PRODUCTION default (demand-aware
    # window, 2s backstop): r05 proved that a bespoke 15s window +
    # 600ms idle gap left workers parked in the gather for ~500s of the
    # 600s wall, so the headline now runs exactly what
    # service-prod-defaults-5K measures — if the defaults can't carry
    # the headline, the defaults are the bug. 128 workers (2x the
    # 64-eval batch) keep a full next wave encoding while the current
    # one is on device. The 360s internal budget is the acceptance bar:
    # overruns surface as headline_status="timeout" in the artifact
    # rather than eating the whole bench wall.
    return bench_system(
        "c1m-mixed-1M", 5000, jobs, workers=128, device_batch=64,
        timeout=360.0, deterministic=True,
        warmup=_warm, tranches=16,
    )


def bench_plan_queue_drain(n_nodes=10_000, n_plans=256, per_plan=100,
                           n_submitters=16):
    """BASELINE metric #2: plan-queue drain time at 10K nodes.

    Floods the leader's plan queue from N submitter threads with dense
    plans (the C1M commit shape) and measures enqueue->commit drain —
    the serialization point the reference instruments at
    nomad/plan_apply.go:185,369,400."""
    import threading

    from nomad_tpu import mock
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

    rng = np.random.default_rng(7)
    server = Server(ServerConfig(
        num_schedulers=0, device_batch=0,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
    ))
    server.start()
    try:
        node_ids = []
        for i in range(n_nodes):
            n = mock.node()
            n.name = f"drain-{i}"
            n.compute_class()
            server.raft_apply(NODE_REGISTER, n)
            node_ids.append(n.id)

        proto = AllocatedResources(
            tasks={"web": AllocatedTaskResources(cpu_shares=15, memory_mb=30)},
            shared=AllocatedSharedResources(disk_mb=10),
        )

        def mk_plan(k):
            chosen = rng.choice(len(node_ids), size=per_plan, replace=False)
            block = DenseTGPlacements(
                namespace="default", job_id=f"drain-job-{k}",
                task_group="web", eval_id=f"drain-eval-{k}",
                resources_proto=proto, ask_vec=(15.0, 30.0, 10.0, 0.0),
                ids=generate_uuids(per_plan),
                names=[f"drain-job-{k}.web[{i}]" for i in range(per_plan)],
                node_ids=[node_ids[j] for j in chosen],
                node_names=[f"drain-{j}" for j in chosen],
                scores=[1.0] * per_plan,
                nodes_evaluated=[1] * per_plan,
            )
            return Plan(eval_id=f"drain-eval-{k}", dense_placements=[block])

        plans = [mk_plan(k) for k in range(n_plans)]
        futures = []
        fut_lock = threading.Lock()

        def submitter(idx):
            for k in range(idx, n_plans, n_submitters):
                pending = server.plan_queue.enqueue(plans[k])
                with fut_lock:
                    futures.append(pending.future)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=submitter, args=(i,))
            for i in range(n_submitters)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for f in list(futures):
            f.result(timeout=120)
        drain_s = time.perf_counter() - t0
        committed = sum(
            len(b.ids)
            for f in futures
            for b in f.result().dense_placements
        )
        out = {
            "config": "plan-queue-drain",
            "nodes": n_nodes,
            "plans": n_plans,
            "placements_committed": committed,
            "drain_s": round(drain_s, 3),
            "plans_per_s": round(n_plans / drain_s, 1),
            "placements_per_s": round(committed / drain_s, 1),
        }
        log(f"drain[10K nodes]: {json.dumps(out)}")
        write_artifact("plan-queue-drain", out)
        return out
    finally:
        server.stop()


def system_benches():
    from nomad_tpu import mock
    from nomad_tpu.structs import Spread, SpreadTarget

    results = []

    # config 1: service scheduler, 100 task-group instances / 50 nodes
    jobs = []
    for i in range(20):
        j = mock.job()
        j.id = f"svc-{i}"
        j.task_groups[0].count = 5
        j.task_groups[0].tasks[0].resources.cpu = 100
        j.task_groups[0].tasks[0].resources.memory_mb = 128
        jobs.append(j)
    def _svc_warm():
        j = mock.job()
        j.id = "warm-svc"
        j.task_groups[0].count = 2
        j.task_groups[0].tasks[0].resources.cpu = 100
        j.task_groups[0].tasks[0].resources.memory_mb = 128
        return j

    r = _diagnostic(bench_system, "service-100x50", 50, jobs, warmup=_svc_warm)
    if r:
        results.append(r)

    # config 2: batch scheduler, bin-pack only, 1K nodes, 10K short tasks
    jobs = []
    for i in range(10):
        j = mock.batch_job()
        j.id = f"batch-{i}"
        j.task_groups[0].count = 1000
        j.task_groups[0].tasks[0].resources.cpu = 20
        j.task_groups[0].tasks[0].resources.memory_mb = 32
        jobs.append(j)
    def _batch_warm():
        j = mock.batch_job()
        j.id = "warm-batch"
        j.task_groups[0].count = 1000
        j.task_groups[0].tasks[0].resources.cpu = 20
        j.task_groups[0].tasks[0].resources.memory_mb = 32
        return j

    r = _diagnostic(bench_system, "batch-10Kx1K", 1000, jobs, timeout=300.0,
                    warmup=_batch_warm)
    if r:
        results.append(r)

    # config 3: service + affinity/anti-affinity + spread stanzas at 5K
    # nodes (BASELINE.md names all three; job anti-affinity is intrinsic
    # to every multi-count service job via JobAntiAffinityIterator)
    from nomad_tpu.structs import Affinity

    def _spread_job(job_id):
        j = mock.job()
        j.id = job_id
        j.task_groups[0].count = 50
        j.task_groups[0].tasks[0].resources.cpu = 50
        j.task_groups[0].tasks[0].resources.memory_mb = 64
        j.task_groups[0].spreads = [Spread(
            attribute="${node.datacenter}", weight=50,
            spread_target=[SpreadTarget(value="dc1", percent=100)],
        )]
        j.task_groups[0].affinities = [Affinity(
            ltarget="${attr.kernel.name}", rtarget="linux",
            operand="=", weight=50,
        )]
        return j

    jobs = [_spread_job(f"spread-{i}") for i in range(10)]

    def _spread_warm():
        return _spread_job("warm-spread")

    # demand-aware gather: the 10-eval burst rides 1-2 dispatches,
    # so the wall here is a few fixed per-dispatch costs (see phases in
    # the JSON) — the single-flight encode cache collapses the per-eval
    # encode
    r = _diagnostic(bench_system, "service-spread-5K", 5000, jobs, timeout=300.0,
                    window_ms=2000.0, warmup=_spread_warm)
    if r:
        results.append(r)

    # config 3b: the PRODUCTION batcher defaults at the 5K-node shape —
    # no gather knobs passed, so this row runs exactly what ServerConfig
    # ships (demand-aware gather, 2s backstop window,
    # device_min_placements=24). Since r06 the headline runs these same
    # defaults, so this row is the small-shape control for the headline
    # rather than a what-an-operator-gets footnote.
    def _prod_job(job_id):
        j = mock.job()
        j.id = job_id
        j.task_groups[0].count = 100
        j.task_groups[0].tasks[0].resources.cpu = 50
        j.task_groups[0].tasks[0].resources.memory_mb = 64
        return j

    jobs = [_prod_job(f"prod-{i}") for i in range(10)]

    def _prod_warm():
        return _prod_job("warm-prod")

    r = _diagnostic(bench_system, "service-prod-defaults-5K", 5000, jobs,
                    timeout=300.0, warmup=_prod_warm)
    if r:
        results.append(r)

    # config 4: system scheduler, one-per-node, device constraints +
    # preemption (BASELINE.md list). A low-priority system job saturates
    # the fleet first; the high-priority GPU job then preempts its way on
    # (the engine's forced-node pass handles the clean placements; evals
    # needing preemption fall back to the host stack by design).
    jobs = []
    low = mock.system_job()
    low.id = "sys-low"
    low.priority = 20
    low.task_groups[0].tasks[0].resources.cpu = 900
    low.task_groups[0].tasks[0].resources.memory_mb = 512
    jobs.append(low)
    high = mock.system_job()
    high.id = "sys-high"
    high.priority = 80
    high.task_groups[0].tasks[0].resources.cpu = 600
    high.task_groups[0].tasks[0].resources.memory_mb = 256
    from nomad_tpu.structs.structs import RequestedDevice

    high.task_groups[0].tasks[0].resources.devices = [
        RequestedDevice(name="gpu", count=1)
    ]
    jobs.append(high)

    def _sys_nodes(server, n_nodes, rng):
        # every node dc1/linux so the system jobs cover the fleet; a
        # quarter carry a GPU device group
        from nomad_tpu.server.fsm import NODE_REGISTER

        for i in range(n_nodes):
            n = mock.nvidia_node() if i % 4 == 0 else mock.node()
            n.name = f"sys-{i}"
            n.datacenter = "dc1"
            n.attributes["kernel.name"] = "linux"
            n.node_resources.cpu_shares = 1200
            n.node_resources.memory_mb = 2048
            n.compute_class()
            server.raft_apply(NODE_REGISTER, n)

    sys_nodes_n = 1000
    gpu_nodes = (sys_nodes_n + 3) // 4  # _sys_nodes: every 4th node has GPUs

    def _sys_done(server):
        # done when the high-priority GPU job covers every GPU node (its
        # allocs preempted the low-priority ones there) AND the low-
        # priority job holds the rest of the fleet
        high = server.fsm.state.allocs_by_job("default", "sys-high", True)
        low = server.fsm.state.allocs_by_job("default", "sys-low", True)
        return (
            sum(1 for a in high if a.desired_status == "run") >= gpu_nodes
            and sum(1 for a in low if a.desired_status == "run")
            >= sys_nodes_n - gpu_nodes
        )

    def _sys_warm():
        # one warm job per MEASURED EVAL SHAPE: sys-low encodes without
        # device dims, sys-high with the gpu dims — each is its own
        # forced-kernel compile bucket, and both must load outside the
        # timed window (per-process first-use of a cached executable
        # still costs seconds)
        plain = mock.system_job()
        plain.id = "warm-sys"
        plain.priority = 10
        plain.task_groups[0].tasks[0].resources.cpu = 100
        plain.task_groups[0].tasks[0].resources.memory_mb = 64
        dev = mock.system_job()
        dev.id = "warm-sys-dev"
        dev.priority = 10
        dev.task_groups[0].tasks[0].resources.cpu = 100
        dev.task_groups[0].tasks[0].resources.memory_mb = 64
        dev.task_groups[0].tasks[0].resources.devices = [
            RequestedDevice(name="gpu", count=1)
        ]
        return [plain, dev]

    # steady state: every node holds exactly one alloc (high on the GPU
    # nodes after preempting low, low on the rest)
    r = _diagnostic(bench_system, "system-preempt-1K", sys_nodes_n, jobs,
                    timeout=300.0, node_factory=_sys_nodes,
                    expected=sys_nodes_n, done=_sys_done, warmup=_sys_warm)
    if r:
        results.append(r)

    return results


# ---------------------------------------------------------------------------
# chaos-churn-5K: sustained churn + injected faults + leader kill, with
# pass/fail SLO gates (tail latency, throughput floor, state invariants)
# ---------------------------------------------------------------------------

def _stitched_headline(result):
    """Compact nomad-xtrace summary for the headline record (the full
    stitched block, sample tree included, lives in the artifact)."""
    st = result.get("stitched") or {}
    rep = st.get("report") or {}
    return {
        "processes": st.get("processes"),
        "span_count": st.get("span_count"),
        "trace_count": st.get("trace_count"),
        "coverage": rep.get("coverage"),
        "components": {
            e["component"]: e["seconds"] for e in rep.get("entries") or []
        },
    }


def bench_chaos_churn(name="chaos-churn-5K", seed=0, duration_s=30.0,
                      n_nodes=250, settle_timeout_s=90.0):
    """Replay the default-seed churn trace against a live 3-server
    cluster: ~5K placements created across overlapping registration/stop
    waves, destructive rollouts, drains, heartbeat TTL expiries, armed
    fault windows on every injection point, and a mid-run leader kill.
    The SLO gate turns the run's nomad-trace gauges, throughput, and
    post-run invariant sweep into a recorded pass/fail — tail latency
    under churn, where the BENCH_r* burst configs measure cold-start
    throughput only."""
    from nomad_tpu.chaos import ChurnReplay, SLOGate, SLOThresholds
    from nomad_tpu.chaos.trace import generate_trace, trace_to_jsonable
    from nomad_tpu.server import ServerConfig

    trace = generate_trace(
        seed=seed, duration_s=duration_s, n_nodes=n_nodes,
        n_jobs=60, tg_count=50, stop_frac=0.3, rollout_frac=0.25,
        n_drains=3, n_expiries=2, n_hipri=2, n_fault_windows=4,
        canary_frac=0.25, n_preempt_waves=1,
        leader_kill=True,
    )
    log(f"{name}: {len(trace)} trace events over {duration_s:.0f}s, "
        f"{n_nodes} nodes, seed {seed}")
    replay = ChurnReplay(
        seed=seed, trace=trace, n_servers=3, n_nodes=n_nodes,
        config=ServerConfig(
            num_schedulers=2,
            heartbeat_min_ttl=1.5,
            heartbeat_max_ttl=2.5,
            eval_gc_interval=3600.0,
            watchdog_stall_s=10.0,
            # leader's flight recorder spills chaos-s*.flight.jsonl
            # under the artifact dir alongside the SLO record
            flight_spill_dir=_ARTIFACT_DIR,
        ),
        settle_timeout_s=settle_timeout_s,
        # pre-compile the trace's padded eval shapes (tg counts 50 and
        # the 25-count hipri arrivals) outside the measured window
        warmup_counts=(50, 25),
    )
    t0 = time.monotonic()
    result = replay.run()
    wall = time.monotonic() - t0

    # calibrated against the CPU-backend floor of this config: p99 well
    # under the broker's nack timeout, no in-flight eval older than the
    # pipeline ack bound, and a sustained placement floor that a wedged
    # broker or hot-looping retry path cannot meet
    gate = SLOGate(SLOThresholds(
        eval_ms_p99_max=5_000.0,
        slowest_inflight_ms_max=30_000.0,
        throughput_min_allocs_per_s=25.0,
        # the run's critical-path ledger must account for >=90% of the
        # churn makespan or its bottleneck claim is untrustworthy
        attribution_coverage_min=0.9,
    ))
    slo = gate.evaluate(result)
    record = {
        "config": name,
        "seed": seed,
        "wall_s": round(wall, 2),
        "slo": slo,
        "result": result,
        "trace": trace_to_jsonable(trace),
    }
    write_artifact(name, record)
    status = "PASS" if slo["passed"] else "FAIL"
    bottleneck = (result.get("bottleneck_report") or {}).get("top")
    log(f"{name}: {status} — {result['total_allocs']} allocs "
        f"({result['throughput_allocs_per_s']}/s), p99 "
        f"{result['trace_summary'].get('eval_ms_p99')}ms, "
        f"{result['events_degraded']} degraded events, "
        f"{result['leader_kills']} leader kill(s), faults "
        f"{result['fault_fires']}, bottleneck: {bottleneck}")
    for check in slo["checks"]:
        log(f"  slo[{check['name']}]: observed={check['observed']} "
            f"bound={check['bound']} passed={check['passed']}")
    # headline-record summary (the full result lives in the artifact)
    return {
        "config": name,
        "slo_passed": slo["passed"],
        "total_allocs": result["total_allocs"],
        "throughput_allocs_per_s": result["throughput_allocs_per_s"],
        "eval_ms_p99": result["trace_summary"].get("eval_ms_p99"),
        "slowest_inflight_ms": result["trace_summary"].get(
            "slowest_inflight_ms"),
        "invariants": result["invariants"],
        "fault_fires": result["fault_fires"],
        "leader_kills": result["leader_kills"],
        "events_degraded": result["events_degraded"],
        "bottleneck": bottleneck,
        "attribution_coverage": (
            result.get("bottleneck_report") or {}).get("coverage"),
        "stitched": _stitched_headline(result),
        "rpc_table": ((result.get("rpc") or {}).get("cluster")) or {},
        "wall_s": round(wall, 2),
    }


# ---------------------------------------------------------------------------
# chaos-crash-5K: real-process SIGKILL failover under churn load, with
# MTTR SLO gates (new-leader election, first post-failover commit) and a
# forced snapshot-install rejoin of the killed server
# ---------------------------------------------------------------------------

def bench_chaos_crash(name="chaos-crash-5K", seed=0, duration_s=25.0,
                      n_nodes=120, settle_timeout_s=150.0):
    """Replay a churn trace against three REAL server OS processes (each
    with its own durable data dir), SIGKILL -9 the leader mid-trace, and
    gate on recovery: time to a new leader, time to the first committed
    write through it, and the killed server restarting into a
    snapshot-install rejoin (the leader compacts its log while the node
    is down, so catch-up must take the InstallSnapshot path, not plain
    log replay). The invariant sweep then runs per-replica over RPC —
    identical desired-run counts on all three data dirs is the whole
    point. chaos-churn-5K measures degradation under in-proc faults;
    this config measures process-death recovery with nothing shared."""
    from nomad_tpu.chaos import CrashReplay, SLOGate, SLOThresholds
    from nomad_tpu.chaos.trace import generate_trace, trace_to_jsonable

    # fault windows are per-process (the injector can't reach into the
    # children) and canaried rollouts need the in-proc deployment nurse,
    # so the crash trace runs with both off; the leader kill is the fault
    trace = generate_trace(
        seed=seed, duration_s=duration_s, n_nodes=n_nodes,
        n_jobs=40, tg_count=25, stop_frac=0.25, rollout_frac=0.2,
        n_drains=2, n_expiries=2, n_hipri=2, n_fault_windows=0,
        n_preempt_waves=1, leader_kill=True,
    )
    log(f"{name}: {len(trace)} trace events over {duration_s:.0f}s, "
        f"{n_nodes} nodes, 3 server processes, seed {seed}")
    replay = CrashReplay(
        seed=seed, trace=trace, n_servers=3, n_nodes=n_nodes,
        settle_timeout_s=settle_timeout_s,
    )
    t0 = time.monotonic()
    result = replay.run()
    wall = time.monotonic() - t0

    # recovery bounds: election timeout is 0.5-1.0s per attempt, so 5s of
    # MTTR covers several split-vote rounds before failing; first commit
    # adds RPC retry/forwarding discovery on top. Latency/throughput gates
    # are owned by chaos-churn-5K (in-proc, 250 nodes) — here the only
    # floor is "the cluster still places work through the failover".
    gate = SLOGate(SLOThresholds(
        eval_ms_p99_max=None,
        slowest_inflight_ms_max=None,
        throughput_min_allocs_per_s=5.0,
        failover_new_leader_ms_max=5_000.0,
        failover_first_commit_ms_max=10_000.0,
        require_rejoin=True,
        # the stitched MULTI-PROCESS ledger (spans drained from every
        # replica over Trace.Export, clock-aligned) must account for
        # >=90% of its makespan — the cross-process wire-time claim
        # (rpc_wait / forward_hop) is only trustworthy above this floor
        stitched_attribution_coverage_min=0.9,
    ))
    slo = gate.evaluate(result)
    record = {
        "config": name,
        "seed": seed,
        "wall_s": round(wall, 2),
        "slo": slo,
        "result": result,
        "trace": trace_to_jsonable(trace),
    }
    write_artifact(name, record)
    failover = result.get("failover") or {}
    status = "PASS" if slo["passed"] else "FAIL"
    log(f"{name}: {status} — {result['total_allocs']} allocs "
        f"({result['throughput_allocs_per_s']}/s), new leader in "
        f"{failover.get('time_to_new_leader_ms')}ms, first commit in "
        f"{failover.get('time_to_first_commit_ms')}ms, rejoined="
        f"{failover.get('rejoined')} via {failover.get('snapshot_installs')}"
        f" snapshot install(s)")
    for check in slo["checks"]:
        log(f"  slo[{check['name']}]: observed={check['observed']} "
            f"bound={check['bound']} passed={check['passed']}")
    stitched = _stitched_headline(result)
    log(f"{name}: stitched {stitched['span_count']} spans / "
        f"{stitched['trace_count']} traces across {stitched['processes']}, "
        f"coverage {stitched['coverage']}, components {stitched['components']}")
    return {
        "config": name,
        "slo_passed": slo["passed"],
        "total_allocs": result["total_allocs"],
        "throughput_allocs_per_s": result["throughput_allocs_per_s"],
        "invariants": result["invariants"],
        "leader_kills": result["leader_kills"],
        "time_to_new_leader_ms": failover.get("time_to_new_leader_ms"),
        "time_to_first_commit_ms": failover.get("time_to_first_commit_ms"),
        "restart_catchup_ms": failover.get("restart_catchup_ms"),
        "snapshot_installs": failover.get("snapshot_installs"),
        "rejoined": failover.get("rejoined"),
        "stitched": stitched,
        "rpc_table": ((result.get("rpc") or {}).get("cluster")) or {},
        "wall_s": round(wall, 2),
    }


# ---------------------------------------------------------------------------
# capacity-pressure-5K: saturation waves park evals in BlockedEvals, then
# node-registration bursts storm them back out through the coalesced
# unblock path while the leader's autoscaler covers the remainder — gated
# on unblock-to-place latency, storm flatline, and drain-to-zero
# ---------------------------------------------------------------------------

def bench_capacity_pressure(name="capacity-pressure-5K", seed=0,
                            duration_s=30.0, n_nodes=100,
                            settle_timeout_s=180.0):
    """Replay a trace whose job load starts near the fleet's capacity
    ceiling (~85% cpu-committed), then submit two saturation waves sized
    well past it: those placements fail and their evals park in
    BlockedEvals. Each wave's paired capacity_release registers a burst
    of fresh nodes — every registration fires the capacity-change
    trigger, so the parked evals re-enqueue as an unblock storm through
    the coalesced batch path — and the leader's autoscaler watches
    blocked depth and registers whatever the releases didn't cover. The
    gate reads the saturated-regime surfaces chaos-churn-5K never
    exercises: unblock-to-place p99, placement flatline while blocked,
    batch-size mean (the storm must demonstrably coalesce), and blocked
    depth drained to <=1% of peak by measurement time. Fault windows are
    off — pressure here is capacity, not injected failure; the mid-run
    leader kill stays (parked evals must survive a leadership transfer
    via eval restore on the new leader)."""
    from nomad_tpu.chaos import ChurnReplay, SLOGate, SLOThresholds
    from nomad_tpu.chaos.trace import generate_trace, trace_to_jsonable
    from nomad_tpu.server import ServerConfig

    # sizing: ~1400 background allocs at 250cpu fill ~93% of the fleet's
    # usable slots (15 per node after the reserved share), so each
    # 15-job saturation wave (600 allocs, ~40 nodes' worth) parks well
    # past free capacity; the two 30-node releases cover most of it and
    # the autoscaler's steps close the remainder
    trace = generate_trace(
        seed=seed, duration_s=duration_s, n_nodes=n_nodes,
        n_jobs=35, tg_count=40, stop_frac=0.2, rollout_frac=0.15,
        n_drains=2, n_expiries=2, n_hipri=1, n_fault_windows=0,
        leader_kill=True, cpu=250, memory_mb=128,
        n_saturate_waves=2, saturate_jobs=15, release_nodes=30,
    )
    log(f"{name}: {len(trace)} trace events over {duration_s:.0f}s, "
        f"{n_nodes} nodes, 2 saturation waves, seed {seed}")
    replay = ChurnReplay(
        seed=seed, trace=trace, n_servers=3, n_nodes=n_nodes,
        config=ServerConfig(
            num_schedulers=2,
            heartbeat_min_ttl=1.5,
            heartbeat_max_ttl=2.5,
            eval_gc_interval=3600.0,
            watchdog_stall_s=10.0,
            flight_spill_dir=_ARTIFACT_DIR,
            # storm path: coalesce per-trigger unblocks for 50ms, cap
            # each batched enqueue (the spike bound under test)
            unblock_coalesce_window_s=0.05,
            unblock_max_batch=256,
            # leader-side autoscaler: tick at 2Hz, add up to 8 nodes per
            # 1s cooldown while evals stay parked (each saturate job
            # spans ~2.6 nodes, so evals_per_node=1 under-provisions per
            # step and the releases + repeated steps share the work)
            autoscaler_interval_s=0.5,
            autoscaler_cooldown_s=1.0,
            autoscaler_max_step=8,
            autoscaler_evals_per_node=1,
        ),
        settle_timeout_s=settle_timeout_s,
        autoscale=True,
        warmup_counts=(40, 20),
    )
    t0 = time.monotonic()
    result = replay.run()
    wall = time.monotonic() - t0

    # eval-latency gates are owned by chaos-churn-5K and deliberately OFF
    # here: a parked eval's lifecycle spans its whole blocked wait, so
    # eval_ms p99 in a saturated run measures time-to-capacity, which
    # unblock_to_place_ms_p99 bounds directly. The saturated regime's
    # gates: evals must actually have parked (else the config measured
    # nothing), placement must follow capacity within 10s at p99, the
    # storm must never starve the pipeline for >5s while work is parked,
    # and the blocked ledger must be drained by the time the gate reads it
    gate = SLOGate(SLOThresholds(
        eval_ms_p99_max=None,
        slowest_inflight_ms_max=None,
        throughput_min_allocs_per_s=20.0,
        attribution_coverage_min=0.9,
        blocked_peak_min=4,
        unblock_to_place_p99_ms_max=10_000.0,
        storm_flatline_s_max=5.0,
        blocked_drain_frac_max=0.01,
        unblock_batch_mean_min=1.5,
    ))
    slo = gate.evaluate(result)
    record = {
        "config": name,
        "seed": seed,
        "wall_s": round(wall, 2),
        "slo": slo,
        "result": result,
        "trace": trace_to_jsonable(trace),
    }
    write_artifact(name, record)
    cap = result.get("capacity") or {}
    status = "PASS" if slo["passed"] else "FAIL"
    bottleneck = (result.get("bottleneck_report") or {}).get("top")
    log(f"{name}: {status} — {result['total_allocs']} allocs "
        f"({result['throughput_allocs_per_s']}/s), blocked peak "
        f"{cap.get('peak_blocked')}, unblock->place p99 "
        f"{cap.get('unblock_to_place_ms_p99')}ms, batch mean "
        f"{cap.get('unblock_batch_size_mean')}, flatline "
        f"{cap.get('max_flatline_s_while_blocked')}s, drain frac "
        f"{cap.get('blocked_drain_frac')}, autoscaled "
        f"{cap.get('autoscaled_nodes')} node(s), bottleneck: {bottleneck}")
    for check in slo["checks"]:
        log(f"  slo[{check['name']}]: observed={check['observed']} "
            f"bound={check['bound']} passed={check['passed']}")
    return {
        "config": name,
        "slo_passed": slo["passed"],
        "total_allocs": result["total_allocs"],
        "throughput_allocs_per_s": result["throughput_allocs_per_s"],
        "eval_ms_p99": result["trace_summary"].get("eval_ms_p99"),
        "blocked_peak": cap.get("peak_blocked"),
        "unblock_to_place_ms_p99": cap.get("unblock_to_place_ms_p99"),
        "unblock_batch_size_mean": cap.get("unblock_batch_size_mean"),
        "unblock_batches": cap.get("unblock_batches"),
        "blocked_drain_frac": cap.get("blocked_drain_frac"),
        "max_flatline_s_while_blocked": cap.get(
            "max_flatline_s_while_blocked"),
        "autoscaled_nodes": cap.get("autoscaled_nodes"),
        "invariants": result["invariants"],
        "leader_kills": result["leader_kills"],
        "bottleneck": bottleneck,
        "attribution_coverage": (
            result.get("bottleneck_report") or {}).get("coverage"),
        "stitched": _stitched_headline(result),
        "rpc_table": ((result.get("rpc") or {}).get("cluster")) or {},
        "wall_s": round(wall, 2),
    }


# ---------------------------------------------------------------------------
# serve-100Kwatch: the read-serving config — a 5K-thread blocking-watcher
# army over real RPC against the 3-process cluster while the churn trace
# runs, gated on wakeup tail latency, zero lost wakeups, and followers
# carrying the majority of the read traffic as allow_stale local serves
# ---------------------------------------------------------------------------

def bench_serve_watch(name="serve-100Kwatch", seed=0, duration_s=22.0,
                      n_nodes=60, n_watchers=5120, settle_timeout_s=240.0):
    """Park >=5K concurrent blocking queries (``Eval.GetEval`` with
    ``min_query_index``) across three real server processes — two thirds
    pinned to FOLLOWERS as ``allow_stale`` reads served by the
    follower's own FSM and watch hub — and drive churn underneath. A
    beacon writer commits rotating key groups through ``Eval.Update``
    (which returns the raft index) into a ledger; every watch return is
    judged against it: covered commit -> wakeup (latency = return -
    max(park, commit)), deadline-shaped return sitting on an old covered
    commit -> LOST (gate: zero). Concurrency is sampled from per-replica
    ``Watch.Stats`` each tick, not assumed from thread count. The name
    is the 100K-capacity claim (hub registry bound per replica); the
    seed-0 config proves the serving path at 5K real parked threads,
    which is where this container's core count stops lying."""
    from nomad_tpu.chaos import SLOGate, SLOThresholds
    from nomad_tpu.chaos.trace import generate_trace, trace_to_jsonable
    from nomad_tpu.watch.serve import ServeReplay

    # no leader kill (watchers pin replicas by role) and no fault
    # windows (per-process injector); churn here is load, not failure
    # churn here is background load, not the product under test (the
    # placement SLOs live in chaos-churn-5K): sized so the replica
    # schedulers converge on one core while the serving army eats a
    # fixed ~220 RPCs/s of the same GIL
    trace = generate_trace(
        seed=seed, duration_s=duration_s, n_nodes=n_nodes,
        n_jobs=16, tg_count=16, stop_frac=0.2, rollout_frac=0.15,
        n_drains=1, n_expiries=1, n_hipri=1, n_fault_windows=0,
        leader_kill=False,
    )
    log(f"{name}: {len(trace)} trace events over {duration_s:.0f}s, "
        f"{n_nodes} nodes, 3 server processes, {n_watchers} watchers, "
        f"seed {seed}")
    replay = ServeReplay(
        seed=seed, trace=trace, n_servers=3, n_nodes=n_nodes,
        settle_timeout_s=settle_timeout_s, n_watchers=n_watchers,
    )
    t0 = time.monotonic()
    result = replay.run()
    wall = time.monotonic() - t0

    serve = result.get("serve") or {}
    # base gate: the cluster must still place work under the army (the
    # latency/throughput bars live in chaos-churn-5K; serving is the
    # product under test here)
    gate = SLOGate(SLOThresholds(
        eval_ms_p99_max=None,
        slowest_inflight_ms_max=None,
        throughput_min_allocs_per_s=1.0,
    ))
    slo = gate.evaluate(result)
    wake = serve.get("wakeup_ms") or {}
    serve_checks = [
        {"name": "concurrent_watchers",
         "observed": serve.get("peak_concurrent_watchers", 0),
         "bound": ">= 5000",
         "passed": serve.get("peak_concurrent_watchers", 0) >= 5000},
        {"name": "lost_wakeups",
         "observed": serve.get("lost_wakeups", -1),
         "bound": "== 0",
         "passed": serve.get("lost_wakeups", -1) == 0},
        {"name": "wakeup_p99_ms",
         "observed": wake.get("p99"),
         "bound": "<= 2000",
         "passed": (wake.get("p99") is not None
                    and wake.get("p99") <= 2000.0)},
        {"name": "follower_read_share",
         "observed": serve.get("follower_read_share", 0.0),
         "bound": ">= 0.5",
         "passed": serve.get("follower_read_share", 0.0) >= 0.5},
        {"name": "stragglers",
         "observed": serve.get("stragglers", -1),
         "bound": "== 0",
         "passed": serve.get("stragglers", -1) == 0},
    ]
    passed = slo["passed"] and all(c["passed"] for c in serve_checks)
    record = {
        "config": name,
        "seed": seed,
        "wall_s": round(wall, 2),
        "passed": passed,
        "slo": slo,
        "serve_checks": serve_checks,
        "result": result,
        "trace": trace_to_jsonable(trace),
    }
    write_artifact(name, record)
    stitched = _stitched_headline(result)
    rpc_wait_share = None
    for e in ((result.get("stitched") or {}).get("report") or {}).get(
            "entries") or []:
        if e.get("component") == "rpc_wait":
            rpc_wait_share = e.get("share")
    status = "PASS" if passed else "FAIL"
    log(f"{name}: {status} — peak {serve.get('peak_concurrent_watchers')} "
        f"parked watchers, {serve.get('wakeups')} wakeups "
        f"(p99 {wake.get('p99')}ms, max {wake.get('max')}ms), "
        f"{serve.get('lost_wakeups')} lost, coalesce ratio "
        f"{serve.get('coalesce_ratio')}, follower read share "
        f"{serve.get('follower_read_share')}, rpc_wait share "
        f"{rpc_wait_share}")
    for check in serve_checks + slo["checks"]:
        log(f"  check[{check['name']}]: observed={check['observed']} "
            f"bound={check['bound']} passed={check['passed']}")
    headline = {
        "config": name,
        "passed": passed,
        "slo_passed": slo["passed"],
        "serve_checks": serve_checks,
        "n_watchers": serve.get("n_watchers"),
        "peak_concurrent_watchers": serve.get("peak_concurrent_watchers"),
        "wakeups": serve.get("wakeups"),
        "lost_wakeups": serve.get("lost_wakeups"),
        "spurious_wakeups": serve.get("spurious_wakeups"),
        "wakeup_ms": wake,
        "coalesce_ratio": serve.get("coalesce_ratio"),
        "reads_total": serve.get("reads_total"),
        "reads_by_role": serve.get("reads_by_role"),
        "follower_read_share": serve.get("follower_read_share"),
        "beacon_commits": serve.get("beacon_commits"),
        "total_allocs": result.get("total_allocs"),
        "throughput_allocs_per_s": result.get("throughput_allocs_per_s"),
        "invariants": result.get("invariants"),
        "rpc_wait_share": rpc_wait_share,
        "stitched": stitched,
        "wall_s": round(wall, 2),
    }
    # round record at the repo root, written atomically by the bench
    # itself (same lesson as BENCH_r06: the run's own data must survive
    # an outer-harness timeout)
    try:
        root = os.path.dirname(os.path.abspath(__file__))
        tmp = os.path.join(root, ".SERVE_r01.json.tmp")
        with open(tmp, "w") as f:
            json.dump(dict(headline, round="r01"), f, indent=2,
                      sort_keys=True)
            f.write("\n")
        os.replace(tmp, os.path.join(root, "SERVE_r01.json"))
    except OSError as e:
        log(f"SERVE_r01.json write failed: {e}")
    return headline


def _diagnostic(fn, *args, **kwargs):
    """Run one diagnostic bench in isolation: a failure is reported but
    never skips later diagnostics or breaks the headline JSON line. The
    failure itself becomes an artifact, so a crashed config is diagnosable
    from disk even when stderr is lost."""
    try:
        return fn(*args, **kwargs)
    except Exception as e:
        import traceback

        traceback.print_exc(file=sys.stderr)
        log(f"diagnostic bench {fn.__name__} failed: {e}")
        write_artifact(f"{fn.__name__}.error", {
            "bench": fn.__name__,
            "error": repr(e),
            "traceback": traceback.format_exc(),
        })
        return None


def main():
    # Cheap, bounded diagnostics run FIRST — kernel microbench, plan-queue
    # drain, chunked/single-scan modes, the small system configs — so a
    # crash or overrun inside the expensive headline window can never
    # erase them (they are already on disk as artifacts by the time the
    # headline starts). The headline runs LAST with its own 360s internal
    # budget and reports headline_status instead of hanging the run.
    kernel_rate = _diagnostic(bench_batched_parity_c1m, budget_s=40.0)
    if kernel_rate:
        write_artifact("kernel-rate",
                       {"placements_per_s": round(kernel_rate, 1)})
    drain = _diagnostic(bench_plan_queue_drain)
    chunked = _diagnostic(bench_c1m_chunked) or {}
    chunked_rate = chunked.get("placements_per_s", 0.0)
    _diagnostic(bench_parity_scan_single)
    _diagnostic(bench_kernel_roofline)
    sys_results = _diagnostic(system_benches) or []
    # churn/chaos SLO config rides the diagnostics tier: a chaos
    # regression (gate FAIL or crash) still yields its own artifact and a
    # complete headline record
    chaos_churn = _diagnostic(bench_chaos_churn)
    # crash-recovery config: real server processes, SIGKILL failover,
    # snapshot-install rejoin — gated on MTTR instead of tail latency.
    # This parent has touched JAX by now and holds the chip; every child
    # server here and in serve-100Kwatch comes from ServerProcess.spawn
    # (chaos/crash.py), which pins JAX_PLATFORMS=cpu, so none of them
    # initialises the TPU backend
    chaos_crash = _diagnostic(bench_chaos_crash)
    # saturated-regime config: blocked-eval storms + autoscaler drain —
    # gated on unblock-to-place latency and drain-to-zero
    capacity_pressure = _diagnostic(bench_capacity_pressure)
    # read-serving config: 5K parked blocking watchers + follower stale
    # reads under churn — gated on wakeup tail, zero lost wakeups, and
    # follower read share; writes SERVE_r01.json at the repo root itself
    serve_watch = _diagnostic(bench_serve_watch)

    # HEADLINE: end-to-end system C1M replay (jobs -> broker -> workers ->
    # eval-batched engine -> plan queue -> raft/FSM), one chip.
    headline = _diagnostic(bench_c1m_system)

    if headline is None:
        # never lose the bench record: fall back to the kernel rate at
        # the per-chip bar (the r3 headline form)
        headline = {"placements_per_s": kernel_rate or 0.0,
                    "config": "kernel-fallback", "status": "timeout"}
    rate = headline["placements_per_s"] or 1e-9
    if kernel_rate:
        log(f"kernel-rate / system-rate gap: {kernel_rate / rate:,.1f}x")

    # The BASELINE bar is 1M placements in <10s on TPU v5e-8. The
    # headline above ran the FULL 1M on ONE chip; extrapolate to 8 chips
    # from the MEASURED phase wall-shares, not an
    # assumed per-chip proration: the device phase (eval-batched scan —
    # the eval axis shards across chips with zero cross-chip traffic;
    # dryrun_multichip executes that sharding) divides by 8, every
    # host-side second (GIL-serialized encode/plan/FSM plus untracked
    # wall) is conservatively kept AS IS. vs_baseline = 10s / t_v5e8.
    phases = headline.get("phases", {})
    wall = headline.get("wall_s", 0.0) or 0.0
    placements = headline.get("placements", 0)
    dev_share = min(phases.get("device", 0.0), wall)
    if wall > 0 and placements > 0:
        t1m_single = wall * (1_000_000 / placements)
        dev_1m = dev_share * (1_000_000 / placements)
        t_v5e8 = (t1m_single - dev_1m) + dev_1m / 8.0
        vs_baseline = 10.0 / t_v5e8
    else:
        t_v5e8 = None
        vs_baseline = 0.0
    if t_v5e8 is not None:
        log(
            f"v5e-8 extrapolation from measured phases: 1M in {t_v5e8:.2f}s "
            f"(host {t1m_single - dev_1m:.2f}s held serial + device "
            f"{dev_1m:.2f}s / 8) -> vs_baseline {vs_baseline:.3f} against "
            "the <10s bar"
        )
    record = {
        "metric": (
            "BASELINE config 5 AS WRITTEN, end-to-end: 1M actual "
            "placements, mixed service+batch, heterogeneous asks/"
            "counts, spread+affinity stanzas on ~25% of jobs, full "
            "rank stack, 5K nodes, exact int-spec scoring, single "
            "chip; vs_baseline = 10s bar / v5e-8 time extrapolated "
            "from MEASURED phases (device/8, host kept serial)"
        ),
        "value": round(rate, 1),
        "unit": "placements/s",
        "vs_baseline": round(vs_baseline, 4),
        "headline_status": headline.get("status", "timeout"),
        # one-line critical-path verdict from the flight recorder: a DNF
        # ("timeout") names its own bottleneck stage right here
        "bottleneck": headline.get("bottleneck"),
        "extra": {
            "headline_config": headline,
            "v5e8_extrapolation_s": (
                round(t_v5e8, 2) if t_v5e8 is not None else None
            ),
            "extrapolation_model": (
                "t = host_wall(serial, measured) + device_wall/8"
            ),
            "kernel_placements_per_s": round(kernel_rate or 0.0, 1),
            "chunked_tier_placements_per_s": round(chunked_rate or 0.0, 1),
            # sampled-parity divergence of the throughput tier, stamped
            # next to its rate: the tier is only quotable WITH its
            # measured drift from the host oracle
            "chunked_tier_parity_sample": chunked.get("parity_sample"),
            "plan_queue_drain_10k_nodes": drain,
            "system_configs": sys_results,
            "chaos_churn": chaos_churn,
            "chaos_crash": chaos_crash,
            "capacity_pressure": capacity_pressure,
            "serve_100kwatch": serve_watch,
        },
    }
    write_artifact("headline", record)

    # Round record at the repo root, written by bench.py itself (r05's
    # lesson: the outer harness timed out and its wrapper recorded
    # parsed=null — the run's own data survived only in a stderr tail).
    # Everything the acceptance gate reads is top-level here.
    r06 = {
        "round": "r06",
        "headline_config": headline.get("config"),
        "headline_status": headline.get("status", "timeout"),
        "placements_per_s": round(rate, 1),
        "placements": placements,
        # expected != 1M marks a NOMAD_BENCH_C1M_TOTAL-scaled dry run —
        # never quote such a file as the round's measured number
        "expected": headline.get("expected"),
        "wall_s": round(wall, 2),
        "vs_baseline": round(vs_baseline, 4),
        "workers": headline.get("workers"),
        "wave_fill": headline.get("wave_fill"),
        "bottleneck": headline.get("bottleneck"),
        "bottleneck_ranked": headline.get("bottleneck_ranked"),
        "attribution_coverage": headline.get("attribution_coverage"),
        "phases": phases,
        "chunked_tier_placements_per_s": round(chunked_rate or 0.0, 1),
        "chunked_tier_parity_sample": chunked.get("parity_sample"),
        "headline_parity_sample": headline.get("parity_sample"),
        "v5e8_extrapolation_s": (
            round(t_v5e8, 2) if t_v5e8 is not None else None
        ),
    }
    try:
        root = os.path.dirname(os.path.abspath(__file__))
        tmp = os.path.join(root, ".BENCH_r06.json.tmp")
        with open(tmp, "w") as f:
            json.dump(r06, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, os.path.join(root, "BENCH_r06.json"))
    except OSError as e:
        log(f"BENCH_r06.json write failed: {e}")

    print(json.dumps(record))


if __name__ == "__main__":
    main()
