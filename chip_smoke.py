#!/usr/bin/env python3
"""chip_smoke.py: the served tpu_binpack path, once, on the chip.

The quickest proof that the system still starts on the accelerator. One
process, no arguments needed, runnable from a fresh checkout:

    python chip_smoke.py            # on a machine with one TPU chip

It refuses at once on anything that is not a TPU (``JAX_PLATFORMS=cpu
python chip_smoke.py`` exits 2 and prints no result). On the chip it drives
``Server(ServerConfig(...))`` -> ``register_job`` -> eval broker -> ``Worker``
-> ``TpuPlacementEngine`` -> ``DeviceBatcher`` -> plan queue -> raft/FSM ->
state store at the width of the C1M headline (BASELINE.md config 5: 5,000
heterogeneous nodes, ``deterministic=True``, ``device_batch=64``, the
900-1,000-task job mix of ``benchmark/configs/c1m-5k.json``), depth cut to two 64-job waves,
plus the two other compiled programs the benchmark's cells use on the same
cluster: a system job over every eligible node (the scan-free forced kernel)
and a preempting system eval whose encode carries preemption tables
(tpu/preempt.py inside the scan carry).

It exits 0 only if every job converged, no node is over capacity, the device
did the work with every fallback counter at zero, device Plans equal host
``binpack`` Plans for one eval of each kind, and the process tears down with
no device thread alive. Stdout ends with two JSON lines: the summary of
everything measured, then the verdict ``{"ok": ..., "device": {"platform",
"kind", "count"}}`` and nothing else on the last line.

To see a warm compile cache run it twice; a chip belongs to one process, so
it never forks.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import functools
import json
import logging
import os
import statistics
import sys
import threading
import time
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NS = "default"
# Counters that must read zero: each one is a path that ends in a correct
# plan without the device having done the work.
ENGINE_FALLBACK_COUNTERS = (
    "nomad.tpu_engine.fallback",
    "nomad.tpu_engine.small_eval_host",
    "nomad.tpu_engine.dispatch_fallback_host",
)
BATCHER_FALLBACK_STATS = ("batch_fallbacks", "prewarm_failures")


@dataclasses.dataclass
class Sizes:
    """Everything the CPU dry run (tests/test_chip_bringup.py) shrinks. The
    defaults are the C1M headline's widths; only depth is cut."""

    n_nodes: int = 5000
    device_batch: int = 64
    workers: int = 128            # 2x the batch
    jobs_per_tranche: int = 64    # one full wave
    tranches: int = 2
    count_scale: float = 1.0      # multiplies the templates' 900-1,000 counts
    # The host reference scores EVERY feasible node for each placement of
    # a job with spread/affinity stanzas (the limit widens) and its score
    # bookkeeping is quadratic in that count (AllocMetric.score_node):
    # ~4.7 s per placement at 5,000 nodes on the sandbox CPU. The stanza
    # parity eval is cut to 16 placements, in the p=64 bucket the
    # small-eval dispatch reading below shares.
    stanza_parity_count: int = 16
    small_eval_count: int = 32    # just above device_min_placements (24)
    dispatch_reps: int = 10
    phase_timeout_s: float = 420.0


class Failed(Exception):
    """One phase of the smoke failed; the message is the reason."""


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# The device gate
# ---------------------------------------------------------------------------

def require_tpu() -> dict:
    """Exit 2, printing no result, unless JAX's default device is a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no accelerator: jax.devices()[0].platform is "
            f"{dev.platform!r} (device_kind {dev.device_kind!r}); this "
            "program measures the TPU path and does not run on anything else",
            file=sys.stderr,
        )
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# Counting: compiles, cache traffic, engine counters
# ---------------------------------------------------------------------------

class CompileMeter:
    """JAX's own compile telemetry for the whole process: every XLA
    compile-or-cache-load (``backend_compile_duration`` wraps both) with
    its jitted function's name, and the persistent cache's hits/misses.
    Cold, the seconds are compiles; warm, they are cache loads."""

    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self.compile_s = 0.0
        self.events: list = []   # (seconds into the run, function, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compile_s += duration
                self.events.append((
                    round(time.perf_counter() - self._t0 - duration, 1),
                    str(kw.get("fun_name", "?")), round(duration, 2)))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.cache_misses += 1

    def seconds(self) -> float:
        with self._lock:
            return self.compile_s


class CounterSink:
    """Whole-run sums of the repo's metrics counters (the in-memory sink
    only retains a minute). Registered through metrics.register_sink."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: dict = {}

    def incr_counter(self, name: str, value: float) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def add_sample(self, name: str, value: float) -> None:
        pass

    def set_gauge(self, name: str, value: float) -> None:
        pass

    def get(self, name: str) -> int:
        with self._lock:
            return int(self.counters.get(name, 0))


# ---------------------------------------------------------------------------
# Data, made from the seed
# ---------------------------------------------------------------------------

SYS_LOW_DISK = 30_000
SYS_HIGH_DISK = 25_000
SMALL_DISK = 50 * 1024


def make_nodes(n: int, seed: int):
    """The headline's heterogeneous fleet (three cpu and three memory
    classes) with two more axes so feasibility and capacity are not
    trivial: ~2% windows nodes that linux-constrained jobs skip,
    and three disk classes — the small one is what the preempting system
    job cannot fit on without evicting."""
    import numpy as np

    from nomad_tpu import mock

    rng = np.random.default_rng(seed)
    nodes = []
    for i in range(n):
        node = mock.node()
        node.id = str(uuid.UUID(bytes=rng.bytes(16), version=4))
        node.name = f"smoke-{i}"
        node.node_resources.cpu_shares = int(rng.choice([4000, 8000, 16000]))
        node.node_resources.memory_mb = int(rng.choice([8192, 16384, 32768]))
        node.node_resources.disk_mb = int(rng.choice(
            [SMALL_DISK, 100 * 1024, 200 * 1024], p=[0.2, 0.5, 0.3]))
        if rng.random() < 0.02:
            node.attributes["kernel.name"] = "windows"
        node.compute_class()
        nodes.append(node)
    return nodes


@functools.lru_cache(maxsize=1)
def c1m_templates() -> list:
    """The C1M mix: ``jobs.templates`` of benchmark/configs/c1m-5k.json,
    the one table of it (the benchmark's cell reads the same file)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "configs", "c1m-5k.json")
    with open(path) as f:
        return json.load(f)["jobs"]["templates"]


def c1m_job(sizes: Sizes, i: int, job_id: str, count: int = 0):
    """Job ``i`` of the C1M mix (40 templates round-robin — 0-9 service
    with spread+affinity stanzas, 10-27 plain service, 28-39 batch;
    900-1,000 tasks each, all in the p=1024 scan bucket). ``count``
    overrides the template's task count."""
    from nomad_tpu import mock

    templates = c1m_templates()
    tpl = templates[i % len(templates)]
    if not count:
        count = max(1, int(round(tpl["count"] * sizes.count_scale)))
    return mock.c1m_job(tpl, job_id, count)


def fill_jobs(sizes: Sizes):
    """``tranches`` groups of ``jobs_per_tranche`` consecutive C1M jobs."""
    per = sizes.jobs_per_tranche
    return [
        [c1m_job(sizes, t * per + k, f"c1m-{t * per + k}") for k in range(per)]
        for t in range(sizes.tranches)
    ]


def system_job(job_id: str, priority: int, disk_mb: int):
    """One alloc per eligible (linux, dc1) node. cpu/mem stay small so the
    bin-pack scores of the fill are undisturbed; disk is the contended
    dimension (checked by fit and by the preemption met-test, never
    scored)."""
    from nomad_tpu import mock

    job = mock.system_job()
    job.id = job_id
    job.priority = priority
    tg = job.task_groups[0]
    tg.ephemeral_disk.size_mb = disk_mb
    tg.tasks[0].resources.cpu = 100
    tg.tasks[0].resources.memory_mb = 64
    return job


# ---------------------------------------------------------------------------
# The served run
# ---------------------------------------------------------------------------

def run_allocs(state, job_id: str) -> int:
    return sum(1 for a in state.allocs_by_job(NS, job_id, True)
               if a.desired_status == "run")


def wait_for(server, what: str, pred, timeout_s: float) -> None:
    """Poll until ``pred()`` holds AND the server is quiescent (broker,
    plan queue and async pipeline all empty) on three consecutive polls;
    raises Failed on timeout."""
    t0 = time.perf_counter()
    quiet = 0
    while time.perf_counter() - t0 < timeout_s:
        b = server.eval_broker.stats()
        idle = (
            b["total_ready"] == 0 and b["total_unacked"] == 0
            and b["total_blocked"] == 0 and b["total_waiting"] == 0
            and server.plan_queue.stats()["depth"] == 0
            and (server.pipeline is None
                 or server.pipeline.stats()["inflight"] == 0)
        )
        quiet = quiet + 1 if idle and pred() else 0
        if quiet >= 3:
            return
        time.sleep(0.02)
    raise Failed(
        f"{what}: not converged after {timeout_s:.0f}s "
        f"(desired-run allocs {server.fsm.state.count_allocs_desired_run()}, "
        f"broker {server.eval_broker.stats()}, "
        f"plan queue {server.plan_queue.stats()})"
    )


def batcher_stats(server) -> dict:
    with server.device_batcher._lock:
        return dict(server.device_batcher.stats)


def served_run(server, sizes: Sizes, nodes, meter: CompileMeter,
               sink: CounterSink) -> dict:
    """Nodes -> low-priority system job -> the C1M fill in full waves ->
    preempting system job. Returns the facts the checks read, and the
    state snapshots the parity phase replays."""
    from nomad_tpu.server.fsm import NODE_REGISTER

    state = server.fsm.state
    snaps = {}
    out = {"snaps": snaps, "phases": {}}

    def phase(name, wall, **extra):
        rec = {"wall_s": round(wall, 3),
               "compile_s_cum": round(meter.seconds(), 1), **extra}
        out["phases"][name] = rec
        log(f"{name}: {rec}")

    t0 = time.perf_counter()
    for node in nodes:
        server.raft_apply(NODE_REGISTER, node)
    eligible = [n for n in nodes if n.attributes["kernel.name"] == "linux"]
    small = [n for n in eligible if n.node_resources.disk_mb == SMALL_DISK]
    out["eligible"], out["small_disk"] = len(eligible), len(small)
    phase("register_nodes", time.perf_counter() - t0, nodes=len(nodes),
          eligible=len(eligible), small_disk=len(small))
    snaps["nodes"] = state.snapshot()

    # -- system job over every eligible node: the forced kernel ---------
    low = system_job("sys-low", 20, SYS_LOW_DISK)
    t0 = time.perf_counter()
    server.register_job(low)
    wait_for(server, "sys-low",
             lambda: run_allocs(state, "sys-low") == len(eligible),
             sizes.phase_timeout_s)
    phase("system_job", time.perf_counter() - t0,
          allocs=run_allocs(state, "sys-low"))
    snaps["low"] = state.snapshot()
    out["low_job"] = low

    # -- the fill: full waves of the headline's job mix ------------------
    placed = state.count_allocs_desired_run()
    out["fill_jobs"] = fill_jobs(sizes)
    fill_expected = 0
    fill_before = batcher_stats(server)
    served_wall = 0.0
    for t, group in enumerate(out["fill_jobs"]):
        want = sum(job.task_groups[0].count for job in group)
        before = batcher_stats(server)
        t0 = time.perf_counter()
        for job in group:
            server.register_job(job)
        target = placed + want
        wait_for(server, f"fill tranche {t}",
                 lambda: state.count_allocs_desired_run() == target,
                 sizes.phase_timeout_s)
        wall = time.perf_counter() - t0
        after = batcher_stats(server)
        placed = target
        fill_expected += want
        served_wall += wall
        phase(f"fill_tranche_{t}", wall, jobs=len(group), placements=want,
              placements_per_s=round(want / wall, 1),
              dispatches=after["dispatches"] - before["dispatches"],
              evals=after["evals"] - before["evals"],
              max_batch_seen=after["max_batch_seen"])
    fill_after = batcher_stats(server)
    out["fill_expected"] = fill_expected
    out["fill_evals"] = fill_after["evals"] - fill_before["evals"]
    out["fill_dispatches"] = fill_after["dispatches"] - fill_before["dispatches"]
    out["served_wall_s"] = served_wall
    snaps["fill"] = state.snapshot()

    # -- preempting system eval: preempt.py inside the scan carry --------
    # priority 58: the priority-20 system job is evictable (delta >= 10),
    # the priority-50 fill is not, so each node offers one candidate.
    high = system_job("sys-high", 58, SYS_HIGH_DISK)
    passes_before = sink.get("nomad.tpu_engine.system_preempt_pass")
    t0 = time.perf_counter()
    server.register_job(high)
    wait_for(server, "sys-high",
             lambda: (run_allocs(state, "sys-high") == len(eligible)
                      and run_allocs(state, "sys-low")
                      == len(eligible) - len(small)),
             sizes.phase_timeout_s)
    wall = time.perf_counter() - t0
    out["high_job"] = high
    out["evicted"] = sum(
        1 for a in state.allocs_by_job(NS, "sys-low", True)
        if a.desired_status == "evict")
    out["preempt_passes"] = (
        sink.get("nomad.tpu_engine.system_preempt_pass") - passes_before)
    phase("preempting_eval", wall, evicted=out["evicted"],
          preempt_passes=out["preempt_passes"])
    out["expected_run"] = (
        fill_expected + len(eligible) + len(eligible) - len(small))
    return out


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def top_bucket_dispatched(stats: dict, sizes: Sizes) -> bool:
    """Whether some wave was big enough to be padded to the B=device_batch
    program (DeviceBatcher._bucket: more than max/4 evals), i.e. the
    widest compiled bucket ran. How FULL that wave was is wave fill — a
    property of the host's gather cadence, recorded (max_batch_seen,
    fill_dispatches) but not gated here."""
    return stats["max_batch_seen"] > max(1, sizes.device_batch // 4)


def check_served(server, sizes: Sizes, run: dict, sink: CounterSink) -> list:
    """Every convergence and device-did-the-work condition; returns the
    list of failures (empty = pass)."""
    from nomad_tpu.structs.funcs import allocs_fit

    state = server.fsm.state
    bad = []
    got = state.count_allocs_desired_run()
    if got != run["expected_run"]:
        bad.append(f"desired-run allocs {got} != expected {run['expected_run']}")
    for job in (j for g in run["fill_jobs"] for j in g):
        n = run_allocs(state, job.id)
        if n != job.task_groups[0].count:
            bad.append(f"job {job.id}: {n}/{job.task_groups[0].count} placed")
    per_node = {}
    for a in state.allocs_by_job(NS, "sys-high", True):
        if a.desired_status == "run":
            per_node[a.node_id] = per_node.get(a.node_id, 0) + 1
    if len(per_node) != run["eligible"] or set(per_node.values()) != {1}:
        bad.append(f"sys-high covers {len(per_node)} nodes, "
                   f"{run['eligible']} eligible, max per node "
                   f"{max(per_node.values(), default=0)}")
    if run["evicted"] != run["small_disk"] or run["evicted"] == 0:
        bad.append(f"preempting eval evicted {run['evicted']}, expected "
                   f"{run['small_disk']} (one per small-disk node)")
    if run["preempt_passes"] < 1:
        bad.append("the preempting eval never entered the device preempt pass")
    depth = server.plan_queue.stats()["depth"]
    if depth:
        bad.append(f"plan queue depth {depth}")
    t0 = time.perf_counter()
    over = []
    for node in state.nodes():
        fit, dim, _used = allocs_fit(node, state.allocs_by_node(node.id))
        if not fit:
            over.append(f"{node.name}:{dim}")
    if over:
        bad.append(f"{len(over)} nodes over capacity: {over[:5]}")
    log(f"allocs_fit over {len(state.nodes())} nodes: {len(over)} over "
        f"capacity ({time.perf_counter() - t0:.1f}s)")

    stats = batcher_stats(server)
    n_fill = sizes.tranches * sizes.jobs_per_tranche
    if stats["dispatches"] <= 0:
        bad.append("no device dispatch recorded")
    if run["fill_evals"] < n_fill:
        bad.append(f"fill rode {run['fill_evals']} device evals for "
                   f"{n_fill} jobs")
    # forced kernel: sys-low + sys-high pass 1; batched scan: pass 2
    if stats["evals"] < n_fill + 3:
        bad.append(f"device evals {stats['evals']} < {n_fill + 3}")
    if not top_bucket_dispatched(stats, sizes):
        bad.append(f"max_batch_seen {stats['max_batch_seen']}: no wave "
                   f"reached the B={sizes.device_batch} bucket")
    for key in BATCHER_FALLBACK_STATS:
        if stats[key]:
            bad.append(f"device_batcher.stats[{key!r}] = {stats[key]}")
    for name in ENGINE_FALLBACK_COUNTERS:
        if sink.get(name):
            bad.append(f"{name} = {sink.get(name)}")
    # the run has drained (wait_for): every announcement was consumed by
    # an arrival or withdrawn; one left over holds later gathers open
    with server.device_batcher._lock:
        run["announced_outstanding"] = server.device_batcher._expected
    if run["announced_outstanding"]:
        bad.append(f"{run['announced_outstanding']} announced evals "
                   "outstanding after the run drained")
    return bad


def plan_view(plans, evals, created) -> dict:
    """UUID-free projection of one Harness run, for equality between two
    runs: every placement as (plan, node, alloc name); every node's
    victims as (job, task group); every preemptor's victims in their final
    eviction ORDER; each eval's status and failed task groups; the number
    of follow-up evals. At least as strict as the comparators of
    tests/test_tpu_parity.py (which key allocs by name) and
    tests/test_system_engine.py (node and name): system allocs share one
    name across nodes, so the node is part of every key here."""
    placed, victims, preempted_by = [], {}, {}
    for i, plan in enumerate(plans):
        stub_by_id = {}
        for nid, stubs in plan.node_preemptions.items():
            for s in stubs:
                stub_by_id[s.id] = (nid, s.job_id, s.task_group)
            victims[(i, nid)] = sorted((s.job_id, s.task_group) for s in stubs)
        for nid, allocs in plan.node_allocation.items():
            for a in allocs:
                placed.append((i, nid, a.name))
                if a.preempted_allocations:
                    preempted_by[(i, nid, a.name)] = [
                        stub_by_id.get(v) for v in a.preempted_allocations]
    return {
        "plans": len(plans),
        "placed": sorted(placed),
        "victims": victims,
        "preempted_by": preempted_by,
        "evals": [(e.status, sorted(e.failed_tg_allocs or {})) for e in evals],
        "created": len(created),
    }


def replay(snapshot, job, batcher=None, algorithm: str = ""):
    """Process ``job``'s registration eval against a private copy of
    ``snapshot`` through the scheduler Harness; returns the Harness.
    ``algorithm`` overrides the snapshot's scheduler algorithm;
    ``batcher`` routes the device path through that DeviceBatcher."""
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs.structs import (
        Evaluation,
        SchedulerConfiguration,
    )

    st = snapshot.snapshot()
    h = Harness(st)
    h._next_index = st.latest_index + 1
    h.device_batcher = batcher
    if algorithm:
        st.scheduler_set_config(
            h.next_index(),
            SchedulerConfiguration(scheduler_algorithm=algorithm))
    st.upsert_job(h.next_index(), copy.deepcopy(job))
    # the eval id derives from the job, so two replays process the SAME eval
    h.process(job.type, Evaluation(
        id=str(uuid.uuid5(uuid.NAMESPACE_OID, job.id)),
        priority=job.priority, type=job.type, job_id=job.id,
        namespace=job.namespace))
    return h


def check_parity(run: dict, sizes: Sizes) -> dict:
    """Plan equality with the plain reference, outside any timing: the
    same eval against the same snapshot under ``tpu_binpack`` and under
    the host ``binpack`` iterator stack, through the scheduler Harness,
    node for node and eviction for eviction (plan_view). One eval of each
    kind; raises Failed on any divergence."""
    from nomad_tpu.tpu.batcher import DeviceBatcher

    cases = [
        ("stanza_service", "low",
         c1m_job(sizes, 0, "parity-stanza", sizes.stanza_parity_count), False),
        ("plain_batch", "low", c1m_job(sizes, 28, "parity-batch"), False),
        ("system", "nodes", run["low_job"], False),
        ("preempting", "fill", run["high_job"], True),
    ]
    # B=1 through the same batched-scan builder the server dispatches to,
    # so the Plans compared are the served programs' (a one-bucket batcher
    # compiles no siblings)
    batcher = DeviceBatcher(max_batch=1, window_ms=0.0)
    report = {}
    try:
        for name, snap_key, job, preempts in cases:
            views = {}
            for alg in ("binpack", "tpu_binpack"):
                t0 = time.perf_counter()
                h = replay(run["snaps"][snap_key], job, algorithm=alg,
                           batcher=batcher if alg == "tpu_binpack" else None)
                views[alg] = plan_view(h.plans, h.evals, h.create_evals)
                log(f"parity {name}/{alg}: {time.perf_counter() - t0:.1f}s")
            host, dev = views["binpack"], views["tpu_binpack"]
            diff = [k for k in host if host[k] != dev[k]]
            if diff:
                raise Failed(f"parity {name}: device Plan != host Plan in "
                             f"{diff}")
            placed = len(dev["placed"])
            evicted = sum(len(v) for v in dev["victims"].values())
            if placed == 0 or (preempts and evicted == 0):
                raise Failed(f"parity {name}: vacuous ({placed} placed, "
                             f"{evicted} evicted)")
            report[name] = {"placed": placed, "evicted": evicted,
                            "equal": True}
            log(f"parity {name}: equal ({placed} placed, {evicted} evicted)")
    finally:
        batcher.stop(timeout=None)
    if batcher.stats["batch_fallbacks"] or batcher.stats["prewarm_failures"]:
        raise Failed(f"parity batcher degraded: {dict(batcher.stats)}")
    return report


def measure_b1_dispatch(run: dict, sizes: Sizes) -> dict:
    """Wall of one warm B=1 dispatch of a small eval (a stanza service
    job of ``small_eval_count`` tasks, p=64 bucket) at the fleet's width:
    pad/stack + H2D + kernel + D2H, as the batcher itself splits it. This
    is the fixed cost ``device_min_placements`` exists to amortize. Median
    of ``dispatch_reps`` readings after one compile."""
    from nomad_tpu.tpu.batcher import DeviceBatcher

    batcher = DeviceBatcher(max_batch=1, window_ms=0.0)
    legs = ("pad_stack_ms_total", "compute_ms_total", "transfer_ms_total")
    readings = []
    try:
        for rep in range(sizes.dispatch_reps + 1):
            job = c1m_job(sizes, 0, f"small-{rep}", sizes.small_eval_count)
            before = dict(batcher.stats)
            replay(run["snaps"]["fill"], job, batcher=batcher)
            after = dict(batcher.stats)
            if after["dispatches"] - before["dispatches"] != 1:
                raise Failed("small eval did not ride exactly one dispatch")
            if rep:  # rep 0 compiles
                readings.append([after[k] - before[k] for k in legs])
    finally:
        batcher.stop(timeout=None)
    walls = [sum(r) for r in readings]
    med = statistics.median
    return {
        "placements": sizes.small_eval_count,
        "readings": len(walls),
        "wall_ms_median": med(walls),
        "wall_ms_min": min(walls),
        "wall_ms_max": max(walls),
        "pad_stack_ms_median": med([r[0] for r in readings]),
        "compute_ms_median": med([r[1] for r in readings]),
        "d2h_ms_median": med([r[2] for r in readings]),
    }


def teardown(server) -> list:
    """Quiesce the whole device stack and prove the process thread-clean:
    a dispatcher or warm-compile thread still inside the runtime at
    interpreter exit is what segfaults teardown."""
    from nomad_tpu.tpu.engine import TpuPlacementEngine

    server.device_batcher.wait_warm()   # unbounded: every sibling compile
    server.stop()
    TpuPlacementEngine.shutdown()
    return [
        t.name for t in threading.enumerate()
        if t.is_alive() and t.name.startswith(("device-batcher",
                                               "batcher-prewarm"))
    ]


def cache_report() -> dict:
    import jax

    path = jax.config.jax_compilation_cache_dir
    entries = 0
    if path and os.path.isdir(path):
        entries = sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    return {"dir": path, "entries": entries}


# ---------------------------------------------------------------------------

def run_smoke(sizes: Sizes, seed: int = 0) -> dict:
    """Drive every phase; returns the summary dict (``ok`` false and
    ``failures`` filled when anything failed). Never consults the
    platform — main() gates on the chip before calling this."""
    from nomad_tpu.server.server import Server, ServerConfig
    from nomad_tpu.utils import metrics

    meter = CompileMeter()
    sink = CounterSink()
    metrics.register_sink(sink)
    t_start = time.perf_counter()
    failures: list = []
    summary: dict = {}
    server = Server(ServerConfig(
        num_schedulers=sizes.workers, device_batch=sizes.device_batch,
        scheduler_algorithm="tpu_binpack", deterministic=True,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        # a cold first wave sits ~55 s inside XLA compiles with placement
        # flat; the default 30 s alarm would bury stderr under 128 worker
        # stacks. A real stall still dumps well inside phase_timeout_s.
        watchdog_stall_s=sizes.phase_timeout_s / 3,
    ), name="chip-smoke")
    server.start()
    try:
        nodes = make_nodes(sizes.n_nodes, seed)
        run = served_run(server, sizes, nodes, meter, sink)
        summary.update(
            placements=server.fsm.state.count_allocs_desired_run(),
            fill_placements=run["fill_expected"],
            served_wall_s=round(run["served_wall_s"], 3),
            phases=run["phases"],
            evicted=run["evicted"],
        )
        failures += check_served(server, sizes, run, sink)
        stats = batcher_stats(server)
        summary.update(
            dispatches=stats["dispatches"], device_evals=stats["evals"],
            max_batch_seen=stats["max_batch_seen"],
            top_bucket_dispatched=top_bucket_dispatched(stats, sizes),
            fill_dispatches=run["fill_dispatches"],
            fill_evals=run["fill_evals"],
            announced_outstanding=run["announced_outstanding"],
            fallbacks={
                **{k: stats[k] for k in BATCHER_FALLBACK_STATS},
                **{n: sink.get(n) for n in ENGINE_FALLBACK_COUNTERS},
            },
        )
        summary["parity"] = check_parity(run, sizes)
        summary["b1_dispatch"] = measure_b1_dispatch(run, sizes)
        log(f"B=1 dispatch: {summary['b1_dispatch']}")
    except Failed as e:
        failures.append(str(e))
    finally:
        lingering = teardown(server)
        metrics.deregister_sink(sink)
    if lingering:
        failures.append(f"device threads alive after teardown: {lingering}")
    summary.update(
        compile_s=round(meter.seconds(), 1), compile_events=meter.events,
        cache_hits=meter.cache_hits, cache_misses=meter.cache_misses,
        cache=cache_report(),
        total_wall_s=round(time.perf_counter() - t_start, 1),
        failures=failures,
    )
    return {"ok": not failures, **summary}


def result_lines(ok: bool, device: dict, summary: dict) -> list:
    """The two JSON lines that end stdout. The summary (everything
    measured, ``claim`` last: this run measures nothing against a parent)
    comes first; the LAST line is the verdict the chip check reads, with
    exactly the keys ``ok`` and ``device`` (platform, kind, count)."""
    verdict = {"ok": bool(ok), "device": {
        "platform": str(device["platform"]),
        "kind": str(device["kind"]),
        "count": int(device["count"])}}
    return [json.dumps({**verdict, **summary, "claim": None}),
            json.dumps(verdict)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the generated cluster (default 0)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)

    device = require_tpu()
    # the program itself, before anything is printed: beside nothing else of
    # the repo this raises, and stdout stays empty
    import jax
    import jaxlib
    from importlib import metadata

    import nomad_tpu  # noqa: F401

    log(f"device: {device}; jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {metadata.version('libtpu')}")
    result = run_smoke(Sizes(), seed=args.seed)
    ok = result.pop("ok")
    for reason in result["failures"]:
        print(f"chip_smoke: FAILED: {reason}", file=sys.stderr)
    for line in result_lines(ok, device, result):
        print(line)
    sys.stdout.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
