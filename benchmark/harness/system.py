"""The system under test, as the benchmark holds it.

Everything that touches the program is here: the server brought up from a
configuration's ``server`` object, the fleet and the jobs turned into the
program's own structs, the counters and compile telemetry, the warm-up, and
the facts read back from the state store after the window. Pieces are
copied from chip_smoke.py (the only code proven on this chip), not
imported: later PRs may change that file.
"""
from __future__ import annotations

import os
import sys
import threading
import time
import zlib

NS = "default"
# Counters that must read zero: each is a path that ends in a correct plan
# without the device having done the work (chip_smoke.py's lists).
ENGINE_FALLBACK_COUNTERS = (
    "nomad.tpu_engine.fallback",
    "nomad.tpu_engine.dispatch_fallback_host",
)
# The server's own routing rule: an eval with fewer placements than
# ``device_min_placements`` is placed by the host stack until the batcher
# has completed a batch; after that such an eval, the tail of a partially
# committed plan among them, rides a warm program (engine.py,
# ``small_eval_device_retry``). Held as a share of the jobs due, to the
# limit that the cell's traffic file states.
SMALL_EVAL_COUNTER = "nomad.tpu_engine.small_eval_host"
BATCHER_FALLBACK_STATS = ("batch_fallbacks", "prewarm_failures")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def import_program() -> None:
    """Put the checkout on the path and import the program. Beside nothing
    else of the repo this raises, before anything is printed."""
    root = repo_root()
    if root not in sys.path:
        sys.path.insert(0, root)
    import nomad_tpu  # noqa: F401


def require_tpu(chips: int) -> dict:
    """Exit 2, printing no result, unless JAX's devices are ``chips`` TPUs
    or more."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); jax.devices() is "
              f"{len(devs)} x {devs[0].platform!r} ({devs[0].device_kind!r}); "
              "this benchmark measures the TPU path and runs on nothing else",
              file=sys.stderr)
        sys.exit(2)
    return device_facts()


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileMeter:
    """JAX's own compile telemetry for the whole process: every XLA
    compile-or-cache-load with its jitted function's name and the moment it
    ended, and the persistent cache's hits and misses."""

    def __init__(self) -> None:
        from jax import monitoring

        self._lock = threading.Lock()
        self.events: list = []   # (perf_counter at end, function, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.events.append((time.perf_counter(),
                                    str(kw.get("fun_name", "?")), duration))

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def inside(self, t0: float, t1: float) -> list:
        """Compiles or cache loads that ended inside [t0, t1]."""
        with self._lock:
            return [(f, s) for t, f, s in self.events if t0 <= t <= t1]

    def seconds(self) -> float:
        with self._lock:
            return sum(s for _, _, s in self.events)


class CounterSink:
    """Whole-run sums of the program's metrics counters (its in-memory sink
    keeps a minute). Registered through metrics.register_sink."""

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

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self.counters)


def program_nodes(fleet) -> list:
    from nomad_tpu import mock

    nodes = []
    for i in range(len(fleet)):
        node = mock.node()
        node.id = fleet.ids[i]
        node.name = fleet.names[i]
        node.datacenter = fleet.dc_names[int(fleet.dc[i])]
        node.node_resources.cpu_shares = int(fleet.cpu[i])
        node.node_resources.memory_mb = int(fleet.mem[i])
        node.node_resources.disk_mb = int(fleet.disk[i])
        node.reserved_resources.cpu_shares = int(fleet.rcpu[i])
        node.reserved_resources.memory_mb = int(fleet.rmem[i])
        node.reserved_resources.disk_mb = int(fleet.rdisk[i])
        if not fleet.linux[i]:
            node.attributes["kernel.name"] = "windows"
        node.compute_class()
        nodes.append(node)
    return nodes


def program_job(spec: dict):
    """A job dict of jobs.py as the program's Job."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Affinity, Spread, SpreadTarget
    from nomad_tpu.structs.structs import Constraint, Resources

    job = mock.job() if spec["kind"] == "service" else mock.batch_job()
    job.id = spec["id"]
    job.datacenters = list(spec["datacenters"])
    job.constraints = ([Constraint(ltarget="${attr.kernel.name}",
                                   rtarget="linux", operand="=")]
                       if spec["linux_only"] else [])
    tg = job.task_groups[0]
    tg.count = spec["count"]
    tg.ephemeral_disk.size_mb = spec["disk"]
    tg.tasks[0].resources = Resources(cpu=spec["cpu"], memory_mb=spec["mem"])
    if spec.get("spread"):
        sp = spec["spread"]
        tg.spreads = [Spread(
            attribute=sp["attribute"], weight=sp["weight"],
            spread_target=[SpreadTarget(value=v, percent=p)
                           for v, p in sp["targets"].items()])]
    if spec.get("affinity"):
        aff = spec["affinity"]
        tg.affinities = [Affinity(
            ltarget="${attr.kernel.name}",
            rtarget="linux" if aff["linux"] else "windows",
            operand="=", weight=aff["weight"])]
    return job


def start_server(server_cfg: dict, name: str, stall_s: float):
    """The in-process server of a configuration. The liveness watchdog's
    stall alarm is raised as chip_smoke.py does, so a cold first compile
    does not dump every worker's stack."""
    from nomad_tpu.server.server import Server, ServerConfig

    server = Server(ServerConfig(
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        watchdog_stall_s=stall_s, **server_cfg), name=name)
    server.start()
    return server


def register_nodes(server, nodes) -> None:
    from nomad_tpu.server.fsm import NODE_REGISTER

    for node in nodes:
        server.raft_apply(NODE_REGISTER, node)


def batcher_stats(server) -> dict:
    with server.device_batcher._lock:
        return dict(server.device_batcher.stats)


def batcher_shapes(server) -> dict:
    """{padded shape the batcher has dispatched: its batch buckets compiled
    or warming}, each shape told by what differs between them: the step
    count p, and whether the spread and affinity planes are there. Empty
    where the batcher keeps no such table."""
    with server.device_batcher._lock:
        table = {k: sorted(v) for k, v in
                 getattr(server.device_batcher, "_warmed", {}).items()}
    out = {}
    for key, buckets in table.items():
        shapes = [shape for shape, _dtype in key]
        digest = zlib.crc32(repr(key).encode()) & 0xFFFF
        p = max((s[0] for s in shapes if len(s) == 1), default=0)
        out[f"p{p}-{digest:04x}"] = buckets
    return out


def quiescent(server) -> bool:
    b = server.eval_broker.stats()
    return (b["total_ready"] == 0 and b["total_unacked"] == 0
            and b["total_waiting"] == 0
            and server.plan_queue.stats()["depth"] == 0
            and (server.pipeline is None
                 or server.pipeline.stats()["inflight"] == 0))


def committed_count(state, job_id: str) -> int:
    """Placements of ``job_id`` in the state store: the job's dense blocks
    and table entries counted, no Allocation built (polling those would
    fight the workers for the GIL, as bench.py notes)."""
    key = (NS, job_id)
    return (sum(len(b.ids) for b in state._dense_by_job.get(key, ()))
            + len(state._allocs_by_job.get(key, ())))


def live_count(state, job_id: str) -> int:
    """Placements of ``job_id`` whose desired status is still ``run``,
    counted as committed_count counts them: a stopped dense slot is
    superseded by a table entry."""
    key = (NS, job_id)
    with state._lock:
        gone = state._dense_superseded
        return (sum(1 for b in state._dense_by_job.get(key, ())
                    for aid in b.ids if aid not in gone)
                + sum(1 for aid in state._allocs_by_job.get(key, ())
                      if aid in state.allocs_table
                      and state.allocs_table[aid].desired_status == "run"))


def alloc_counts(state) -> tuple:
    """(allocations the store has ever been given, those of them whose
    desired status is no longer ``run``), read under one hold of the
    store's lock. A placement that is stopped or evicted stays in the
    store as a table entry, so the first only grows; where nothing leaves
    ``run`` its change equals that of count_allocs_desired_run."""
    with state._lock:
        total = (len(state.allocs_table)
                 + sum(len(b.ids) for b in state._dense_blocks)
                 - len(state._dense_superseded))
        return total, total - state.count_allocs_desired_run()


def run_allocs(state, job_id: str) -> list:
    return [a for a in state.allocs_by_job(NS, job_id, True)
            if a.desired_status == "run"]


def recorded_score(alloc) -> float:
    """The final score the scheduler recorded for the node it chose, as
    ``nomad alloc status -verbose`` shows it; nan where it recorded none."""
    meta = alloc.metrics.score_meta if alloc.metrics is not None else None
    for m in meta or ():
        if m.node_id == alloc.node_id:
            return float(m.norm_score)
    return float("nan")


def warm_up(server, steps: list, timeout_s: float, job_of, count_of) -> int:
    """Every compiled shape the cell's traffic can reach, through the whole
    served path, one job at a time. ``steps`` is [(job dict, scale_to)]: the
    job is registered and waited for; where ``scale_to`` is set it is then
    registered again with that count, as ``nomad job scale`` would, which
    is the one way a client reaches the small step buckets that the tail of
    a partially committed plan rides (an eval of fewer placements than
    device_min_placements goes to the device only once the batcher is
    warm). Then every sibling batch bucket's background compile is joined,
    the warm jobs are stopped and their stop evals landed. ``job_of`` and
    ``count_of`` are the deployment's ``program_job`` and the number of its
    ``expected_placements``.
    Returns the number of device dispatches it took."""
    state = server.fsm.state
    d0 = batcher_stats(server)["dispatches"]
    for spec, scale_to in steps:
        for count in (spec["count"], scale_to):
            if not count:
                continue
            spec = dict(spec, count=int(count))
            want = count_of(spec)
            server.register_job(job_of(spec))
            _wait(lambda: committed_count(state, spec["id"]) >= want
                  and quiescent(server), timeout_s, f"warm job {spec['id']}")
    # each shape's sibling buckets load on a thread of their own meanwhile:
    # joined once, here, not after every job
    server.device_batcher.wait_warm()
    for spec, _ in steps:
        server.deregister_job(NS, spec["id"], purge=False)
    _wait(lambda: not any(live_count(state, spec["id"]) for spec, _ in steps)
          and quiescent(server), timeout_s, "warm jobs stopped")
    server.device_batcher.wait_warm()
    return batcher_stats(server)["dispatches"] - d0


def _wait(pred, timeout_s: float, what: str) -> None:
    t0 = time.perf_counter()
    quiet = 0
    while time.perf_counter() - t0 < timeout_s:
        quiet = quiet + 1 if pred() else 0
        if quiet >= 3:
            return
        time.sleep(0.02)
    raise RuntimeError(f"{what}: not done after {timeout_s:.0f}s")


def teardown(server) -> list:
    """Quiesce the device stack; returns device threads still alive."""
    from nomad_tpu.tpu.engine import TpuPlacementEngine

    server.device_batcher.wait_warm()
    server.stop()
    TpuPlacementEngine.shutdown()
    return [t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(("device-batcher",
                                                   "batcher-prewarm"))]
