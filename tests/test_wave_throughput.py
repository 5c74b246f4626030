"""Wave throughput: mini c1m-mixed end-to-end through the async pipeline.

Tier-1 guard for the r06 perf round. A full-wave fill (chip_smoke.py's
C1M tranches) depends on three properties that used to regress
silently:

  1. WAVE FORMATION — the broker/gather cadence hands workers enough
     concurrent evals that device dispatches actually fill the eval
     batch (r05 shipped 328 evals over 21 dispatches against a 64 cap
     because the gather window amputated cohorts mid-encode).
  2. ATTRIBUTION COVERAGE — the flight recorder's critical-path ledger
     explains >=90% of the wall, INCLUDING the instrumented ``idle``
     component (r05's ~500s worker-parked gap was invisible because
     idle time was nobody's span).
  3. DEVICE/HOST PARITY — the batched device path places the same
     allocation map as the host oracle, so none of the cadence work
     above bought throughput by changing answers.

Scale is deliberately small (2K placements over 50 nodes) so this stays
tier-1.
"""
import copy
import time

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.fsm import NODE_REGISTER
from nomad_tpu.structs.structs import Resources
from nomad_tpu.trace import attribution, lifecycle


def wait_for(cond, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def mini_node(i, cpu=4000, mem=8192):
    n = mock.node()
    n.name = f"wave-{i}"
    n.node_resources.cpu_shares = cpu
    n.node_resources.memory_mb = mem
    n.compute_class()
    return n


def mini_job(job_id, count=50, cpu=50, mem=64):
    j = mock.job()
    j.id = job_id
    j.task_groups[0].count = count
    j.task_groups[0].tasks[0].resources = Resources(cpu=cpu, memory_mb=mem)
    return j


def placed_count(server, jobs):
    return sum(
        len(server.fsm.state.allocs_by_job(j.namespace, j.id, True))
        for j in jobs
    )


def test_mini_c1m_wave_fill_and_idle_coverage():
    """2K placements (40 jobs x 50) flood a server with 8 workers and a
    4-eval device batch. Asserts full wave formation (mean eval batch >=
    half the cap, no gather cut by the window) and that the bottleneck
    ledger covers >=90% of the window with the instrumented ``idle``
    component present — workers idled between server start and the
    flood, and that time must be a named span, not an attribution
    hole."""
    lifecycle.reset()
    server = Server(ServerConfig(
        num_schedulers=8, deterministic=True, device_batch=4,
        device_min_placements=0,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
    ))
    server.start()
    try:
        for i in range(50):
            server.raft_apply(NODE_REGISTER, mini_node(i))
        # let the workers visibly idle-poll before the flood: the idle
        # spans they record on their first dequeue are what satellite 1
        # promises the attribution ledger
        time.sleep(0.8)

        jobs = [mini_job(f"mini-c1m-{i}") for i in range(40)]
        for j in jobs:
            server.register_job(j)

        wait_for(lambda: placed_count(server, jobs) >= 2000,
                 timeout=180.0, msg="2000 placements")
        wait_for(
            lambda: server.eval_broker.stats().get("total_unacked", 0) == 0,
            timeout=30.0, msg="broker drained",
        )

        # (1) wave formation: dispatches filled at least half the batch
        # on average — 40 concurrent evals against a 4-eval cap must not
        # degenerate into single-eval waves. The flood is larger than the
        # worker pool, so what holds its waves together is the token a
        # worker carries from one eval's answer to its next eval's
        # arrival (Worker.announce_next): without it the announced count
        # reaches 0 between the two and stragglers dispatch alone (24-33
        # dispatches here). No gather is cut by the window cap with
        # announced evals still en route — r05's amputation.
        stats = server.device_batcher.stats
        assert stats["dispatches"] > 0, stats
        mean_batch = stats["evals"] / stats["dispatches"]
        assert mean_batch >= 2.0, (
            f"waves did not fill: {stats['evals']} evals over "
            f"{stats['dispatches']} dispatches (mean {mean_batch:.2f}, "
            f"cap 4) — gather cadence regression"
        )
        assert stats["gathers"] > 0, stats
        assert stats["full_gathers"] >= 1, dict(stats)
        closed = [d["closed_by"] for d in lifecycle.dispatch_records()
                  if d.get("source") == "batcher"]
        assert closed and "window" not in closed, closed
        with server.device_batcher._lock:
            assert server.device_batcher._expected == 0

        # (2) coverage: the ledger explains the window, idle included
        report = attribution.bottleneck_report()
        assert report["coverage"] >= 0.9, (
            f"attribution coverage {report['coverage']} < 0.9: "
            f"{report['entries']}"
        )
        components = {e["component"] for e in report["entries"]}
        assert "idle" in components, (
            f"instrumented worker idle missing from the ledger: "
            f"{sorted(components)}"
        )
        idle_s = next(
            e["seconds"] for e in report["entries"]
            if e["component"] == "idle"
        )
        assert idle_s > 0.0
    finally:
        server.stop()


def _placement_map(config, nodes, jobs):
    """Run ``jobs`` serially through a fresh server built from ``config``
    and return {(job_id, alloc name) -> node_id}. Serial registration
    (wait for each job to place) keeps both servers' scheduling
    snapshots identical so the maps are comparable bit-for-bit."""
    server = Server(config)
    server.start()
    try:
        for n in nodes:
            server.raft_apply(NODE_REGISTER, copy.deepcopy(n))
        out = {}
        for tpl in jobs:
            j = copy.deepcopy(tpl)
            server.register_job(j)
            wait_for(
                lambda: len(server.fsm.state.allocs_by_job(
                    j.namespace, j.id, True)) >= j.task_groups[0].count,
                timeout=60.0, msg=f"{j.id} placed",
            )
            for a in server.fsm.state.allocs_by_job(j.namespace, j.id, True):
                out[(a.job_id, a.name)] = a.node_id
        return out
    finally:
        server.stop()


def test_device_path_matches_host_oracle_end_to_end():
    """Placement-map parity at the SERVER level: the same nodes and jobs
    through the batched device path and through the pure-host path
    (device_batch=0) must land every allocation on the same node.
    ring_decorrelate is off on both sides because the per-eval ring
    rotation keys on eval IDs, which necessarily differ across servers."""
    nodes = [mini_node(i) for i in range(20)]
    jobs = [mini_job(f"parity-{i}", count=25) for i in range(8)]

    device_cfg = ServerConfig(
        num_schedulers=2, deterministic=True, device_batch=4,
        device_min_placements=0, ring_decorrelate=False,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
    )
    host_cfg = ServerConfig(
        num_schedulers=2, deterministic=True, device_batch=0,
        ring_decorrelate=False,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
    )

    via_device = _placement_map(device_cfg, nodes, jobs)
    via_host = _placement_map(host_cfg, nodes, jobs)

    assert len(via_device) == sum(j.task_groups[0].count for j in jobs)
    assert via_device == via_host, (
        "device path diverged from host oracle: "
        f"{sorted(set(via_device.items()) ^ set(via_host.items()))[:10]}"
    )
