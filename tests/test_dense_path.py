"""Dense plan->FSM path: placements stay as arrays (DenseTGPlacements)
from the device scan through plan submit, plan apply and FSM upsert, with
Allocation objects materialized lazily on read.

This is the TPU-native answer to the kernel-vs-system gap: the reference
already normalizes alloc DIFFS on the raft wire (plan_apply.go:324-336);
this design goes further and never materializes per-alloc objects on the
commit path at all.
"""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server, ServerConfig
from nomad_tpu.server.fsm import NODE_REGISTER
from nomad_tpu.structs.structs import (
    ALLOC_CLIENT_RUNNING,
    ALLOC_DESIRED_RUN,
    ALLOC_DESIRED_STOP,
    DenseTGPlacements,
    Resources,
)


def dense_job(job_id="dense-job", count=10, cpu=100, mem=128):
    """A service job WITHOUT network/device asks — dense-path eligible."""
    j = mock.job()
    j.id = job_id
    j.task_groups[0].count = count
    j.task_groups[0].tasks[0].resources = Resources(cpu=cpu, memory_mb=mem)
    return j


def wait_for(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def server():
    s = Server(ServerConfig(num_schedulers=2, deterministic=True,
                            device_batch=4, device_batch_window_ms=5.0,
                            device_min_placements=0))  # always device/dense
    s.start()
    yield s
    s.stop()


def _register_nodes(server, n, cpu=4000, mem=8192):
    nodes = []
    for i in range(n):
        node = mock.node()
        node.name = f"dense-{i}"
        node.node_resources.cpu_shares = cpu
        node.node_resources.memory_mb = mem
        node.compute_class()
        server.raft_apply(NODE_REGISTER, node)
        nodes.append(node)
    return nodes


def test_dense_blocks_commit_without_alloc_objects(server):
    _register_nodes(server, 5)
    job = dense_job(count=10)
    server.register_job(job)

    wait_for(
        lambda: server.fsm.state.count_allocs_desired_run() == 10,
        msg="10 dense placements",
    )
    state = server.fsm.state
    # the commit path stored dense blocks, not table allocs
    assert len(state.allocs_table) == 0
    assert sum(len(b.ids) for b in state._dense_blocks) == 10
    # reads materialize on demand and agree across every index
    allocs = state.allocs_by_job(job.namespace, job.id, True)
    assert len(allocs) == 10
    a = allocs[0]
    assert a.desired_status == ALLOC_DESIRED_RUN
    assert a.job_id == job.id
    assert a.create_index == a.modify_index > 0
    assert a.allocated_resources.tasks["web"].cpu_shares == 100
    assert a.metrics is not None and a.metrics.score_meta
    assert state.alloc_by_id(a.id) is a  # materialization is cached
    by_node = state.allocs_by_node(a.node_id)
    assert any(x.id == a.id for x in by_node)
    assert len(state.allocs()) == 10
    # names follow the reconciler's name index, one per instance
    assert {x.index() for x in allocs} == set(range(10))


def test_dense_usage_mirror_matches_materialized_usage(server):
    from nomad_tpu.structs.funcs import alloc_usage_vec

    _register_nodes(server, 4)
    job = dense_job(count=8, cpu=250, mem=256)
    server.register_job(job)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 8,
             msg="8 placed")
    state = server.fsm.state
    # mirror rows equal the sum over materialized allocs per node
    per_node = {}
    for a in state.allocs():
        u = alloc_usage_vec(a)
        row = per_node.setdefault(a.node_id, [0.0] * 4)
        for d in range(4):
            row[d] += u[d]
    for node_id, row in per_node.items():
        assert tuple(row) == tuple(state._node_usage[node_id])


def test_client_update_supersedes_dense_slot(server):
    _register_nodes(server, 3)
    job = dense_job(count=3)
    server.register_job(job)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 3,
             msg="3 placed")
    state = server.fsm.state
    target = state.allocs()[0]

    # client sync: the dense slot is superseded by a table alloc
    from nomad_tpu.server.fsm import ALLOC_CLIENT_UPDATE

    update = target.copy_skip_job()
    update.client_status = ALLOC_CLIENT_RUNNING
    server.raft_apply(ALLOC_CLIENT_UPDATE, [update])

    stored = state.alloc_by_id(target.id)
    assert stored.client_status == ALLOC_CLIENT_RUNNING
    assert target.id in state._dense_superseded
    assert target.id in state.allocs_table
    # no duplicates in any read path
    assert len(state.allocs()) == 3
    assert len(state.allocs_by_job(job.namespace, job.id, True)) == 3
    assert (
        sum(1 for a in state.allocs_by_node(target.node_id) if a.id == target.id)
        == 1
    )
    # count helper agrees
    assert state.count_allocs_desired_run() == 3


def test_job_deregister_stops_dense_allocs(server):
    _register_nodes(server, 3)
    job = dense_job(count=6)
    server.register_job(job)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 6,
             msg="6 placed")

    server.deregister_job(job.namespace, job.id, purge=False)
    wait_for(
        lambda: all(
            a.desired_status == ALLOC_DESIRED_STOP
            for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
        ),
        msg="all stopped",
    )
    state = server.fsm.state
    # stops superseded every dense slot -> fully-dead blocks compacted,
    # and the usage mirror returned to zero
    assert state._dense_blocks == [] and state._dense_superseded == set()
    assert len(state.allocs_table) == 6
    for node_id, row in state._node_usage.items():
        assert max(row) <= 1e-9, (node_id, row)


def test_fully_superseded_block_compacts_away(server):
    """Once every slot of a block is rewritten as a table alloc (steady-
    state client syncs), the block and all its index entries disappear —
    a long-lived store must not accumulate dead history."""
    from nomad_tpu.server.fsm import ALLOC_CLIENT_UPDATE

    _register_nodes(server, 2)
    job = dense_job(count=4)
    server.register_job(job)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 4,
             msg="4 placed")
    state = server.fsm.state
    assert len(state._dense_blocks) >= 1
    for a in list(state.allocs()):
        upd = a.copy_skip_job()
        upd.client_status = ALLOC_CLIENT_RUNNING
        server.raft_apply(ALLOC_CLIENT_UPDATE, [upd])
    assert state._dense_blocks == []
    assert state._dense_by_id == {}
    assert state._dense_by_job == {}
    assert state._dense_by_node == {}
    assert state._dense_superseded == set()
    assert state._dense_dead == {}
    assert len(state.allocs_table) == 4
    assert state.count_allocs_desired_run() == 4


def test_dense_two_blocks_one_node_all_or_nothing(server):
    """Per-node all-or-nothing must span ALL blocks of a plan (the object
    path's evaluateNodePlan semantics): if the combined asks of two task
    groups exceed a node, NEITHER group's placements commit there."""
    from nomad_tpu.server.plan_apply import PlanQueue, Planner
    from nomad_tpu.structs.structs import Plan

    node = mock.node()
    node.node_resources.cpu_shares = 1000
    node.node_resources.memory_mb = 1024
    node.compute_class()
    server.raft_apply(NODE_REGISTER, node)

    def mk_block(job_id, tg, cpu):
        from nomad_tpu.structs.structs import (
            AllocatedResources,
            AllocatedSharedResources,
        )

        return DenseTGPlacements(
            namespace="default", job_id=job_id, task_group=tg,
            eval_id="e1", ask_vec=(cpu, 100.0, 50.0, 0.0),
            resources_proto=AllocatedResources(
                shared=AllocatedSharedResources(disk_mb=50)
            ),
            ids=[f"{tg}-id"], names=[f"{job_id}.{tg}[0]"],
            node_ids=[node.id], node_names=[node.name],
            scores=[1.0], nodes_evaluated=[1],
        )

    plan = Plan(eval_id="e1", dense_placements=[
        mk_block("j1", "big", 700.0), mk_block("j1", "small", 400.0),
    ])
    snapshot = server.fsm.state.snapshot()
    out, partial = server.planner._evaluate_dense(
        snapshot, plan, __import__(
            "nomad_tpu.structs.structs", fromlist=["PlanResult"]
        ).PlanResult()
    )
    assert partial
    assert out == []  # combined 1100 cpu > 1000: the WHOLE node rejects


def test_dense_partial_commit_on_capacity_conflict(server):
    """Two racing dense plans over one small node: the plan applier's
    vectorized re-check must reject the loser's placements (per-node
    all-or-nothing) and hand back a refresh index."""
    node = mock.node()
    node.node_resources.cpu_shares = 1000
    node.node_resources.memory_mb = 1024
    node.compute_class()
    server.raft_apply(NODE_REGISTER, node)

    # each job fits alone (600 cpu), both together exceed 1000
    j1 = dense_job("dense-a", count=1, cpu=600, mem=300)
    j2 = dense_job("dense-b", count=1, cpu=600, mem=300)
    server.register_job(j1)
    server.register_job(j2)

    # exactly one wins; the other blocks (no capacity) — never both
    def settled():
        placed = server.fsm.state.count_allocs_desired_run()
        blocked = server.blocked_evals.stats()["total_blocked"]
        return placed == 1 and blocked >= 1

    wait_for(settled, msg="one placed, one blocked")
    time.sleep(0.3)  # any double-commit would land by now
    assert server.fsm.state.count_allocs_desired_run() == 1


def test_dense_block_survives_codec_roundtrip():
    from nomad_tpu.rpc.codec import decode, encode

    block = DenseTGPlacements(
        namespace="default", job_id="j1", task_group="web", eval_id="e1",
        ask_vec=(100.0, 128.0, 150.0, 0.0),
        ids=["a1", "a2"], names=["j1.web[0]", "j1.web[1]"],
        node_ids=["n1", "n2"], node_names=["node-1", "node-2"],
        scores=[0.5, 0.25], nodes_evaluated=[3, 3],
        nodes_available={"dc1": 2},
    )
    out = decode(encode(block))
    assert isinstance(out, DenseTGPlacements)
    assert out.ids == block.ids
    assert out.ask_vec == block.ask_vec
    assert out.node_ids == block.node_ids
    a = out.materialize(1)
    assert a.id == "a2" and a.node_id == "n2" and a.name == "j1.web[1]"


def test_dense_store_snapshot_roundtrip(server):
    """Raft-snapshot (codec) roundtrip of a store holding dense blocks:
    derived indexes rebuild, reads agree."""
    from nomad_tpu.server.wire_raft import _decode_fsm_state, _encode_fsm_state

    _register_nodes(server, 3)
    job = dense_job(count=5)
    server.register_job(job)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 5,
             msg="5 placed")

    blob = _encode_fsm_state(server.fsm.state.snapshot())
    restored = _decode_fsm_state(blob)
    assert restored.count_allocs_desired_run() == 5
    allocs = restored.allocs_by_job(job.namespace, job.id, True)
    assert len(allocs) == 5
    a = allocs[0]
    assert restored.alloc_by_id(a.id) is not None
    assert len(restored.allocs_by_node(a.node_id)) >= 1
    # usage mirror survived (it is serialized state, not derived)
    assert restored._node_usage == server.fsm.state._node_usage


def test_encode_cache_shares_arrays_across_identical_jobs(server):
    """Whole-eval encode cache: a burst of identical
    fresh jobs encodes ONCE; the cached arrays produce plans identical
    to uncached encoding, and per-eval ring offsets still differ under
    ring decorrelation."""
    _register_nodes(server, 8)

    # widen the gather window so all evals encode BEFORE any commit
    # (one usage epoch -> cache hits); production gets this from the
    # adaptive arrival-gap gather
    server.device_batcher.window_s = 0.5
    jobs = [dense_job(f"cache-{i}", count=6) for i in range(4)]
    for j in jobs:
        server.register_job(j)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 24,
             msg="24 placed")

    # every job fully placed with valid nodes
    for j in jobs:
        allocs = server.fsm.state.allocs_by_job(j.namespace, j.id, True)
        assert len(allocs) == 6
        assert all(a.node_id for a in allocs)

    # all evals gathered into one dispatch encode at ONE usage epoch:
    # at least the later three must have hit the first one's entry
    assert _cache_hits() > 0, "encode cache never hit for identical fresh jobs"


def _cache_hits():
    from nomad_tpu.utils import metrics
    total = 0.0
    sink = metrics.global_sink()
    with sink._lock:
        for iv in sink._intervals:
            agg = iv.counters.get("nomad.tpu_engine.encode_cache_hit")
            if agg is not None:
                total += agg.sum
    return total


def test_encode_cache_invalidated_by_usage_change(server):
    """A committed alloc write bumps usage_epoch: the next eval of an
    identical job must NOT reuse stale usage arrays — its placements
    must account for the capacity the first job consumed."""
    nodes = _register_nodes(server, 2, cpu=1000, mem=2048)
    # job A: 2 allocs of 400 cpu -> one per node under binpack spread?
    # (binpack PACKS; both may land one node). Either way job B's encode
    # must see A's usage: give B asks that only fit the emptier node.
    a = dense_job("use-a", count=2, cpu=400, mem=256)
    server.register_job(a)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 2,
             msg="A placed")
    usage_before = dict(server.fsm.state._node_usage)

    b = dense_job("use-b", count=2, cpu=400, mem=256)
    server.register_job(b)
    wait_for(lambda: server.fsm.state.count_allocs_desired_run() == 4,
             msg="B placed")

    # total usage must equal 4 allocs x 400 cpu across the fleet — if B
    # had reused A's pre-commit encoding AND the plan applier somehow
    # accepted it, usage would overcommit a 1000-cpu node
    for node in nodes:
        row = server.fsm.state._node_usage.get(node.id, (0, 0, 0, 0))
        assert row[0] <= 1000, f"node overcommitted: {row}"
    assert usage_before != server.fsm.state._node_usage
