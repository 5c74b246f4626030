"""Server runtime tests: the full async scheduling loop, multi-server raft,
heartbeat failure recovery, blocked-eval unblocking."""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import InProcRaft, Server, ServerConfig
from nomad_tpu.structs.structs import (
    ALLOC_CLIENT_RUNNING,
    ALLOC_DESIRED_RUN,
    EVAL_STATUS_BLOCKED,
    EVAL_STATUS_COMPLETE,
    NODE_STATUS_DOWN,
)


@pytest.fixture
def server():
    s = Server(ServerConfig(num_schedulers=2, deterministic=True,
                            scheduler_algorithm="binpack"))
    s.start()
    yield s
    s.stop()


def wait_for(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def test_end_to_end_job_schedule(server):
    for _ in range(5):
        server.register_node(mock.node())
    job = mock.job()
    job.task_groups[0].count = 5
    eval_id = server.register_job(job)

    wait_for(
        lambda: len([
            a for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
            if a.desired_status == ALLOC_DESIRED_RUN
        ]) == 5,
        msg="5 allocs placed",
    )
    ev = server.fsm.state.eval_by_id(eval_id)
    wait_for(lambda: server.fsm.state.eval_by_id(eval_id).status == EVAL_STATUS_COMPLETE,
             msg="eval complete")
    allocs = server.fsm.state.allocs_by_job(job.namespace, job.id, True)
    assert len({a.node_id for a in allocs}) == 5  # anti-affinity spread


def test_scale_up_and_down(server):
    for _ in range(6):
        server.register_node(mock.node())
    job = mock.job()
    job.task_groups[0].count = 3
    server.register_job(job)
    wait_for(lambda: len(server.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 3,
             msg="initial 3")

    job2 = job.copy()
    job2.task_groups[0].count = 6
    server.register_job(job2)
    wait_for(
        lambda: len([
            a for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
            if a.desired_status == ALLOC_DESIRED_RUN
        ]) == 6,
        msg="scaled to 6",
    )

    job3 = job.copy()
    job3.task_groups[0].count = 2
    server.register_job(job3)
    wait_for(
        lambda: len([
            a for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
            if a.desired_status == ALLOC_DESIRED_RUN
        ]) == 2,
        msg="scaled to 2",
    )


def test_blocked_eval_unblocks_on_capacity(server):
    # No nodes: placement fails, eval blocks
    job = mock.job()
    job.task_groups[0].count = 2
    server.register_job(job)
    wait_for(lambda: server.blocked_evals.stats()["total_blocked"] >= 1,
             msg="eval blocked")
    assert server.fsm.state.allocs_by_job(job.namespace, job.id, True) == []

    # Capacity appears: blocked eval re-runs and places
    server.register_node(mock.node())
    server.register_node(mock.node())
    wait_for(
        lambda: len(server.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 2,
        msg="unblocked placement",
    )


def test_heartbeat_failure_reschedules():
    server = Server(ServerConfig(num_schedulers=2, deterministic=True,
                                 scheduler_algorithm="binpack",
                                 heartbeat_min_ttl=0.3, heartbeat_max_ttl=0.5))
    server.start()
    nodes = [mock.node() for _ in range(3)]
    ttls = [server.register_node(n) for n in nodes]
    assert all(0.3 <= t <= 0.5 for t in ttls)
    job = mock.job()
    job.task_groups[0].count = 1
    job.task_groups[0].reschedule_policy.delay_ns = 0
    server.register_job(job)

    def placed_keeping_alive():
        for n in nodes:
            server.heartbeat(n.id)
        return len(server.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 1

    wait_for(placed_keeping_alive, msg="placed")
    alloc = server.fsm.state.allocs_by_job(job.namespace, job.id, True)[0]
    first_node = alloc.node_id

    # mark running on client, then stop heartbeating ONLY that node
    ca = alloc.copy_skip_job()
    ca.client_status = ALLOC_CLIENT_RUNNING
    server.update_allocs_from_client([ca])
    hb_nodes = [n for n in nodes if n.id != first_node]

    deadline = time.monotonic() + 8
    replaced = []

    def check():
        for n in hb_nodes:
            server.heartbeat(n.id)
        node = server.fsm.state.node_by_id(first_node)
        if node.status != NODE_STATUS_DOWN:
            return False
        live = [
            a for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
            if a.desired_status == ALLOC_DESIRED_RUN and not a.terminal_status()
        ]
        replaced[:] = live
        return len(live) == 1 and live[0].node_id != first_node

    try:
        wait_for(check, timeout=10, msg="alloc replaced off dead node")
        # lost-node replacements are fresh placements (reference semantics:
        # only migrate/reschedule placements chain previous_allocation)
        assert replaced[0].id != alloc.id
        stopped = [
            a for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
            if a.id == alloc.id
        ]
        assert stopped and stopped[0].client_status == "lost"
    finally:
        server.stop()


def test_multi_server_replication_and_failover():
    raft = InProcRaft()
    cfg = ServerConfig(num_schedulers=1, deterministic=True, scheduler_algorithm="binpack")
    s1 = Server(cfg, raft=raft, name="s1")
    s2 = Server(cfg, raft=raft, name="s2")
    s3 = Server(cfg, raft=raft, name="s3")
    for s in (s1, s2, s3):
        s.start()
    try:
        assert s1.is_leader and not s2.is_leader

        for _ in range(3):
            s1.register_node(mock.node())
        job = mock.job()
        job.task_groups[0].count = 3
        s1.register_job(job)
        wait_for(lambda: len(s1.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 3,
                 msg="leader placed")
        # replicated to followers
        assert len(s2.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 3
        assert len(s3.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 3

        # failover: s2 takes leadership, can schedule new work
        raft.transfer_leadership(s2.peer)
        assert s2.is_leader and not s1.is_leader
        job2 = mock.job()
        job2.task_groups[0].count = 2
        s2.register_job(job2)
        wait_for(lambda: len(s2.fsm.state.allocs_by_job(job2.namespace, job2.id, True)) == 2,
                 msg="new leader placed")
        assert len(s1.fsm.state.allocs_by_job(job2.namespace, job2.id, True)) == 2
    finally:
        for s in (s1, s2, s3):
            s.stop()


def test_plan_rejection_on_stale_state():
    """Two plans racing for the same capacity: the applier rejects the loser."""
    from nomad_tpu.structs.structs import (
        AllocatedResources,
        AllocatedTaskResources,
        Allocation,
        Plan,
    )

    s = Server(ServerConfig(num_schedulers=0, scheduler_algorithm="binpack"))
    s.start()
    try:
        node = mock.node()  # 4000 MHz, 100 reserved
        s.register_node(node)

        def make_plan(cpu):
            job = mock.job()
            plan = Plan(priority=50, job=job)
            alloc = Allocation(
                node_id=node.id, job_id=job.id, task_group="web",
                allocated_resources=AllocatedResources(
                    tasks={"web": AllocatedTaskResources(cpu_shares=cpu, memory_mb=64)}
                ),
            )
            plan.node_allocation[node.id] = [alloc]
            return plan

        p1 = s.plan_queue.enqueue(make_plan(3000))
        r1 = p1.future.result(timeout=5)
        assert len(r1.node_allocation) == 1  # fits

        p2 = s.plan_queue.enqueue(make_plan(3000))
        r2 = p2.future.result(timeout=5)
        # 3000 + 3000 + 100 reserved > 4000: rejected, refresh forced
        assert len(r2.node_allocation) == 0
        assert r2.refresh_index > 0
    finally:
        s.stop()


def test_deregister_job_stops_allocs(server):
    for _ in range(3):
        server.register_node(mock.node())
    job = mock.job()
    job.task_groups[0].count = 3
    server.register_job(job)
    wait_for(lambda: len(server.fsm.state.allocs_by_job(job.namespace, job.id, True)) == 3,
             msg="placed")
    server.deregister_job(job.namespace, job.id)
    wait_for(
        lambda: all(
            a.desired_status != ALLOC_DESIRED_RUN
            for a in server.fsm.state.allocs_by_job(job.namespace, job.id, True)
        ),
        msg="all stopped",
    )


def test_failed_eval_reaped_and_followed_up():
    """An eval that exhausts its delivery limit lands in _failed and the
    leader reaper marks it failed + creates a follow-up."""
    s = Server(ServerConfig(num_schedulers=0, scheduler_algorithm="binpack",
                            unblock_failed_interval=0.2))
    s.start()
    try:
        s.eval_broker.delivery_limit = 1
        s.eval_broker.initial_nack_delay = 0.01
        s.eval_broker.subsequent_nack_delay = 0.01
        ev = mock.eval()
        s.raft_apply("eval-update", [ev])
        # dequeue + nack once: with delivery_limit=1 it goes to _failed
        got, token = s.eval_broker.dequeue(["service"], timeout=2)
        assert got is not None
        s.eval_broker.nack(got.id, token)
        wait_for(
            lambda: s.fsm.state.eval_by_id(ev.id) is not None
            and s.fsm.state.eval_by_id(ev.id).status == "failed",
            timeout=5, msg="eval reaped as failed",
        )
        reaped = s.fsm.state.eval_by_id(ev.id)
        assert reaped.next_eval  # follow-up chained
        assert s.fsm.state.eval_by_id(reaped.next_eval) is not None
    finally:
        s.stop()


def test_block_after_missed_unblock_reenqueues():
    """An eval blocking against a stale snapshot re-enqueues immediately if
    capacity appeared since (reference missedUnblock)."""
    s = Server(ServerConfig(num_schedulers=0, scheduler_algorithm="binpack"))
    s.start()
    try:
        n = mock.node()
        s.register_node(n)  # capacity change at some index
        ev = mock.eval()
        ev.snapshot_index = 0  # older than the node registration
        ev.status = EVAL_STATUS_BLOCKED
        s.blocked_evals.block(ev)
        # not captured: re-enqueued to the broker instead
        assert s.blocked_evals.stats()["total_blocked"] == 0
        got, token = s.eval_broker.dequeue(["service"], timeout=2)
        assert got is not None and got.id == ev.id
        s.eval_broker.ack(got.id, token)
    finally:
        s.stop()


def test_reblock_while_outstanding_requeues_after_ack():
    """An unblock racing a worker's in-flight reblock must not drop the eval.

    The worker reblocks an eval while it is still unacked in the broker; a
    capacity change then unblocks it before the ack lands. The token carried
    through BlockedEvals routes the re-enqueue via the broker's
    requeue-after-ack path (reference wrappedEval + EnqueueAll semantics).
    """
    from nomad_tpu.server.eval_broker import EvalBroker
    from nomad_tpu.server.blocked_evals import BlockedEvals
    from nomad_tpu.structs.structs import EVAL_STATUS_BLOCKED as _BLK

    broker = EvalBroker()
    broker.set_enabled(True)
    blocked = BlockedEvals(broker)
    blocked.set_enabled(True)

    ev = mock.eval()
    ev.class_eligibility = {"c1": True}
    broker.enqueue(ev)
    out, token = broker.dequeue([ev.type], timeout=1.0)
    assert out is not None and out.id == ev.id

    # Leader ordering: the raft apply fires the FSM eval-upsert hook first,
    # capturing the eval with no token...
    reblocked = ev.copy()
    reblocked.status = _BLK
    blocked.block(reblocked)
    # ...then the worker's reblock records its delivery token on the entry.
    blocked.reblock(reblocked, token)
    assert blocked.tokens[ev.id] == token

    # Capacity change unblocks while the eval is still unacked: without the
    # token this enqueue is silently dropped as a duplicate.
    blocked.unblock("c1", index=100)
    assert broker.stats()["total_ready"] == 0  # parked behind the ack

    broker.ack(ev.id, token)
    # The requeued copy is now deliverable again.
    out2, token2 = broker.dequeue([ev.type], timeout=1.0)
    assert out2 is not None and out2.id == ev.id
    assert out2.snapshot_index == 100
    broker.ack(ev.id, token2)


def test_deployment_alloc_health_counts_are_idempotent():
    """Duplicate health reports must not inflate deployment counters, and a
    healthy->unhealthy flip must move the count, not double-book it."""
    from nomad_tpu.server.fsm import DEPLOYMENT_ALLOC_HEALTH, NomadFSM
    from nomad_tpu.structs.structs import Deployment, DeploymentState

    fsm = NomadFSM()
    node = mock.node()
    fsm.state.upsert_node(1, node)
    job = mock.job()
    fsm.state.upsert_job(2, job)
    alloc = mock.alloc()
    alloc.namespace, alloc.job_id, alloc.job = job.namespace, job.id, job
    alloc.node_id = node.id
    alloc.task_group = job.task_groups[0].name
    fsm.state.upsert_allocs(3, [alloc])

    d = Deployment(
        job_id=job.id,
        namespace=job.namespace,
        job_version=job.version,
        task_groups={job.task_groups[0].name: DeploymentState(desired_total=1)},
        status="running",
    )
    fsm.state.upsert_deployment(4, d)
    alloc.deployment_id = d.id
    fsm.state.upsert_allocs(4, [alloc])

    # A report for an alloc of a different deployment must be ignored.
    other = mock.alloc()
    other.namespace, other.job_id, other.job = job.namespace, job.id, job
    other.node_id, other.task_group = node.id, job.task_groups[0].name
    other.deployment_id = "some-other-deployment"
    fsm.state.upsert_allocs(4, [other])

    def health(idx, healthy_ids, unhealthy_ids):
        fsm.apply(idx, DEPLOYMENT_ALLOC_HEALTH,
                  (d.id, healthy_ids, unhealthy_ids, 0, None, None))

    health(5, [alloc.id], [])
    health(6, [alloc.id], [])  # duplicate report
    health(6, [], [other.id])  # other deployment's alloc: ignored
    ds = fsm.state.deployment_by_id(d.id).task_groups[alloc.task_group]
    assert ds.healthy_allocs == 1
    assert ds.unhealthy_allocs == 0

    health(7, [], [alloc.id])  # flip
    ds = fsm.state.deployment_by_id(d.id).task_groups[alloc.task_group]
    assert ds.healthy_allocs == 0
    assert ds.unhealthy_allocs == 1


def test_client_sync_without_health_preserves_counters():
    """A status sync carrying no deployment_status must not erase recorded
    health — otherwise a later re-report double-counts healthy_allocs."""
    from nomad_tpu.server.fsm import DEPLOYMENT_ALLOC_HEALTH, NomadFSM
    from nomad_tpu.structs.structs import Deployment, DeploymentState

    fsm = NomadFSM()
    node = mock.node()
    fsm.state.upsert_node(1, node)
    job = mock.job()
    fsm.state.upsert_job(2, job)
    alloc = mock.alloc()
    alloc.namespace, alloc.job_id, alloc.job = job.namespace, job.id, job
    alloc.node_id = node.id
    alloc.task_group = job.task_groups[0].name
    d = Deployment(
        job_id=job.id,
        namespace=job.namespace,
        job_version=job.version,
        task_groups={alloc.task_group: DeploymentState(desired_total=2)},
        status="running",
    )
    fsm.state.upsert_deployment(3, d)
    alloc.deployment_id = d.id
    fsm.state.upsert_allocs(4, [alloc])

    fsm.apply(5, DEPLOYMENT_ALLOC_HEALTH, (d.id, [alloc.id], [], 0, None, None))
    assert fsm.state.deployment_by_id(d.id).task_groups[alloc.task_group].healthy_allocs == 1

    # plain client sync with no deployment_status
    sync = alloc.copy_skip_job()
    sync.client_status = ALLOC_CLIENT_RUNNING
    sync.deployment_status = None
    fsm.state.update_allocs_from_client(6, [sync])
    stored = fsm.state.alloc_by_id(alloc.id)
    assert stored.deployment_status is not None and stored.deployment_status.healthy is True

    # duplicate health report must still be a no-op
    fsm.apply(7, DEPLOYMENT_ALLOC_HEALTH, (d.id, [alloc.id], [], 0, None, None))
    assert fsm.state.deployment_by_id(d.id).task_groups[alloc.task_group].healthy_allocs == 1


def test_node_capacity_event_racing_block_is_not_lost():
    """unblock_node firing between eval creation and block() must be caught
    by the missed-unblock witness (system-scheduler analog of the class
    capacity race)."""
    from nomad_tpu.server.blocked_evals import BlockedEvals
    from nomad_tpu.server.eval_broker import EvalBroker
    from nomad_tpu.structs.structs import Evaluation

    broker = EvalBroker()
    broker.set_enabled(True)
    blocked = BlockedEvals(broker)
    blocked.set_enabled(True)

    ev = Evaluation(type="system", job_id="sysjob", node_id="node-1",
                    status=EVAL_STATUS_BLOCKED, snapshot_index=10)
    # capacity appears on the node AFTER the eval's snapshot but BEFORE block()
    blocked.unblock_node("node-1", 12)
    blocked.block(ev)
    # the eval must have been re-enqueued, not left blocked
    assert blocked.stats()["total_blocked"] == 0
    dequeued, token = broker.dequeue(["system"], timeout=1.0)
    assert dequeued is not None and dequeued.job_id == "sysjob"


@pytest.mark.parametrize("case", ["idle", "unmoved", "busy"])
def test_heap_is_settled_in_idle_moments_only(case, monkeypatch):
    """The leader freezes what survives a collection when the store has
    moved and the broker holds no eval; an unmoved store costs no
    collection, and an eval in flight puts it off. Leadership begins with
    a freeze and ends with the unfreeze."""
    from nomad_tpu.server import server as server_mod

    calls = []

    class Collector:
        collect = staticmethod(lambda: calls.append("collect"))
        freeze = staticmethod(lambda: calls.append("freeze"))
        unfreeze = staticmethod(lambda: calls.append("unfreeze"))

    monkeypatch.setattr(server_mod, "gc", Collector)
    # the test calls the leader task itself: keep the timer out of it
    monkeypatch.setattr(server_mod, "HEAP_SETTLE_INTERVAL_S", 3600.0)
    s = Server(ServerConfig(num_schedulers=0, scheduler_algorithm="binpack"))
    s.start()
    try:
        assert calls == ["freeze"]
        s.register_node(mock.node())
        if case == "busy":
            s.raft_apply("eval-update", [mock.eval()])
            got, token = s.eval_broker.dequeue(["service"], timeout=2)
            assert got is not None
            s._settle_heap()
            assert calls == ["freeze"]
            assert s._heap_settled_index != s.fsm.state.latest_index
            s.eval_broker.ack(got.id, token)
        s._settle_heap()
        assert calls == ["freeze", "collect", "freeze"]
        assert s._heap_settled_index == s.fsm.state.latest_index
        if case == "unmoved":
            s._settle_heap()
            assert calls == ["freeze", "collect", "freeze"]
    finally:
        s.stop()
    assert calls[-1] == "unfreeze"     # a server that stops gives it back
