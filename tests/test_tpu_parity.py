"""Plan parity: tpu_binpack engine vs host iterator pipeline.

The north-star requirement (BASELINE.md): identical Plan output to the stock
BinPackIterator given identical candidate order (deterministic mode).
"""
import copy
import random

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.structs import Affinity, Constraint
from nomad_tpu.structs.structs import (
    EVAL_TRIGGER_JOB_REGISTER,
    SCHED_ALG_TPU_BINPACK,
    Evaluation,
    SchedulerConfiguration,
    Spread,
    SpreadTarget,
)


def make_nodes(num, seed):
    rng = random.Random(seed)
    nodes = []
    for i in range(num):
        n = mock.node()
        n.name = f"node-{i}"
        n.node_resources.cpu_shares = rng.choice([2000, 4000, 8000])
        n.node_resources.memory_mb = rng.choice([4096, 8192, 16384])
        n.datacenter = rng.choice(["dc1", "dc2"])
        n.attributes["rack"] = f"r{rng.randint(0, 3)}"
        if rng.random() < 0.2:
            n.attributes["kernel.name"] = "windows"
        n.compute_class()
        nodes.append(n)
    return nodes


def run_pair(nodes, jobs, evals_for, batcher=None):
    """Run the same workload under binpack and tpu_binpack; return plans.
    With a ``batcher`` the tpu_binpack side dispatches through it."""
    plans = {}
    for alg in ("binpack", "tpu_binpack"):
        h = Harness()
        if alg == "tpu_binpack" and batcher is not None:
            h.device_batcher = batcher
        h.state.scheduler_set_config(
            h.next_index(), SchedulerConfiguration(scheduler_algorithm=alg)
        )
        for n in nodes:
            h.state.upsert_node(h.next_index(), copy.deepcopy(n))
        for job in jobs:
            h.state.upsert_job(h.next_index(), copy.deepcopy(job))
        for job in jobs:
            ev = Evaluation(
                priority=job.priority,
                type=job.type,
                triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                job_id=job.id,
                namespace=job.namespace,
            )
            h.process(evals_for(job), ev)
        plans[alg] = (h.plans, h.evals, h.create_evals)
    return plans


def plan_assignments(plans):
    """{(eval, alloc name) -> node id} across all plans."""
    out = {}
    for i, plan in enumerate(plans):
        for node_id, allocs in plan.node_allocation.items():
            for a in allocs:
                out[(i, a.name)] = node_id
    return out


def assert_parity(plans, check_failures=True):
    host_plans, host_evals, host_blocked = plans["binpack"]
    tpu_plans, tpu_evals, tpu_blocked = plans["tpu_binpack"]
    assert len(host_plans) == len(tpu_plans)
    assert plan_assignments(host_plans) == plan_assignments(tpu_plans)
    if check_failures:
        assert len(host_blocked) == len(tpu_blocked)
        for he, te in zip(host_evals, tpu_evals):
            assert he.status == te.status
            assert set(he.failed_tg_allocs) == set(te.failed_tg_allocs)


def test_parity_basic_service():
    nodes = make_nodes(20, seed=1)
    job = mock.job()
    job.task_groups[0].count = 8
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_multi_tg_multi_job():
    nodes = make_nodes(30, seed=2)
    jobs = []
    for ji in range(3):
        job = mock.job()
        tg0 = job.task_groups[0]
        job.task_groups = []
        for t in range(3):
            tg = copy.deepcopy(tg0)
            tg.name = f"tg{t}"
            tg.count = 4
            tg.tasks[0].resources.cpu = 300 + 100 * t
            job.task_groups.append(tg)
        jobs.append(job)
    assert_parity(run_pair(nodes, jobs, lambda j: "service"))


@pytest.mark.parametrize("counts, stanzas", [
    ((1,), False), ((16,), False), ((17,), True), ((50,), True),
    ((64,), False), ((3, 5), False), ((30, 2, 9), True),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else
    ("stanzas" if v else "plain"))
def test_parity_through_the_bounded_loop(counts, stanzas):
    """Host pipeline against the batcher's program, every eval a lone
    dispatch: the device's loop runs the eval's own step count inside the
    one 64-step program (no 16 bucket), and a one-group job rides a group
    axis of one (no pre-failed pad slot)."""
    from nomad_tpu.tpu.batcher import DeviceBatcher
    from nomad_tpu.trace import lifecycle

    nodes = make_nodes(40, seed=sum(counts))
    job = mock.job()
    tg0 = job.task_groups[0]
    job.task_groups = []
    for t, count in enumerate(counts):
        tg = copy.deepcopy(tg0)
        tg.name = f"tg{t}"
        tg.count = count
        tg.tasks[0].resources.cpu = 100 + 50 * t
        tg.tasks[0].resources.memory_mb = 64
        job.task_groups.append(tg)
    if stanzas:
        job.affinities = [Affinity(ltarget="${attr.rack}", rtarget="r1",
                                   operand="=", weight=50)]
        job.spreads = [Spread(
            attribute="${node.datacenter}", weight=100,
            spread_target=[SpreadTarget(value="dc1", percent=60),
                           SpreadTarget(value="dc2", percent=40)])]
    batcher = DeviceBatcher(max_batch=1)
    try:
        plans = run_pair(nodes, [job], lambda j: "service", batcher=batcher)
        recs = [d for d in lifecycle.dispatch_records()
                if d["batcher"] == batcher._serial]
    finally:
        batcher.stop()
    assert_parity(plans)
    assert len(plan_assignments(plans["tpu_binpack"][0])) == sum(counts)
    (d,) = recs
    assert (d["p_pad"], d["n_steps"], d["steps"], d["padded_steps"]) == (
        64, sum(counts), sum(counts), sum(counts))
    assert batcher.stats["batch_fallbacks"] == 0


def test_parity_batch_power_of_two():
    nodes = make_nodes(25, seed=3)
    job = mock.batch_job()
    job.task_groups[0].count = 12
    assert_parity(run_pair(nodes, [job], lambda j: "batch"))


def test_parity_affinities():
    nodes = make_nodes(20, seed=4)
    job = mock.job()
    job.task_groups[0].count = 6
    job.affinities = [Affinity("${attr.rack}", "r1", "=", 75)]
    job.task_groups[0].affinities = [Affinity("${node.datacenter}", "dc2", "=", -30)]
    job.datacenters = ["dc1", "dc2"]
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_spread():
    nodes = make_nodes(24, seed=5)
    job = mock.job()
    job.task_groups[0].count = 10
    job.datacenters = ["dc1", "dc2"]
    job.spreads = [
        Spread("${node.datacenter}", 100, [SpreadTarget("dc1", 70), SpreadTarget("dc2", 30)])
    ]
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_even_spread():
    nodes = make_nodes(16, seed=6)
    job = mock.job()
    job.task_groups[0].count = 8
    job.task_groups[0].spreads = [Spread("${attr.rack}", 50, [])]
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_distinct_hosts():
    nodes = make_nodes(15, seed=7)
    job = mock.job()
    job.task_groups[0].count = 10
    job.constraints.append(Constraint(operand="distinct_hosts"))
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_overcommitted_cluster():
    """More asks than capacity: failures + blocked evals must match."""
    nodes = make_nodes(5, seed=8)
    for n in nodes:
        n.node_resources.cpu_shares = 1000
    job = mock.job()
    job.task_groups[0].count = 20
    job.task_groups[0].tasks[0].resources.cpu = 400
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_scale_up_down():
    nodes = make_nodes(18, seed=9)
    job = mock.job()
    job.task_groups[0].count = 9

    for alg in ("binpack", "tpu_binpack"):
        pass  # runs inside run_pair-like flow below

    results = {}
    for alg in ("binpack", "tpu_binpack"):
        h = Harness()
        h.state.scheduler_set_config(
            h.next_index(), SchedulerConfiguration(scheduler_algorithm=alg)
        )
        for n in nodes:
            h.state.upsert_node(h.next_index(), copy.deepcopy(n))
        j = copy.deepcopy(job)
        h.state.upsert_job(h.next_index(), j)
        ev = Evaluation(priority=j.priority, type=j.type,
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=j.id, namespace=j.namespace)
        h.process("service", ev)
        # scale up
        j2 = copy.deepcopy(j)
        j2.task_groups[0].count = 14
        h.state.upsert_job(h.next_index(), j2)
        ev2 = Evaluation(priority=j2.priority, type=j2.type,
                         triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                         job_id=j2.id, namespace=j2.namespace)
        h.process("service", ev2)
        # destructive update
        j3 = copy.deepcopy(j2)
        j3.task_groups[0].tasks[0].config = {"command": "/bin/new"}
        h.state.upsert_job(h.next_index(), j3)
        ev3 = Evaluation(priority=j3.priority, type=j3.type,
                         triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                         job_id=j3.id, namespace=j3.namespace)
        h.process("service", ev3)
        results[alg] = h.plans

    assert plan_assignments(results["binpack"]) == plan_assignments(results["tpu_binpack"])


def test_parity_fuzz():
    """Randomized configs; any divergence is a real parity bug."""
    for seed in range(10, 16):
        rng = random.Random(seed)
        nodes = make_nodes(rng.randint(5, 40), seed=seed)
        jobs = []
        for _ in range(rng.randint(1, 3)):
            job = mock.job()
            tg = job.task_groups[0]
            tg.count = rng.randint(1, 12)
            tg.tasks[0].resources.cpu = rng.choice([100, 500, 1500])
            tg.tasks[0].resources.memory_mb = rng.choice([64, 256, 1024])
            if rng.random() < 0.5:
                job.affinities = [Affinity("${attr.rack}", f"r{rng.randint(0,3)}", "=",
                                           rng.choice([-50, 50, 100]))]
            if rng.random() < 0.5:
                job.datacenters = ["dc1", "dc2"]
                job.spreads = [Spread("${node.datacenter}", 50,
                                      [SpreadTarget("dc1", rng.choice([0, 40, 60]))])]
            if rng.random() < 0.3:
                job.constraints.append(Constraint(operand="distinct_hosts"))
            jobs.append(job)
        plans = run_pair(nodes, jobs, lambda j: "service")
        host = plan_assignments(plans["binpack"][0])
        tpu = plan_assignments(plans["tpu_binpack"][0])
        assert host == tpu, f"seed {seed}: parity diverged"


class TestPreemptionParity:
    """Device-vs-host bit-equality for the preemption engine: the TPU
    scan's eviction sets (tpu/preempt.py kernels) must match the host
    Preemptor (scheduler/preemption.py) victim-for-victim — same nodes,
    same evicted allocs, same final eviction order on each preemptor's
    ``preempted_allocations``. Both paths evaluate the same exact int
    spec, so any divergence is a real engine bug, not rounding."""

    @staticmethod
    def _run_pair(nodes, victim_jobs, preemptor_jobs):
        from nomad_tpu.structs.structs import PreemptionConfig

        plans = {}
        for alg in ("binpack", "tpu_binpack"):
            h = Harness()
            h.state.scheduler_set_config(
                h.next_index(),
                SchedulerConfiguration(
                    scheduler_algorithm=alg,
                    preemption_config=PreemptionConfig(
                        system_scheduler_enabled=True,
                        service_scheduler_enabled=True,
                        batch_scheduler_enabled=True,
                    ),
                ),
            )
            for n in nodes:
                h.state.upsert_node(h.next_index(), copy.deepcopy(n))
            # phase 1 fills the cluster with low-priority victims; phase 2
            # schedules the high-priority preemptors over the full fleet
            for phase in (victim_jobs, preemptor_jobs):
                for job in phase:
                    j = copy.deepcopy(job)
                    h.state.upsert_job(h.next_index(), j)
                    ev = Evaluation(
                        priority=j.priority, type=j.type,
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=j.id, namespace=j.namespace,
                    )
                    h.process(j.type, ev)
            plans[alg] = (h.plans, h.evals, h.create_evals)
        return plans

    @staticmethod
    def _preemption_view(plans):
        """UUID-free projection of each plan's preemption outcome: alloc
        ids differ between the two harness runs, so victims are keyed by
        (job_id, task_group) and preemptors by alloc NAME (both
        deterministic)."""
        out = {}
        for i, plan in enumerate(plans):
            stub_by_id = {}
            for nid, stubs in plan.node_preemptions.items():
                for s in stubs:
                    stub_by_id[s.id] = (nid, s.job_id, s.task_group)
                out[(i, "victims", nid)] = sorted(
                    (s.job_id, s.task_group) for s in stubs
                )
            for nid, allocs in plan.node_allocation.items():
                for a in allocs:
                    if a.preempted_allocations:
                        # ORDER preserved: the final second-pass eviction
                        # order must match, not just the victim set
                        out[(i, "by", a.name)] = [
                            stub_by_id.get(v) for v in a.preempted_allocations
                        ]
        return out

    def assert_preempt_parity(self, plans, require_preemptions=False):
        host_plans, host_evals, _hb = plans["binpack"]
        tpu_plans, tpu_evals, _tb = plans["tpu_binpack"]
        assert len(host_plans) == len(tpu_plans)
        assert plan_assignments(host_plans) == plan_assignments(tpu_plans)
        hv = self._preemption_view(host_plans)
        tv = self._preemption_view(tpu_plans)
        assert hv == tv, "preemption outcome diverged device vs host"
        for he, te in zip(host_evals, tpu_evals):
            assert he.status == te.status
            assert set(he.failed_tg_allocs) == set(te.failed_tg_allocs)
        if require_preemptions:
            assert any(k[1] == "victims" for k in tv), (
                "scenario was expected to exercise preemption"
            )

    @staticmethod
    def _plain_service(priority, count, cpu, mem):
        job = mock.job()
        job.priority = priority
        tg = job.task_groups[0]
        tg.count = count
        tg.tasks[0].resources.cpu = cpu
        tg.tasks[0].resources.memory_mb = mem
        # no network asks: networks force the host fallback by design
        tg.tasks[0].resources.networks = []
        return job

    @staticmethod
    def _uniform_nodes(num, cpu=2000, mem=4096):
        nodes = []
        for i in range(num):
            n = mock.node()
            n.name = f"pnode-{i}"
            n.node_resources.cpu_shares = cpu
            n.node_resources.memory_mb = mem
            n.compute_class()
            nodes.append(n)
        return nodes

    def test_service_preempts_low_priority(self, monkeypatch):
        """Saturated fleet, high-priority service job: placements ride
        the device (engine handled counter) and evict the same victims
        in the same order as the host oracle."""
        spy = _CounterSpy(monkeypatch)
        nodes = self._uniform_nodes(6)
        low = self._plain_service(20, 6, 1500, 2048)  # one per node
        high = self._plain_service(70, 3, 1000, 1024)  # needs eviction
        plans = self._run_pair(nodes, [low], [high])
        assert "nomad.tpu_engine.handled" in spy.calls
        self.assert_preempt_parity(plans, require_preemptions=True)

    def test_no_preemption_below_priority_delta(self):
        """Priority gap under PRIORITY_DELTA: neither path evicts and the
        blocked/failed bookkeeping matches."""
        nodes = self._uniform_nodes(4)
        low = self._plain_service(45, 4, 1500, 2048)
        close = self._plain_service(50, 2, 1000, 1024)  # delta 5 < 10
        plans = self._run_pair(nodes, [low], [close])
        self.assert_preempt_parity(plans)
        assert not any(
            k[1] == "victims" for k in
            self._preemption_view(plans["tpu_binpack"][0])
        )

    def test_system_job_preemption_parity(self):
        """System scheduler second pass: forced one-per-node placements
        that fail capacity re-enter the engine as a preemption pass."""
        nodes = self._uniform_nodes(5)
        low = self._plain_service(20, 5, 1500, 2048)
        high = mock.system_job()
        high.priority = 80
        high.task_groups[0].tasks[0].resources.cpu = 1000
        high.task_groups[0].tasks[0].resources.memory_mb = 512
        plans = self._run_pair(nodes, [low], [high])
        self.assert_preempt_parity(plans, require_preemptions=True)

    def test_preemption_fuzz(self):
        """Randomized saturated clusters + preemptors; any divergence in
        victims, order or placements is a real parity bug. Runnable on a
        real chip via NOMAD_TPU_TEST_PLATFORM=tpu — the int spec makes
        the comparison exact there too."""
        preempting_seeds = 0
        for seed in range(40, 46):
            rng = random.Random(seed)
            num = rng.randint(3, 10)
            nodes = self._uniform_nodes(
                num, cpu=rng.choice([2000, 3000]), mem=4096)
            victims = []
            for vi in range(rng.randint(1, 2)):
                victims.append(self._plain_service(
                    rng.choice([10, 20, 30]), num,
                    rng.choice([600, 900, 1200]),
                    rng.choice([512, 1024, 2048]),
                ))
            preemptor = self._plain_service(
                rng.choice([60, 80]), rng.randint(1, num),
                rng.choice([800, 1200, 1600]),
                rng.choice([1024, 2048]),
            )
            plans = self._run_pair(nodes, victims, [preemptor])
            self.assert_preempt_parity(plans)
            if any(
                k[1] == "victims"
                for k in self._preemption_view(plans["tpu_binpack"][0])
            ):
                preempting_seeds += 1
        # the fuzz must actually exercise the eviction path, not just
        # vacuously agree on preemption-free plans
        assert preempting_seeds >= 2


class _CounterSpy:
    """Record engine path counters event-wise (the in-mem sink's interval
    retention makes before/after count comparisons flaky)."""

    def __init__(self, monkeypatch):
        from nomad_tpu.utils import metrics

        self.calls = []
        orig = metrics.incr_counter

        def spy(name, value=1.0):
            self.calls.append(name)
            orig(name, value)

        monkeypatch.setattr(metrics, "incr_counter", spy)


def test_parity_device_counts_on_engine(monkeypatch):
    """Plain count-based device asks take the DEVICE path (capacity dims +
    host-side instance assignment) with plan parity."""
    spy = _CounterSpy(monkeypatch)
    nodes = [mock.nvidia_node() for _ in range(3)]
    job = mock.job()
    job.task_groups[0].count = 4
    from nomad_tpu.structs.structs import RequestedDevice

    job.task_groups[0].tasks[0].resources.devices = [RequestedDevice(name="gpu", count=1)]
    plans = run_pair(nodes, [job], lambda j: "service")
    assert "nomad.tpu_engine.handled" in spy.calls, (
        "device-count job should take the engine path"
    )
    assert len(plan_assignments(plans["tpu_binpack"][0])) == 4
    assert plan_assignments(plans["binpack"][0]) == plan_assignments(plans["tpu_binpack"][0])
    # every placed alloc carries concrete device instances
    for plan in plans["tpu_binpack"][0]:
        for allocs in plan.node_allocation.values():
            for a in allocs:
                devs = [d for tr in a.allocated_resources.tasks.values() for d in tr.devices]
                assert devs and all(d.device_ids for d in devs)


def test_parity_device_exhaustion():
    """More GPU asks than instances: failures must match the host path."""
    nodes = [mock.nvidia_node() for _ in range(2)]  # 2 nodes x 2 instances
    job = mock.job()
    job.task_groups[0].count = 6  # asks 6 GPUs, only 4 exist
    from nomad_tpu.structs.structs import RequestedDevice

    job.task_groups[0].tasks[0].resources.devices = [RequestedDevice(name="gpu", count=1)]
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_reserved_ports_on_engine(monkeypatch):
    """Reserved-port jobs take the device path: static port-feasibility
    mask + same-TG-per-node exclusion, identical plans to the host."""
    from nomad_tpu.structs.structs import Port

    spy = _CounterSpy(monkeypatch)
    nodes = make_nodes(8, seed=21)
    job = mock.job()
    job.task_groups[0].count = 5
    job.task_groups[0].tasks[0].resources.networks[0].reserved_ports = [
        Port(label="http", value=8080)
    ]
    plans = run_pair(nodes, [job], lambda j: "service")
    assert "nomad.tpu_engine.handled" in spy.calls, (
        "reserved-port job should take the engine path"
    )
    assert_parity(plans)
    # self-exclusion: no node hosts two instances (they'd collide on 8080)
    for plan in plans["tpu_binpack"][0]:
        for node_id, allocs in plan.node_allocation.items():
            assert len(allocs) <= 1


def test_parity_reserved_ports_competing_jobs():
    """Two jobs fighting for the same static port: the second job must
    avoid nodes the first claimed — identically on both paths."""
    from nomad_tpu.structs.structs import Port

    nodes = make_nodes(10, seed=22)
    jobs = []
    for i in range(2):
        job = mock.job()
        job.id = f"port-fight-{i}"
        job.task_groups[0].count = 4
        job.task_groups[0].tasks[0].resources.networks[0].reserved_ports = [
            Port(label="svc", value=9999)
        ]
        jobs.append(job)
    plans = run_pair(nodes, jobs, lambda j: "service")
    assert_parity(plans)
    # across BOTH jobs, port 9999 is claimed at most once per node
    node_claims = {}
    for plan in plans["tpu_binpack"][0]:
        for node_id, allocs in plan.node_allocation.items():
            node_claims[node_id] = node_claims.get(node_id, 0) + len(allocs)
    assert all(v <= 1 for v in node_claims.values())


def test_parity_reserved_ports_destructive_update():
    """Destructive update of a reserved-port job: the replacement may land
    on the SAME node because the eviction frees the port first."""
    from nomad_tpu.structs.structs import Port

    nodes = make_nodes(6, seed=23)
    results = {}
    for alg in ("binpack", "tpu_binpack"):
        h = Harness()
        h.state.scheduler_set_config(
            h.next_index(), SchedulerConfiguration(scheduler_algorithm=alg)
        )
        for n in nodes:
            h.state.upsert_node(h.next_index(), copy.deepcopy(n))
        job = mock.job()
        job.id = "port-update"
        job.task_groups[0].count = 3
        job.task_groups[0].tasks[0].resources.networks[0].reserved_ports = [
            Port(label="http", value=7070)
        ]
        h.state.upsert_job(h.next_index(), copy.deepcopy(job))
        ev = Evaluation(priority=50, type="service",
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=job.id, namespace="default")
        h.process("service", ev)
        # apply the plan into state, then bump the job (destructive change)
        job2 = copy.deepcopy(job)
        job2.version = 1
        job2.job_modify_index = h.next_index()
        job2.task_groups[0].tasks[0].env = {"V": "2"}
        h.state.upsert_job(h.next_index(), copy.deepcopy(job2))
        ev2 = Evaluation(priority=50, type="service",
                         triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                         job_id=job.id, namespace="default")
        h.process("service", ev2)
        results[alg] = (h.plans, h.evals, h.create_evals)
    assert_parity(results)


def test_fallback_metrics_for_unsupported(monkeypatch):
    """Unsupported shapes still fall back — and the fallback is counted."""
    spy = _CounterSpy(monkeypatch)
    nodes = make_nodes(5, seed=24)
    job = mock.job()
    job.task_groups[0].count = 2
    # cross-TG reserved-port overlap is a host-only shape
    from nomad_tpu.structs.structs import Port

    tg2 = copy.deepcopy(job.task_groups[0])
    tg2.name = "other"
    tg2.count = 1
    job.task_groups.append(tg2)
    for tg in job.task_groups:
        tg.tasks[0].resources.networks[0].reserved_ports = [
            Port(label="shared", value=12345)
        ]
    plans = run_pair(nodes, [job], lambda j: "service")
    assert "nomad.tpu_engine.fallback" in spy.calls
    assert plan_assignments(plans["binpack"][0]) == plan_assignments(plans["tpu_binpack"][0])


def test_parity_distinct_property_on_engine(monkeypatch):
    """distinct_property rides the engine (value-count feasibility carry):
    the fallback counter stays untouched and plans match the host."""
    spy = _CounterSpy(monkeypatch)
    nodes = make_nodes(12, seed=25)
    job = mock.job()
    job.task_groups[0].count = 6
    job.constraints.append(Constraint(operand="distinct_property",
                                      ltarget="${attr.rack}", rtarget="2"))
    plans = run_pair(nodes, [job], lambda j: "service")
    assert "nomad.tpu_engine.handled" in spy.calls
    assert "nomad.tpu_engine.fallback" not in spy.calls
    assert_parity(plans)
    # at most 2 allocs per rack value
    node_rack = {n.id: n.attributes["rack"] for n in nodes}
    rack_counts = {}
    for (_, _name), nid in plan_assignments(plans["tpu_binpack"][0]).items():
        r = node_rack[nid]
        rack_counts[r] = rack_counts.get(r, 0) + 1
    assert all(v <= 2 for v in rack_counts.values())


def test_parity_distinct_property_tg_level():
    """TG-level distinct_property counts only that TG's allocs."""
    nodes = make_nodes(16, seed=26)
    job = mock.job()
    tg0 = job.task_groups[0]
    job.task_groups = []
    for t in range(2):
        tg = copy.deepcopy(tg0)
        tg.name = f"tg{t}"
        tg.count = 3
        tg.constraints.append(Constraint(operand="distinct_property",
                                         ltarget="${attr.rack}"))
        job.task_groups.append(tg)
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_distinct_property_destructive_update(monkeypatch):
    """DP + in-eval evictions: the host PropertySet's cleared-value refund
    quirk can't be replayed by exact counters, so the engine must fall
    back — and the plans must still match."""
    spy = _CounterSpy(monkeypatch)
    nodes = make_nodes(12, seed=28)
    results = {}
    for alg in ("binpack", "tpu_binpack"):
        h = Harness()
        h.state.scheduler_set_config(
            h.next_index(), SchedulerConfiguration(scheduler_algorithm=alg)
        )
        for n in nodes:
            h.state.upsert_node(h.next_index(), copy.deepcopy(n))
        job = mock.job()
        job.id = "dp-update"
        job.task_groups[0].count = 5
        job.constraints.append(Constraint(operand="distinct_property",
                                          ltarget="${attr.rack}", rtarget="3"))
        h.state.upsert_job(h.next_index(), copy.deepcopy(job))
        ev = Evaluation(priority=50, type="service",
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=job.id, namespace="default")
        h.process("service", ev)
        job2 = copy.deepcopy(job)
        job2.version = 1
        job2.task_groups[0].tasks[0].config = {"command": "/bin/new"}
        h.state.upsert_job(h.next_index(), copy.deepcopy(job2))
        ev2 = Evaluation(priority=50, type="service",
                         triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                         job_id=job.id, namespace="default")
        h.process("service", ev2)
        results[alg] = (h.plans, h.evals, h.create_evals)
    assert "nomad.tpu_engine.fallback" in spy.calls
    assert plan_assignments(results["binpack"][0]) == plan_assignments(results["tpu_binpack"][0])


def test_parity_distinct_property_overcommit():
    """More instances than distinct values: failures/blocked must match."""
    nodes = make_nodes(8, seed=27)
    job = mock.job()
    job.task_groups[0].count = 7
    job.constraints.append(Constraint(operand="distinct_property",
                                      ltarget="${node.datacenter}"))
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_destructive_update_with_spread():
    """Regression: eviction must clear spread usage like the host's
    cleared_values path."""
    nodes = make_nodes(20, seed=20)
    job = mock.job()
    job.task_groups[0].count = 8
    job.datacenters = ["dc1", "dc2"]
    job.spreads = [Spread("${node.datacenter}", 100,
                          [SpreadTarget("dc1", 50), SpreadTarget("dc2", 50)])]
    results = {}
    for alg in ("binpack", "tpu_binpack"):
        h = Harness()
        h.state.scheduler_set_config(
            h.next_index(), SchedulerConfiguration(scheduler_algorithm=alg)
        )
        for n in nodes:
            h.state.upsert_node(h.next_index(), copy.deepcopy(n))
        j = copy.deepcopy(job)
        h.state.upsert_job(h.next_index(), j)
        ev = Evaluation(priority=j.priority, type=j.type,
                        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                        job_id=j.id, namespace=j.namespace)
        h.process("service", ev)
        # destructive update (config change) with the spread still in force
        j2 = copy.deepcopy(j)
        j2.task_groups[0].tasks[0].config = {"command": "/bin/new"}
        h.state.upsert_job(h.next_index(), j2)
        ev2 = Evaluation(priority=j2.priority, type=j2.type,
                         triggered_by=EVAL_TRIGGER_JOB_REGISTER,
                         job_id=j2.id, namespace=j2.namespace)
        h.process("service", ev2)
        results[alg] = h.plans
    assert plan_assignments(results["binpack"]) == plan_assignments(results["tpu_binpack"])


def test_parity_multi_tg_spread_weight_accumulation():
    """Regression: host SpreadIterator accumulates weight sums across TGs."""
    nodes = make_nodes(24, seed=21)
    job = mock.job()
    tg0 = job.task_groups[0]
    job.task_groups = []
    job.datacenters = ["dc1", "dc2"]
    job.spreads = [Spread("${node.datacenter}", 50,
                          [SpreadTarget("dc1", 60), SpreadTarget("dc2", 40)])]
    for t in range(3):
        tg = copy.deepcopy(tg0)
        tg.name = f"tg{t}"
        tg.count = 4
        job.task_groups.append(tg)
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_spread_tg_then_plain_tg():
    """Regression: MaxInt32 limit widening is sticky across TGs in an eval."""
    nodes = make_nodes(32, seed=22)
    job = mock.job()
    tg0 = job.task_groups[0]
    job.task_groups = []
    spread_tg = copy.deepcopy(tg0)
    spread_tg.name = "spready"
    spread_tg.count = 3
    spread_tg.spreads = [Spread("${attr.rack}", 50, [])]
    plain_tg = copy.deepcopy(tg0)
    plain_tg.name = "plain"
    plain_tg.count = 6
    job.task_groups = [spread_tg, plain_tg]
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_affinity_matching_no_node():
    """Regression: widening keys off stanza existence, not matches."""
    nodes = make_nodes(32, seed=23)
    job = mock.job()
    job.task_groups[0].count = 6
    job.affinities = [Affinity("${attr.rack}", "no-such-rack", "=", 100)]
    assert_parity(run_pair(nodes, [job], lambda j: "service"))


def test_parity_epoch_patched_encode_cache(monkeypatch):
    """The whole-eval encode cache's usage-epoch PATCH (engine.encode_eval
    + encode.epoch_usage_arrays): identically-shaped jobs scheduled
    SEQUENTIALLY — each commit rolls the usage epoch, so every eval
    after the first takes the patched-arrays path — must produce plans
    bit-identical to the host pipeline, and the patch counter must
    actually fire (no silent fallback to full re-encode)."""
    from nomad_tpu.utils import metrics

    calls = []
    orig = metrics.incr_counter

    def spy(name, value=1.0):
        calls.append(name)
        orig(name, value)

    monkeypatch.setattr(metrics, "incr_counter", spy)

    nodes = make_nodes(40, seed=9)
    jobs = []
    for i in range(6):
        j = mock.job()
        j.id = f"epoch-{i}"
        j.task_groups[0].count = 30
        # replace resources wholesale: the default mock task carries a
        # network ask, which (correctly) disqualifies the dense path
        from nomad_tpu.structs.structs import Resources
        j.task_groups[0].tasks[0].resources = Resources(cpu=120, memory_mb=96)
        jobs.append(j)
    plans = run_pair(nodes, jobs, lambda j: "service")
    assert "nomad.tpu_engine.encode_cache_patch" in calls, (
        "sequential same-shape jobs across commits should hit the "
        "epoch-patched cache path"
    )
    assert_parity(plans)


def test_parity_epoch_patched_with_spread_affinity(monkeypatch):
    """Same, with the full rank stack active (spread + affinity): the
    patch must leave the job-scoped spread/affinity arrays untouched
    while swapping only the usage pair."""
    from nomad_tpu.utils import metrics

    calls = []
    orig = metrics.incr_counter

    def spy(name, value=1.0):
        calls.append(name)
        orig(name, value)

    monkeypatch.setattr(metrics, "incr_counter", spy)

    nodes = make_nodes(40, seed=10)
    jobs = []
    for i in range(5):
        j = mock.job()
        j.id = f"epoch-sp-{i}"
        j.task_groups[0].count = 25
        from nomad_tpu.structs.structs import Resources
        j.task_groups[0].tasks[0].resources = Resources(cpu=100, memory_mb=64)
        j.task_groups[0].spreads = [Spread(
            attribute="${node.datacenter}", weight=50,
            spread_target=[SpreadTarget(value="dc1", percent=70),
                           SpreadTarget(value="dc2", percent=30)],
        )]
        j.task_groups[0].affinities = [Affinity(
            ltarget="${attr.kernel.name}", rtarget="linux",
            operand="=", weight=50,
        )]
        jobs.append(j)
    plans = run_pair(nodes, jobs, lambda j: "service")
    assert "nomad.tpu_engine.encode_cache_patch" in calls
    assert_parity(plans)


# ---------------------------------------------------------------------------
# Packed-mask layout (intscore packed lanes): fuzz the lane algebra the
# fused scan step relies on.
# ---------------------------------------------------------------------------


def test_packed_lane_ring_cumsum_fuzz():
    """The fused scan's ONE packed ring cumsum must be bit-identical to
    the two separate int32 ring cumsums it replaced, for any masks and
    ring offset (totals bounded by n_pad < 2^15 => no inter-lane carry,
    and both selected ring branches are lane-wise non-negative)."""
    import numpy as np

    from nomad_tpu.tpu.intscore import (
        pack_count_lanes,
        unpack_count_hi,
        unpack_count_lo,
    )

    rng = random.Random(77)
    for trial in range(200):
        n = rng.choice([4, 16, 64, 256, 1024])
        low = np.asarray([rng.random() < 0.4 for _ in range(n)])
        feas = np.asarray([rng.random() < 0.7 for _ in range(n)])
        offset = rng.randrange(n)
        iota = np.arange(n, dtype=np.int32)

        def ring_cumsum(a_int):
            s_nat = np.cumsum(a_int, dtype=np.int32)
            total = s_nat[-1]
            before = np.sum(np.where(iota < offset, a_int, 0),
                            dtype=np.int32)
            return (
                np.where(iota >= offset, s_nat - before,
                         s_nat + (total - before)),
                total,
            )

        packed_cum, packed_total = ring_cumsum(pack_count_lanes(low, feas))
        low_cum, low_total = ring_cumsum(low.astype(np.int32))
        feas_cum, feas_total = ring_cumsum(feas.astype(np.int32))
        assert (unpack_count_lo(packed_cum) == low_cum).all()
        assert (unpack_count_hi(packed_cum) == feas_cum).all()
        assert unpack_count_lo(packed_total) == low_total
        assert unpack_count_hi(packed_total) == feas_total


def test_packed_feat_plane_roundtrip_fuzz():
    """pack_feat_planes/pack_presence_lanes round-trip bit-exactly: the
    unpacked lanes and the popcount num_terms match the unpacked int32
    arithmetic they fused away."""
    import numpy as np

    from nomad_tpu.tpu.intscore import (
        FEAT_AFF_BIT,
        FEAT_FEAS_BIT,
        pack_feat_planes,
        pack_presence_lanes,
        unpack_feat_lane,
    )

    rng = random.Random(13)
    for _ in range(100):
        g, n = rng.randint(1, 6), rng.choice([8, 64, 512])
        feas = np.asarray(
            [[rng.random() < 0.5 for _ in range(n)] for _ in range(g)])
        aff = np.asarray(
            [[rng.random() < 0.5 for _ in range(n)] for _ in range(g)])
        packed = pack_feat_planes(feas, aff)
        assert packed.dtype == np.uint8
        assert (unpack_feat_lane(packed, FEAT_FEAS_BIT) == feas).all()
        assert (unpack_feat_lane(packed, FEAT_AFF_BIT) == aff).all()
        # zero-G affinity specialization: bit1 lane stays all-zero
        sparse = pack_feat_planes(feas, np.zeros((0, n), bool))
        assert (unpack_feat_lane(sparse, FEAT_AFF_BIT) == False).all()  # noqa: E712

        masks = [np.asarray([rng.random() < 0.5 for _ in range(n)])
                 for _ in range(4)]
        presence = pack_presence_lanes(*masks)
        popcounts = np.asarray(
            [bin(int(v)).count("1") for v in presence.reshape(-1)]
        ).reshape(presence.shape)
        expected = sum(m.astype(np.int32) for m in masks)
        assert (popcounts == expected).all()
