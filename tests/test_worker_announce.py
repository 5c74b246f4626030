"""Who announces an eval to the DeviceBatcher, and who releases the token.

The batcher closes a gather on announced demand alone (tpu/batcher.py), so
the count must be exact: ``Worker._process`` announces a service or batch
eval after its index wait and before the snapshot's permit,
``engine.compute_placements`` takes the token over at its top and its
try/finally releases it on every way out, and ``_process`` releases what
nobody took. System and core evals never announce, nor does any eval under
a host algorithm. A worker that finds a backlog in the broker carries its
token from one eval's answer over to its next eval's arrival
(``announce_next``), which is what holds a flood's waves together when
there are more evals than workers. After each case ``_expected`` is 0: a
leaked token would hold every later gather up to the window cap.
"""
import math
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.testing import Harness
from nomad_tpu.server.server import Server, ServerConfig
from nomad_tpu.server.worker import Worker
from nomad_tpu.structs.structs import (
    EVAL_STATUS_PENDING,
    EVAL_TRIGGER_JOB_REGISTER,
    JOB_TYPE_CORE,
    Evaluation,
    Resources,
    SchedulerConfiguration,
)
from nomad_tpu.tpu.engine import TpuPlacementEngine
from nomad_tpu.utils import hostwork


class _Spy:
    """Counts what reaches a batcher's announcement API."""

    def __init__(self, batcher) -> None:
        self.batcher = batcher
        self.calls = []
        for name in ("expect", "cancel_expected", "run"):
            setattr(batcher, name, self._wrap(name, getattr(batcher, name)))

    def _wrap(self, name, fn):
        def wrapped(*args, **kwargs):
            with self.batcher._lock:
                before = self.batcher._expected
            if name == "run":
                self.calls.append(("run", bool(kwargs.get("expected")), before))
            else:
                self.calls.append((name, before))
            return fn(*args, **kwargs)

        return wrapped

    def names(self):
        return [c[0] for c in self.calls]

    def outstanding(self) -> int:
        with self.batcher._lock:
            return self.batcher._expected


@pytest.fixture
def served():
    """A server with a batcher and no worker thread: the test is the
    worker, one eval at a time."""
    server = Server(ServerConfig(
        num_schedulers=0, device_batch=4, device_min_placements=4,
        pipeline_async=False,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
    ))
    server.start()
    try:
        for i in range(6):
            n = mock.node()
            n.name = f"announce-{i}"
            n.compute_class()
            server.register_node(n)
        yield server, _Spy(server.device_batcher), Worker(server, 0)
    finally:
        server.stop()


def _process_next(server, worker, scheduler):
    evaluation, token = server.eval_broker.dequeue([scheduler], timeout=5.0)
    assert evaluation is not None
    worker._eval_token = token
    worker._handed_off = False
    try:
        worker._process(evaluation, token)
    finally:
        if not worker._handed_off:              # else the applier acks
            server.eval_broker.ack(evaluation.id, token)
    return evaluation


def _service_job(job_id, count, cpu=None):
    job = mock.job()
    job.id = job_id
    job.task_groups[0].count = count
    if cpu is not None:
        job.task_groups[0].tasks[0].resources = Resources(
            cpu=cpu, memory_mb=64)
    return job


def _placed(server, job):
    return len(server.fsm.state.allocs_by_job(job.namespace, job.id, True))


def _case_device_eval_arrives_with_the_workers_token(served, monkeypatch):
    server, spy, worker = served
    job = _service_job("announce-device", 5)
    server.register_job(job)
    _process_next(server, worker, "service")
    # announced once, by the worker; the engine arrived with that token
    assert spy.calls == [("expect", 0), ("run", True, 1)]
    assert _placed(server, job) == 5


def _case_small_eval_goes_to_the_host_stack_unannounced(served, monkeypatch):
    server, spy, worker = served
    job = _service_job("announce-small", 2)     # under device_min_placements
    server.register_job(job)
    seen = []
    real = worker.submit_plan
    monkeypatch.setattr(
        worker, "submit_plan",
        lambda plan: (seen.append(spy.outstanding()), real(plan))[1])
    _process_next(server, worker, "service")
    # withdrawn at the gate, before the host stack placed anything
    assert spy.calls == [("expect", 0), ("cancel_expected", 1)]
    assert seen == [0]
    assert _placed(server, job) == 2


def _case_encode_not_implemented_withdraws(served, monkeypatch):
    server, spy, worker = served
    monkeypatch.setattr(TpuPlacementEngine, "encode_eval",
                        lambda self, sched, destructive, place: NotImplemented)
    job = _service_job("announce-unsupported", 5)
    server.register_job(job)
    _process_next(server, worker, "service")
    assert spy.calls == [("expect", 0), ("cancel_expected", 1)]
    assert _placed(server, job) == 5            # the host stack's


def _case_nothing_to_place_is_released_by_the_worker(served, monkeypatch):
    server, spy, worker = served
    job = _service_job("announce-empty", 0)
    server.register_job(job)
    _process_next(server, worker, "service")
    assert spy.calls == [("expect", 0), ("cancel_expected", 1)]


def _case_a_raise_in_encode_releases(served, monkeypatch):
    server, spy, worker = served

    def boom(self, sched, destructive, place):
        raise RuntimeError("encode blew up")

    monkeypatch.setattr(TpuPlacementEngine, "encode_eval", boom)
    server.register_job(_service_job("announce-raise", 5))
    with pytest.raises(RuntimeError, match="encode blew up"):
        _process_next(server, worker, "service")
    assert spy.calls == [("expect", 0), ("cancel_expected", 1)]


def _case_a_raise_before_the_engine_releases(served, monkeypatch):
    server, spy, worker = served

    def boom(*args, **kwargs):
        raise RuntimeError("no scheduler")

    monkeypatch.setattr("nomad_tpu.server.worker.new_scheduler", boom)
    server.register_job(_service_job("announce-raise-early", 5))
    with pytest.raises(RuntimeError, match="no scheduler"):
        _process_next(server, worker, "service")
    assert spy.calls == [("expect", 0), ("cancel_expected", 1)]


def _case_a_second_attempt_announces_for_itself(served, monkeypatch):
    """The scheduler comes to compute_placements twice in one eval (its
    retry loop): the first attempt took the worker's token and here
    withdraws it; the second finds none and has the engine announce."""
    server, spy, worker = served
    real_place = TpuPlacementEngine.compute_placements
    real_encode = TpuPlacementEngine.encode_eval
    encodes = []

    def encode(self, sched, destructive, place):
        encodes.append(1)
        if len(encodes) == 1:
            return NotImplemented
        return real_encode(self, sched, destructive, place)

    def place_twice(self, sched, destructive, place):
        out = real_place(self, sched, destructive, place)
        if out is True:
            return out
        return real_place(self, sched, destructive, place)

    monkeypatch.setattr(TpuPlacementEngine, "encode_eval", encode)
    monkeypatch.setattr(TpuPlacementEngine, "compute_placements", place_twice)
    job = _service_job("announce-again", 5)
    server.register_job(job)
    _process_next(server, worker, "service")
    assert spy.calls == [("expect", 0), ("cancel_expected", 1),
                         ("expect", 0), ("run", True, 1)]
    assert _placed(server, job) == 5


def _case_system_eval_never_announces(served, monkeypatch):
    server, spy, worker = served
    job = mock.system_job()
    job.id = "announce-system"
    server.register_job(job)
    _process_next(server, worker, "system")
    assert "expect" not in spy.names() and "cancel_expected" not in spy.names()
    assert all(call[1] is False for call in spy.calls if call[0] == "run")
    assert _placed(server, job) == 6


def _case_core_eval_never_announces(served, monkeypatch):
    server, spy, worker = served
    evaluation = Evaluation(
        namespace="-", priority=200, type=JOB_TYPE_CORE,
        triggered_by="scheduled", job_id="eval-gc",
        status=EVAL_STATUS_PENDING,
        snapshot_index=server.fsm.state.latest_index,
    )
    worker._process(evaluation, "")
    assert spy.calls == []


def _case_a_planner_without_a_token_is_announced_by_the_engine(
        served, monkeypatch):
    server, spy, _worker = served
    h = Harness()
    h.device_batcher = server.device_batcher
    h.state.scheduler_set_config(
        h.next_index(),
        SchedulerConfiguration(scheduler_algorithm="tpu_binpack"))
    for i in range(4):
        n = mock.node()
        n.compute_class()
        h.state.upsert_node(h.next_index(), n)
    job = _service_job("announce-harness", 3)
    h.state.upsert_job(h.next_index(), job)
    h.process("service", Evaluation(
        priority=job.priority, type=job.type,
        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
        job_id=job.id, namespace=job.namespace,
    ))
    assert not hasattr(h, "take_announcement")
    assert spy.calls == [("expect", 0), ("run", True, 1)]
    assert sum(len(a) for p in h.plans
               for a in p.node_allocation.values()) == 3


def _case_a_host_algorithm_never_announces(served, monkeypatch):
    """Under ``binpack`` the scheduler never calls the engine, so nobody
    would take the token: it would ride through the whole host-stack
    placement and hold every device user's gather meanwhile."""
    server, spy, worker = served
    server.fsm.state.scheduler_set_config(
        server.fsm.state.latest_index + 1,
        SchedulerConfiguration(scheduler_algorithm="binpack"))
    jobs = [_service_job(f"announce-host-algorithm-{i}", 5) for i in (0, 1)]
    for job in jobs:
        server.register_job(job)        # the second is the first's backlog
    for job in jobs:
        _process_next(server, worker, "service")
    assert spy.calls == []
    assert [_placed(server, job) for job in jobs] == [5, 5]


def _case_a_backlog_carries_the_token_to_the_next_eval(served, monkeypatch):
    """Two evals ready, one worker: with the first eval's answer in hand
    the worker announces the second (the broker holds it and nobody is
    parked to take it), keeps that token through the plan's commit, and
    the second eval arrives with it: no expect() of its own."""
    server, spy, worker = served
    first, second = (_service_job(f"announce-carry-{i}", 5) for i in (0, 1))
    server.register_job(first)
    server.register_job(second)
    seen = []
    real = worker.submit_plan
    monkeypatch.setattr(
        worker, "submit_plan",
        lambda plan: (seen.append(spy.outstanding()), real(plan))[1])
    _process_next(server, worker, "service")
    assert spy.calls == [("expect", 0), ("run", True, 1), ("expect", 0)]
    assert seen == [1]                          # held through the commit
    assert worker._announced is server.device_batcher
    _process_next(server, worker, "service")
    assert spy.calls[3:] == [("run", True, 1)]  # carried, not re-announced
    assert seen == [1, 0]                       # and no backlog after it
    assert _placed(server, first) == _placed(server, second) == 5


def _case_a_carried_token_never_parks_in_the_broker(served, monkeypatch):
    """The backlog went to another worker: the run loop's dequeue comes
    back empty at once and the token is withdrawn, not parked on."""
    server, spy, _worker = served
    worker = Worker(server, 1)
    worker._announced = server.device_batcher
    server.device_batcher.expect()
    worker.start()
    try:
        deadline = time.monotonic() + 10.0
        while spy.outstanding() and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        worker.stop()
    assert spy.calls == [("expect", 0), ("cancel_expected", 1)]


@pytest.mark.parametrize("case", [
    _case_device_eval_arrives_with_the_workers_token,
    _case_small_eval_goes_to_the_host_stack_unannounced,
    _case_encode_not_implemented_withdraws,
    _case_nothing_to_place_is_released_by_the_worker,
    _case_a_raise_in_encode_releases,
    _case_a_raise_before_the_engine_releases,
    _case_a_second_attempt_announces_for_itself,
    _case_system_eval_never_announces,
    _case_core_eval_never_announces,
    _case_a_planner_without_a_token_is_announced_by_the_engine,
    _case_a_host_algorithm_never_announces,
    _case_a_backlog_carries_the_token_to_the_next_eval,
    _case_a_carried_token_never_parks_in_the_broker,
], ids=lambda f: f.__name__.replace("_case_", ""))
def test_announcement(case, served, monkeypatch):
    case(served, monkeypatch)
    _server, spy, worker = served
    assert spy.outstanding() == 0
    assert worker._announced is None


def _drained(server, jobs, count):
    return (all(_placed(server, j) == count for j in jobs)
            and server.eval_broker.stats()["total_unacked"] == 0)


def _wait(cond, timeout, msg):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {msg}"
        time.sleep(0.005)


@pytest.mark.parametrize("workers,cap", [(8, 4), (6, 8)])
def test_cohort_dequeued_together_rides_whole_waves(workers, cap):
    """The worker's announce-before-permit, end to end: ``workers`` evals
    are dequeued together while the test holds every host-work permit,
    so each worker stands announced in front of its snapshot. Only then
    do the permits go back, one worker at a time through the snapshot:
    the whole cohort was counted before its first member arrived, and it
    rides ceil(workers / cap) dispatches (one more allowed). Announced
    after the permit, the count would never reach ``workers`` here."""
    server = Server(ServerConfig(
        num_schedulers=0, device_batch=cap, device_min_placements=0,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
    ))
    server.start()
    pool = [Worker(server, i) for i in range(workers)]
    batcher = server.device_batcher
    permits = 0
    try:
        # room for every eval's placements on one snapshot: a plan the
        # applier cuts comes back for another dispatch
        for i in range(24):
            n = mock.node()
            n.name = f"cohort-{i}"
            n.compute_class()
            server.register_node(n)
        # warm every bucket of the shape, so no compile staggers the cohort
        warm = _service_job("cohort-warm", 5, cpu=50)
        server.register_job(warm)
        _process_next(server, pool[0], "service")
        batcher.wait_warm()
        _wait(lambda: _drained(server, [warm], 5), 30.0, "the warm-up eval")
        before = batcher.stats["dispatches"]

        jobs = [_service_job(f"cohort-{i}", 5, cpu=50)
                for i in range(workers)]
        for j in jobs:
            server.register_job(j)
        while hostwork.HOST_WORK_SEM.acquire(blocking=False):
            permits += 1
        for w in pool:
            w.start()

        def announced():
            with batcher._lock:
                return batcher._expected

        _wait(lambda: announced() == workers, 30.0,
              f"{workers} announcements ahead of the permit")
        assert batcher.stats["dispatches"] == before
        while permits:
            hostwork.HOST_WORK_SEM.release()
            permits -= 1
        _wait(lambda: _drained(server, jobs, 5), 60.0, "the cohort's plans")

        rode = batcher.stats["dispatches"] - before
        assert rode <= math.ceil(workers / cap) + 1, dict(batcher.stats)
        assert announced() == 0
    finally:
        while permits:
            hostwork.HOST_WORK_SEM.release()
            permits -= 1
        for w in pool:
            w.stop()
        server.stop()
