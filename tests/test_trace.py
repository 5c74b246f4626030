"""nomad-trace: eval lifecycle records, the liveness watchdog, and the
/v1/trace surface.

The lifecycle tests drive a bare EvalBroker (the stamping call sites are
inside enqueue/dequeue/ack/nack, so no server is needed); the watchdog
test runs a real in-proc Server whose scheduler is replaced by a stub
that parks mid-invoke — the synthetic form of round 5's stall, where
evals sat unacked for minutes with placement flat and nothing alarmed.
"""
import logging
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server.eval_broker import EvalBroker
from nomad_tpu.structs.structs import EVAL_STATUS_PENDING, Evaluation
from nomad_tpu.trace import lifecycle
from nomad_tpu.utils import metrics


def _gauges():
    return {g["Name"]: g["Value"]
            for g in metrics.global_sink().summary()["Gauges"]}


def spin_until(fn, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out: {msg}")


# ---------------------------------------------------------------------------
# lifecycle records through the broker
# ---------------------------------------------------------------------------


def test_broker_round_trip_produces_one_acked_record():
    lifecycle.reset()
    broker = EvalBroker(nack_timeout=5.0)
    broker.set_enabled(True)
    ev = Evaluation(job_id="trace-job", type="service",
                    status=EVAL_STATUS_PENDING, priority=50)
    broker.enqueue(ev)
    assert lifecycle.summary()["inflight"] == 1

    got, token = broker.dequeue(["service"], timeout=2.0)
    assert got is not None and got.id == ev.id
    broker.ack(ev.id, token)

    s = lifecycle.summary()
    assert s["inflight"] == 0
    assert s["completed"] == 1
    assert s["outcomes"]["ack"] == 1
    assert s["eval_ms_p50"] > 0

    rec = lifecycle.snapshot()["recent"][-1]
    assert rec["eval_id"] == ev.id
    assert rec["job_id"] == "trace-job"
    assert rec["outcome"] == "ack"
    assert rec["attempt"] == 1
    assert rec["queue_ms"] is not None and rec["queue_ms"] >= 0
    assert rec["total_ms"] >= rec["queue_ms"]


def test_nack_closes_record_and_redelivery_opens_fresh_one():
    lifecycle.reset()
    broker = EvalBroker(nack_timeout=5.0, delivery_limit=10,
                        initial_nack_delay=0.02, subsequent_nack_delay=0.05)
    broker.set_enabled(True)
    ev = Evaluation(job_id="trace-nack", type="service",
                    status=EVAL_STATUS_PENDING, priority=50)
    broker.enqueue(ev)
    got, token = broker.dequeue(["service"], timeout=2.0)
    broker.nack(got.id, token)

    s = lifecycle.summary()
    assert s["outcomes"]["nack"] == 1

    # after the nack delay the broker re-enqueues: a FRESH record opens
    # carrying the bumped delivery counter as the OCC attempt number
    got2, token2 = broker.dequeue(["service"], timeout=5.0)
    assert got2 is not None and got2.id == ev.id
    broker.ack(got2.id, token2)
    recs = lifecycle.snapshot()["recent"]
    assert [r["outcome"] for r in recs] == ["nack", "ack"]
    assert recs[-1]["attempt"] == 2


def test_publish_gauges_exports_tail_latency():
    lifecycle.reset()
    metrics.global_sink().reset()
    broker = EvalBroker(nack_timeout=5.0)
    broker.set_enabled(True)
    ev = Evaluation(job_id="trace-gauge", type="service",
                    status=EVAL_STATUS_PENDING, priority=50)
    broker.enqueue(ev)
    got, token = broker.dequeue(["service"], timeout=2.0)
    broker.ack(ev.id, token)

    lifecycle.publish_gauges()
    g = _gauges()
    assert g["nomad.trace.eval_ms.p50"] > 0
    assert g["nomad.trace.inflight"] == 0
    assert "nomad.trace.slowest_inflight_ms" in g


# ---------------------------------------------------------------------------
# liveness watchdog on a synthetic stall
# ---------------------------------------------------------------------------


class _StuckScheduler:
    """Stands in for every scheduler type: parks mid-invoke until released."""

    started = threading.Event()
    release = threading.Event()

    def __init__(self, *a, **kw):
        pass

    def process(self, evaluation):
        _StuckScheduler.started.set()
        _StuckScheduler.release.wait(timeout=60)


def test_watchdog_dumps_on_stalled_eval(monkeypatch, caplog):
    from nomad_tpu.server.server import Server, ServerConfig

    lifecycle.reset()
    _StuckScheduler.started.clear()
    _StuckScheduler.release.clear()
    monkeypatch.setattr("nomad_tpu.server.worker.new_scheduler",
                        lambda *a, **kw: _StuckScheduler())

    server = Server(ServerConfig(
        num_schedulers=1, device_batch=0,
        heartbeat_min_ttl=3600, heartbeat_max_ttl=7200,
        watchdog_interval=0,  # tick manually for determinism
    ))
    server.watchdog.stall_after = 0.3
    server.start()
    try:
        server.register_job(mock.job())
        assert _StuckScheduler.started.wait(timeout=15), \
            "worker never invoked the stub scheduler"

        # first tick establishes the placed-count baseline
        assert server.watchdog.tick() is False
        time.sleep(0.4)
        with caplog.at_level(logging.WARNING,
                             logger="nomad_tpu.trace.watchdog"):
            fired = server.watchdog.tick()
        assert fired is True
        assert server.watchdog.fired == 1

        dump = caplog.text
        assert "liveness watchdog" in dump
        assert "total_unacked" in dump           # broker stats
        assert "invoke_scheduler" in dump        # per-worker current span
        assert "slowest in-flight" in dump
        assert "thread stacks" in dump

        spans = server.watchdog.worker_spans()
        assert any(s["span"] is not None
                   and s["span"]["phase"] == "invoke_scheduler"
                   for s in spans)

        # the stuck eval shows up as a nonzero slowest-in-flight gauge
        metrics.global_sink().reset()
        lifecycle.publish_gauges()
        g = _gauges()
        assert g["nomad.trace.slowest_inflight_ms"] > 300
        assert g["nomad.trace.inflight"] >= 1

        # rate limit: an immediate re-tick inside the window stays quiet
        assert server.watchdog.tick() is False
    finally:
        _StuckScheduler.release.set()
        server.stop()


# ---------------------------------------------------------------------------
# /v1/trace endpoint
# ---------------------------------------------------------------------------


def test_v1_trace_endpoint_end_to_end():
    import json
    import urllib.request

    from nomad_tpu.agent import Agent, AgentConfig

    lifecycle.reset()
    agent = Agent(AgentConfig(dev_mode=True, num_schedulers=2, name="trace1"))
    agent.start()
    try:
        agent.server.register_job(mock.job())
        spin_until(lambda: lifecycle.summary()["completed"] >= 1,
                   msg="an eval completing")
        with urllib.request.urlopen(
                agent.http_addr + "/v1/trace?recent=8", timeout=30) as resp:
            out = json.loads(resp.read().decode())
        assert out["completed"] >= 1
        assert "eval_ms_p50" in out and "slowest_inflight_ms" in out
        assert isinstance(out["inflight_evals"], list)
        assert isinstance(out["recent"], list) and len(out["recent"]) <= 8
        assert out["recent"][-1]["outcome"] in ("ack", "nack", "failed")
        # agent runs a server: worker spans ride along
        assert "workers" in out
    finally:
        agent.shutdown()


# ---------------------------------------------------------------------------
# pipeline stage spans (nomad-pipeline rides on nomad-trace)
# ---------------------------------------------------------------------------


def test_pipeline_stage_spans_and_summary():
    lifecycle.reset()
    with lifecycle.pipeline_stage("encode", "wave-1"):
        # depth is visible while the stage is open
        assert lifecycle.pipeline_summary()["encode"]["depth"] == 1
        time.sleep(0.01)
    t0 = lifecycle.pipeline_now()
    lifecycle.pipeline_record("commit", "wave-1", t0, t0 + 0.005)

    spans = lifecycle.pipeline_spans()
    assert ("encode", "wave-1") in {(s, w) for (s, w, _, _) in spans}
    assert lifecycle.pipeline_spans("commit") and \
        not lifecycle.pipeline_spans("evaluate")

    summ = lifecycle.pipeline_summary()
    assert summ["encode"]["depth"] == 0
    assert summ["encode"]["count"] == 1
    assert summ["commit"]["count"] == 1
    assert summ["commit"]["latency_ms_p95"] >= 4.0
    # every declared stage reports, populated or not
    assert set(lifecycle.PIPELINE_STAGES) <= set(summ)
    # the /v1/trace payload carries the same block
    assert lifecycle.snapshot()["pipeline"]["encode"]["count"] == 1


def test_pipeline_gauges_published():
    lifecycle.reset()
    with lifecycle.pipeline_stage("dispatch", "wave-g"):
        pass
    lifecycle.publish_gauges()
    g = _gauges()
    assert g["nomad.trace.pipeline.dispatch.count"] == 1
    assert g["nomad.trace.pipeline.dispatch.depth"] == 0
    assert "nomad.trace.pipeline.dispatch.latency_ms_p95" in g


# ---------------------------------------------------------------------------
# the eval-scoped span record: stages, new stamps, dispatch records, the
# phases that name an idle device (64-node in-process server, tpu_binpack)
# ---------------------------------------------------------------------------

STANZA_STAGES = ("snapshot", "reconcile", "encode", "device_wait", "apply",
                 "plan_evaluate", "raft_fsm")


def _stanza_job(job_id, count=10):
    from nomad_tpu.structs import Affinity, Spread, SpreadTarget
    from nomad_tpu.structs.structs import Resources

    job = mock.job()
    job.id = job_id
    job.datacenters = ["dc1", "dc2"]
    job.constraints = []
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = Resources(cpu=20, memory_mb=32)
    tg.spreads = [Spread(
        attribute="${node.datacenter}", weight=100,
        spread_target=[SpreadTarget(value="dc1", percent=60),
                       SpreadTarget(value="dc2", percent=40)])]
    tg.affinities = [Affinity(ltarget="${attr.kernel.name}", rtarget="linux",
                              operand="=", weight=50)]
    return job


@pytest.fixture(scope="module")
def stanza_run():
    """One warm job, then two stanza jobs 0.3 s apart with phases on:
    the records, the dispatch records and the phase shares of that."""
    from nomad_tpu.server.server import Server, ServerConfig
    from nomad_tpu.utils import phases

    server = Server(ServerConfig(
        num_schedulers=4, device_batch=4, deterministic=True,
        device_min_placements=0, scheduler_algorithm="tpu_binpack"))
    server.start()
    try:
        for i in range(64):
            n = mock.node()
            n.name = f"span-node-{i}"
            n.datacenter = "dc1" if i % 5 < 3 else "dc2"
            n.compute_class()
            server.register_node(n)

        def run(job_id):
            server.register_job(_stanza_job(job_id))
            spin_until(
                lambda: len(server.fsm.state.allocs_by_job(
                    "default", job_id, True)) >= 10
                and lifecycle.summary()["inflight"] == 0,
                timeout=120, msg=f"{job_id} placed and acked")

        run("span-warm")
        lifecycle.reset()
        phases.enable()
        t0 = phases.now()
        run("span-a")
        t_pause = phases.now()
        time.sleep(0.3)
        run("span-b")
        t1 = phases.now()
        phases.disable()
        yield {
            "records": {r["job_id"]: r for r in lifecycle.raw_records()},
            "dispatches": lifecycle.dispatch_records(),
            "shares": phases.wall_shares(t0, t1),
            "pause": phases.wall_shares(t_pause, t_pause + 0.3),
            "v1_trace": lifecycle.snapshot(recent=8)["recent"],
            "stats": dict(server.device_batcher.stats),
            "profile": server.device_batcher.dispatch_profile(),
        }
    finally:
        server.stop()


def _stage_starts(rec):
    first = {}
    for name, t0, _t1 in rec["stages"]:
        first.setdefault(name, t0)
    return first


def _check_stages_in_time_order(run):
    for job_id in ("span-a", "span-b"):
        rec = run["records"][job_id]
        starts = _stage_starts(rec)
        assert set(STANZA_STAGES) <= set(starts), sorted(starts)
        order = [starts[n] for n in STANZA_STAGES]
        assert order == sorted(order), list(zip(STANZA_STAGES, order))
        for _name, a, b in rec["stages"]:
            assert rec["enqueue_t"] <= a <= b <= rec["end_t"]
        # engine_gate brackets encode, the device wait and apply
        gate = [(a, b) for n, a, b in rec["stages"] if n == "engine_gate"]
        assert gate and gate[0][0] <= starts["encode"]


def _check_new_stamps(run):
    for job_id in ("span-a", "span-b"):
        rec = run["records"][job_id]
        assert rec["outcome"] == "ack" and rec["path"] == "device"
        assert 0 < rec["snapshot_index"] <= rec["commit_index"]
        assert (rec["enqueue_t"] <= rec["dequeue_t"] <= rec["submit_t"]
                <= rec["evaluate_start_t"] <= rec["commit_t"]
                <= rec["end_t"])
        # evaluate_start_t is the start of the plan_evaluate stage, and
        # commit_t the end of raft_fsm: one stamp each, not two
        assert rec["evaluate_start_t"] == _stage_starts(rec)["plan_evaluate"]
        assert rec["commit_t"] == [b for n, _a, b in rec["stages"]
                                   if n == "raft_fsm"][-1]
    a, b = run["records"]["span-a"], run["records"]["span-b"]
    assert a["commit_index"] < b["snapshot_index"] + 1


def _check_wave_lists_the_eval(run):
    by_wave = {d["wave"]: d for d in run["dispatches"]}
    assert len(by_wave) == len(run["dispatches"]) >= 2
    for job_id in ("span-a", "span-b"):
        rec = run["records"][job_id]
        assert rec["wave"] is not None and rec["waves"] == [rec["wave"]]
        d = by_wave[rec["wave"]]
        assert rec["eval_id"] in d["eval_ids"] and d["source"] == "batcher"
        # the dispatch sits inside the eval's device_wait stage
        wait = [(x, y) for n, x, y in rec["stages"] if n == "device_wait"][0]
        assert wait[0] <= d["t_first_enqueue"] <= d["t_handed"]
        assert d["t_host"] <= wait[1]


def _check_no_ready_eval_covers_the_pause(run):
    # nothing in flight between the two jobs: the pause is named, and is
    # no work
    assert run["pause"]["no_ready_eval"] >= 0.29
    assert run["pause"]["busy"] <= 0.01
    assert run["shares"]["no_ready_eval"] >= 0.29
    assert run["shares"]["untracked"] >= run["shares"]["no_ready_eval"] - 0.005


def _check_dispatch_phases(run):
    shares = run["shares"]
    for name in ("gather", "pad_stack", "h2d_launch", "kernel_wait", "d2h",
                 "device_wait", "engine_gate"):
        assert name in shares, sorted(shares)
    # the batcher's one bracket is three now; the name is the engine's own
    assert "device" not in shares
    # the dispatch's legs lie inside the workers' device wait
    legs = sum(shares[n] for n in ("pad_stack", "h2d_launch", "kernel_wait",
                                   "d2h"))
    assert legs <= shares["device_wait"] + 0.002


def _check_v1_trace_shows_stages(run):
    rec = [r for r in run["v1_trace"] if r["job_id"] == "span-b"][-1]
    assert {s["stage"] for s in rec["stages"]} >= set(STANZA_STAGES)
    assert all(s["ms"] >= 0 and s["at_ms"] >= 0 for s in rec["stages"])
    assert rec["snapshot_index"] <= rec["commit_index"]
    assert rec["plan_queue_ms"] >= 0 and rec["enqueue_to_commit_ms"] > 0
    assert rec["waves"]


def _check_stats_and_profile(run):
    stats, prof = run["stats"], run["profile"]
    assert 0 < stats["steps"] <= stats["padded_steps"]
    recs = [d for d in run["dispatches"] if d["source"] == "batcher"]
    assert prof["recorded"] == len(recs)
    assert prof["pad_stack_ms_avg"] > 0 and prof["kernel_wait_ms_avg"] >= 0
    assert 0 < prof["useful_steps_pct"] <= 100
    assert prof["compute_ms_avg"] == pytest.approx(
        prof["h2d_launch_ms_avg"] + prof["kernel_wait_ms_avg"], abs=2e-3)


@pytest.mark.parametrize("check", [
    _check_stages_in_time_order,
    _check_new_stamps,
    _check_wave_lists_the_eval,
    _check_no_ready_eval_covers_the_pause,
    _check_dispatch_phases,
    _check_v1_trace_shows_stages,
    _check_stats_and_profile,
], ids=lambda f: f.__name__.lstrip("_"))
def test_stanza_job_span_record(stanza_run, check):
    check(stanza_run)


def _broker_eval(job_id):
    broker = EvalBroker(nack_timeout=5.0, delivery_limit=10,
                        initial_nack_delay=0.02, subsequent_nack_delay=0.05)
    broker.set_enabled(True)
    ev = Evaluation(job_id=job_id, type="service",
                    status=EVAL_STATUS_PENDING, priority=50)
    broker.enqueue(ev)
    return broker, ev


def _case_redelivery_opens_a_second_record_with_its_own_stages():
    broker, ev = _broker_eval("span-nack")
    got, token = broker.dequeue(["service"], timeout=2.0)
    with lifecycle.stage("encode", ev.id):
        pass
    broker.nack(got.id, token)
    got2, token2 = broker.dequeue(["service"], timeout=5.0)
    with lifecycle.stage("encode", ev.id), lifecycle.stage("apply", ev.id):
        pass
    broker.ack(got2.id, token2)
    first, second = lifecycle.raw_records()
    assert (first["outcome"], second["outcome"]) == ("nack", "ack")
    assert [n for n, _a, _b in first["stages"]] == ["encode"]
    assert sorted(n for n, _a, _b in second["stages"]) == ["apply", "encode"]
    assert first["stages"][0][2] <= first["end_t"] <= second["enqueue_t"]
    # the retry-reuse reading: one encode ring span per delivery
    assert len(lifecycle.pipeline_spans("encode")) == 2


def _case_stage_feeds_record_ring_and_phases_once():
    from nomad_tpu.utils import phases

    broker, ev = _broker_eval("span-one-call")
    got, token = broker.dequeue(["service"], timeout=2.0)
    phases.enable()
    try:
        with lifecycle.stage("device_wait", ev.id) as span:
            assert lifecycle.current_eval() == ev.id
            assert lifecycle.pipeline_summary()["dispatch"]["depth"] == 1
            time.sleep(0.005)
        with lifecycle.stage("snapshot", ev.id):
            pass
        shares = phases.wall_shares(span.t0, phases.now())
    finally:
        phases.disable()
    assert lifecycle.current_eval() is None
    broker.ack(got.id, token)
    rec = lifecycle.raw_records()[-1]
    assert rec["stages"][0] == ("device_wait", span.t0, span.t1)
    # ring: under the name attribution reads, keyed by eval; a stage with
    # no ring name stays off it
    assert lifecycle.pipeline_spans("dispatch") == [
        ("dispatch", ev.id, span.t0, span.t1)]
    assert not lifecycle.pipeline_spans("snapshot")
    assert lifecycle.pipeline_summary()["dispatch"]["depth"] == 0
    assert shares["device_wait"] >= 0.004 and "snapshot" in shares


def _case_shared_interval_and_commit_stamp():
    broker, ev = _broker_eval("span-batch-a")
    ev2 = Evaluation(job_id="span-batch-b", type="service",
                     status=EVAL_STATUS_PENDING, priority=50)
    broker.enqueue(ev2)
    with lifecycle.stage("raft_fsm", [ev.id, ev2.id]) as commit:
        pass
    lifecycle.on_apply(ev.id, commit_t=commit.t1, commit_index=7)
    lifecycle.on_apply(ev2.id)
    recs = {r["eval_id"]: r for r in lifecycle.raw_records()}
    assert recs[ev.id]["stages"] == recs[ev2.id]["stages"] == [
        ("raft_fsm", commit.t0, commit.t1)]
    assert {w for _s, w, _a, _b in lifecycle.pipeline_spans("commit")} == {
        ev.id, ev2.id}
    assert (recs[ev.id]["commit_t"], recs[ev.id]["commit_index"]) == (
        commit.t1, 7)
    assert recs[ev2.id]["commit_t"] is None and recs[ev2.id]["apply_t"]
    # no eval to name: an aux ring span keyed by the tag, no record touched
    with lifecycle.stage("raft_fsm", tag="eval_update"):
        pass
    assert ("raft_fsm", "eval_update") in {
        (s, w) for s, w, _a, _b in lifecycle.pipeline_spans("raft_fsm")}


def _case_one_clock():
    import inspect

    from nomad_tpu.tpu import batcher
    from nomad_tpu.trace import context
    from nomad_tpu.utils import phases

    assert lifecycle._clock is phases.now is time.perf_counter
    assert lifecycle.pipeline_now() == pytest.approx(phases.now(), abs=0.05)
    for mod in (lifecycle, batcher):
        assert "monotonic" not in inspect.getsource(mod).replace(
            "wall_from_monotonic", "")
    wall = context.wall_from_monotonic(phases.now())
    assert wall == pytest.approx(time.time(), abs=0.05)


@pytest.mark.parametrize("case", [
    _case_redelivery_opens_a_second_record_with_its_own_stages,
    _case_stage_feeds_record_ring_and_phases_once,
    _case_shared_interval_and_commit_stamp,
    _case_one_clock,
], ids=lambda f: f.__name__.replace("_case_", ""))
def test_lifecycle_stage(case):
    lifecycle.reset()
    case()
