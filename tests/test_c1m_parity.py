"""The C1M deployment (benchmark/configs/c1m-5k.json) at small size on the
CPU: what the cell ``c1m-5k.arrivals`` forces of the program.

  (a) every kind of C1M template, device plan == host stack ==
      benchmark/harness/reference.py, node for node;
  (b) near ties are decided as float64 decides them (tpu/referee.py);
  (c) a partly committed stanza eval is completed on the refreshed
      snapshot with no nack (eval_broker.refresh), within upstream's bound;
  (d) concurrent stanza jobs all commit and nothing is nacked;
  (e) a 2-placement tail rides the device.
"""
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import cluster, jobs, reference, system  # noqa: E402

from nomad_tpu import mock  # noqa: E402
from nomad_tpu.server.eval_broker import (  # noqa: E402
    DEFAULT_REFRESH_ATTEMPTS,
    REFRESH_ATTEMPTS,
    EvalBroker,
)
from nomad_tpu.trace import lifecycle  # noqa: E402
from nomad_tpu.utils import metrics  # noqa: E402

with open(os.path.join(BENCH, "configs", "c1m-5k.json")) as _f:
    CONFIG = json.load(_f)
TEMPLATES = CONFIG["jobs"]["templates"]
# one row of each kind the mix has
KINDS = {"service_stanzas": 0, "service_plain": 10, "batch": 28}


def counter(name):
    total = 0.0
    sink = metrics.global_sink()
    with sink._lock:
        for iv in sink._intervals:
            agg = iv.counters.get(name)
            if agg is not None:
                total += agg.sum
    return total


def wait_for(cond, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def start(nodes=96, seed=7, **server):
    """A server over the deployment's fleet cut to ``nodes``."""
    fleet = cluster.make_fleet(dict(CONFIG["cluster"], nodes=nodes), seed)
    cfg = dict(num_schedulers=2, device_batch=4, deterministic=True,
               device_min_placements=0, scheduler_algorithm="tpu_binpack")
    cfg.update(server)
    srv = system.start_server(cfg, "c1m-test", 100.0)
    system.register_nodes(srv, system.program_nodes(fleet))
    return srv, fleet


def served(state, fleet, spec):
    """(node index, recorded score) of the job's placements by name index."""
    index = {nid: i for i, nid in enumerate(fleet.ids)}
    nodes, scores = [None] * spec["count"], [None] * spec["count"]
    allocs = system.run_allocs(state, spec["id"])
    for a in allocs:
        k = int(re.search(r"\[(\d+)\]$", a.name).group(1))
        assert nodes[k] is None, f"{a.name} placed twice"
        nodes[k], scores[k] = index[a.node_id], system.recorded_score(a)
    return allocs, nodes, scores


# -- (a) device == host stack == reference --------------------------------


@pytest.mark.parametrize("algorithm", ["binpack", "tpu_binpack"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_c1m_template_places_as_the_reference_does(kind, algorithm):
    """Three jobs of the row one after another, so the second and third
    run over the usage the first left: every placement is the node the
    float64 reference chooses, under the host stack and under the device."""
    template = dict(TEMPLATES[KINDS[kind]], count=120)
    server, fleet = start(nodes=96, seed=3100008919,
                          scheduler_algorithm=algorithm)
    try:
        state = server.fsm.state
        usage = [np.zeros(len(fleet), np.int64) for _ in range(3)]
        for rep in range(3):
            spec = jobs.job_spec(template, f"{kind}-{rep}")
            server.register_job(system.program_job(spec))
            system._wait(lambda: system.committed_count(state, spec["id"])
                         >= spec["count"] and system.quiescent(server),
                         120, spec["id"])
            allocs, nodes, scores = served(state, fleet, spec)
            mism, gap, steps = reference.compare(
                fleet, usage, spec, allocs[0].eval_id, nodes,
                scores if algorithm == "tpu_binpack" else None)
            assert (mism, steps) == (0, spec["count"]), spec["id"]
            assert gap < 1e-6
            for i in nodes:
                usage[0][i] += spec["cpu"]
                usage[1][i] += spec["mem"]
                usage[2][i] += spec["disk"]
    finally:
        assert system.teardown(server) == []


# -- (b) near ties ---------------------------------------------------------


def _pair_fleet(mems):
    n = len(mems)
    a = lambda v: np.asarray([v] * n, np.int64)  # noqa: E731
    return cluster.Fleet(
        ids=[f"00000000-0000-4000-8000-00000000000{i}" for i in range(n)],
        names=[f"n{i}" for i in range(n)],
        cpu=a(8000), mem=np.asarray(mems, np.int64), disk=a(100000),
        rcpu=a(0), rmem=a(0), rdisk=a(0), linux=np.ones(n, bool),
        dc=a(0), dc_names=["dc1"])


@pytest.mark.parametrize("mems", [
    (16_000_000, 16_000_001), (16_000_001, 16_000_000),
    # three: the pair behind a node that is no rival
    (9_000_000, 16_000_001, 16_000_000),
], ids=["larger_second", "larger_first", "behind_a_third"])
def test_near_tie_is_served_as_float64_orders_it(mems):
    """Two nodes whose float64 scores lie under one Q30 unit apart (their
    free memory shares quantise to the same Q24 fraction): the served node
    is float64's, in both orders, and the step named its rival."""
    fleet = _pair_fleet(list(mems))
    spec = {"id": "tie", "kind": "batch", "count": 4, "cpu": 100,
            "mem": 1000, "disk": 10, "datacenters": ["dc1"],
            "linux_only": False, "spread": None, "affinity": None}
    seen0 = counter("nomad.tpu_engine.near_tie_steps")
    server = system.start_server(
        dict(num_schedulers=1, device_batch=4, deterministic=True,
             device_min_placements=0, scheduler_algorithm="tpu_binpack"),
        "tie", 100.0)
    try:
        system.register_nodes(server, system.program_nodes(fleet))
        state = server.fsm.state
        server.register_job(system.program_job(spec))
        system._wait(lambda: system.committed_count(state, "tie") >= 4
                     and system.quiescent(server), 120, "tie")
        allocs, nodes, scores = served(state, fleet, spec)
        zero = [np.zeros(len(fleet), np.int64)] * 3
        mism, _gap, steps = reference.compare(
            fleet, zero, spec, allocs[0].eval_id, nodes, scores)
        assert (mism, steps) == (0, 4), nodes
        assert counter("nomad.tpu_engine.near_tie_steps") > seen0
        assert counter("nomad.tpu_engine.referee_unsupported") == 0
    finally:
        assert system.teardown(server) == []


def _harness_eval(fleet, spec, algorithm, flag_all=False):
    """One eval of ``spec`` on an empty ``fleet`` through the scheduler's
    test harness. Under the device algorithm the referee's arguments are
    captured (``flag_all``: every step is handed a rival, so it is called
    and scores every step); returns (harness, captured)."""
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs.structs import SchedulerConfiguration
    from nomad_tpu.tpu import engine as engine_mod

    from nomad_tpu.tpu import referee as ref_mod

    captured = {}

    real = ref_mod.referee
    orig_scan = engine_mod.TpuPlacementEngine.run_scan_single

    def spy(enc, job, ctx, outs, dispatch, eval_id=None):
        captured.setdefault(
            "args", (enc, job, ctx, [np.asarray(o) for o in outs]))
        return real(enc, job, ctx, outs, dispatch, eval_id)

    def all_flagged(self, enc):
        outs = list(orig_scan(self, enc))
        chosen = np.asarray(outs[0])
        near = (chosen + 1) % enc.n_real
        outs[5] = np.where(chosen >= 0, near | (near << 15),
                           -1).astype(np.int32)
        return tuple(outs)

    h = Harness()
    for node in system.program_nodes(fleet):
        h.state.upsert_node(h.next_index(), node)
    h.state.scheduler_set_config(h.next_index(), SchedulerConfiguration(
        scheduler_algorithm=algorithm))
    job = system.program_job(spec)
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval()
    ev.id = "5b1f3f4e-0000-4000-8000-000000000001"
    ev.job_id, ev.type = job.id, job.type
    h.state.upsert_evals(h.next_index(), [ev])
    ref_mod.referee = spy
    if flag_all:
        engine_mod.TpuPlacementEngine.run_scan_single = all_flagged
    try:
        h.process(spec["kind"], ev, deterministic=True)
    finally:
        ref_mod.referee = real
        engine_mod.TpuPlacementEngine.run_scan_single = orig_scan
    return h, captured


def _replay_stream(seed, n_jobs, referee_on):
    """The first ``n_jobs`` of the seed's C1M stream (a quarter of their
    tasks) one after another on 128 nodes of the seed's fleet, on the
    device; returns the reference's mismatches per job and how many steps
    the referee overruled."""
    from nomad_tpu.scheduler.scheduler import new_scheduler
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs.structs import SchedulerConfiguration
    from nomad_tpu.tpu import referee as ref_mod

    fleet = cluster.make_fleet(dict(CONFIG["cluster"], nodes=128), seed)
    index = {nid: i for i, nid in enumerate(fleet.ids)}
    h = Harness()
    for node in system.program_nodes(fleet):
        h.state.upsert_node(h.next_index(), node)
    h.state.scheduler_set_config(h.next_index(), SchedulerConfiguration(
        scheduler_algorithm="tpu_binpack"))
    stream = jobs.JobStream(TEMPLATES, seed)
    usage = [np.zeros(len(fleet), np.int64) for _ in range(3)]
    over0 = counter("nomad.tpu_engine.near_tie_overruled")
    real = ref_mod.referee
    if not referee_on:
        ref_mod.referee = lambda enc, job, ctx, outs, *rest: outs
    mismatches = []
    try:
        for i in range(n_jobs):
            spec = stream.next()
            spec["count"] //= 4
            job = system.program_job(spec)
            h.state.upsert_job(h.next_index(), job)
            ev = mock.eval()
            ev.id = "%08x-0000-4000-8000-%012x" % (seed, i)
            ev.job_id, ev.type = job.id, job.type
            h.state.upsert_evals(h.next_index(), [ev])
            # the server's frame: the ring starts at crc32(eval id)
            sched = new_scheduler(spec["kind"], h.logger, h.snapshot(), h)
            sched.deterministic = sched.ring_decorrelate = True
            sched.process(ev)
            nodes = [None] * spec["count"]
            for allocs in h.plans[-1].node_allocation.values():
                for a in allocs:
                    k = int(re.search(r"\[(\d+)\]$", a.name).group(1))
                    nodes[k] = index[a.node_id]
            mism, _gap, steps = reference.compare(
                fleet, usage, spec, ev.id, nodes, None)
            assert steps == spec["count"]
            mismatches.append(mism)
            for n in nodes:
                usage[0][n] += spec["cpu"]
                usage[1][n] += spec["mem"]
                usage[2][n] += spec["disk"]
    finally:
        ref_mod.referee = real
    return mismatches, counter("nomad.tpu_engine.near_tie_overruled") - over0


# nodes that hold the rest of a staged job and are never a rival: empty, so
# their binpack term lies far under a used node's
_FILLER = ((16000, 32768, 204800), (100, 256, 4096))


def _staged_pair(rec, referee_on=True):
    """A pair of nodes the referee logged ("near tie overruled", the
    device's pick first) in a fleet of their own, beside four empty nodes
    that take the rest of the job: each of the pair with its totals, its
    reserved share and what it held of OTHER jobs (what the log gives it at
    the step, less this ask and the job's own placements on it), and the
    job's template at its own count. A stanza job scores every node at
    every step, so its placements on the pair fall in the order they fell
    and the pair meets again in the logged state. Returns the reference's
    mismatches and the steps the referee overruled."""
    from nomad_tpu.tpu import referee as ref_mod
    from nomad_tpu.scheduler.scheduler import new_scheduler
    from nomad_tpu.scheduler.testing import Harness
    from nomad_tpu.structs.structs import SchedulerConfiguration

    pair = list(rec["pair"]) + [_FILLER + (tuple(rec["ask"]), 0)] * 4
    cols = list(zip(*[(tot + res) for tot, res, _held, _own in pair]))
    a = lambda k: np.asarray(cols[k], np.int64)  # noqa: E731
    n = len(pair)
    fleet = cluster.Fleet(
        ids=[f"00000000-0000-4000-8000-00000000000{i}" for i in range(n)],
        names=[f"n{i}" for i in range(n)],
        cpu=a(0), mem=a(1), disk=a(2), rcpu=a(3), rmem=a(4), rdisk=a(5),
        linux=np.ones(n, bool), dc=np.zeros(n, np.int64), dc_names=["dc1"])
    ask = np.asarray(rec["ask"], np.int64)
    usage = [np.asarray([held[d] - (own + 1) * ask[d]
                         for _t, _r, held, own in pair], np.int64)
             for d in range(3)]
    row = TEMPLATES[KINDS["service_stanzas" if rec["stanzas"] else "batch"]]
    spec = jobs.job_spec(dict(
        row, kind=rec["kind"], count=rec["count"], cpu=int(ask[0]),
        mem=int(ask[1]), disk=int(ask[2])), "staged")
    h = Harness()
    for i, node in enumerate(system.program_nodes(fleet)):
        h.state.upsert_node(h.next_index(), node)
        held = mock.alloc()
        held.node_id = node.id
        task = held.allocated_resources.tasks["web"]
        task.cpu_shares, task.memory_mb, task.networks = (
            int(usage[0][i]), int(usage[1][i]), [])
        held.allocated_resources.shared.disk_mb = int(usage[2][i])
        h.state.upsert_allocs(h.next_index(), [held])
    h.state.scheduler_set_config(h.next_index(), SchedulerConfiguration(
        scheduler_algorithm="tpu_binpack"))
    job = system.program_job(spec)
    h.state.upsert_job(h.next_index(), job)
    ev = mock.eval()
    ev.id = "5b1f3f4e-0000-4000-8000-000000000002"
    ev.job_id, ev.type = job.id, job.type
    h.state.upsert_evals(h.next_index(), [ev])
    over0 = counter("nomad.tpu_engine.near_tie_overruled")
    sched = new_scheduler(spec["kind"], h.logger, h.snapshot(), h)
    sched.deterministic = sched.ring_decorrelate = True
    real = ref_mod.referee
    if not referee_on:
        ref_mod.referee = lambda enc, job, ctx, outs, *rest: outs
    try:
        sched.process(ev)
    finally:
        ref_mod.referee = real
    index = {nid: i for i, nid in enumerate(fleet.ids)}
    nodes = {}
    for allocs in h.plans[-1].node_allocation.values():
        for al in allocs:
            nodes[int(re.search(r"\[(\d+)\]$", al.name).group(1))] = (
                index[al.node_id])
    placed = [nodes[k] for k in range(len(nodes))]
    mism, _gap, steps = reference.compare(
        fleet, usage, dict(spec, count=len(placed)), ev.id, placed, None)
    assert steps == len(placed)
    return mism, counter("nomad.tpu_engine.near_tie_overruled") - over0


# Seed 3100008919 (ROADMAP M3's) on the chip, the cell c1m-5k.arrivals at
# 5,000 nodes (PERF.md, call cR): the two steps of the run at which float64
# orders the device's pick and its rival the other way, as the referee logged
# them: totals, reserved and what the node held at the step (this ask
# included) of cpu MHz, memory MB and disk MB; the device's pick first. The
# float64 scores lie 6.8e-9 and 5.4e-9 apart.
SEED_3100008919_STEPS = {
    "job_100_step_286": dict(
        kind="service", stanzas=True, count=1000, ask=(16, 16, 50), pair=[
            ((4000, 8192, 102400), (100, 256, 4096), (1144, 2232, 4100), 0),
            ((4000, 8192, 102400), (100, 256, 4096), (1152, 2216, 4150), 0)]),
    "job_111_step_463": dict(
        kind="service", stanzas=True, count=1000, ask=(12, 24, 50), pair=[
            ((4000, 16384, 102400), (100, 256, 4096), (1440, 2960, 5300), 0),
            ((4000, 8192, 102400), (100, 256, 4096), (1040, 1968, 3700), 0)]),
}


@pytest.mark.parametrize("ring", ["device_pick_first", "rival_first"])
@pytest.mark.parametrize("step", sorted(SEED_3100008919_STEPS))
def test_seed_3100008919s_two_steps_are_served_as_float64_orders_them(
        step, ring):
    """The regression of ROADMAP M3: each of the two 5,000-node steps, its
    pair staged in both ring orders. Q30 alone picks the node the chip's
    scan picked and the exact comparison finds it; refereed, the served
    plan is float64's at every step."""
    rec = SEED_3100008919_STEPS[step]
    if ring == "rival_first":
        rec = dict(rec, pair=rec["pair"][::-1])
    bare, _ = _staged_pair(rec, referee_on=False)
    assert bare >= 1
    judged, overruled = _staged_pair(rec)
    assert judged == 0 and overruled >= 1


def test_a_near_tie_of_the_c1m_mix_is_served_as_float64_orders_it():
    """A near tie the mix meets by itself at 128 nodes, many steps into an
    eval (the pair holds 14 and 1 of the job's placements, so the job's
    anti-affinity term and the nodes' running Q27 products take part): the
    second job of seed 23's stream, a stanza job. Without the referee the
    exact comparison finds the mismatch; with it, none."""
    bare, _ = _replay_stream(23, 2, referee_on=False)
    assert bare[0] == 0 and bare[1] >= 1
    judged, overruled = _replay_stream(23, 2, referee_on=True)
    assert judged == [0, 0] and overruled >= 1


def test_a_fleet_beyond_the_rival_lane_is_counted_not_refereed():
    """Past 2**15 padded nodes the step names no rival (two indices do not
    fit its int32): every integer eval there is counted as not refereed,
    not passed in silence."""
    from types import SimpleNamespace

    from nomad_tpu.tpu import referee as ref_mod

    outs = tuple(np.zeros(4, np.int32) for _ in range(5)) + (
        np.full(4, -1, np.int32),)
    seen = counter(ref_mod.REFEREE_UNSUPPORTED)
    wide = SimpleNamespace(n_pad=1 << 16, dtype=np.int32)
    assert ref_mod.referee(wide, None, None, outs, None) is outs
    assert counter(ref_mod.REFEREE_UNSUPPORTED) == seen + 1
    fits = SimpleNamespace(n_pad=1 << 15, dtype=np.int32)
    assert ref_mod.referee(fits, None, None, outs, None) is outs
    assert counter(ref_mod.REFEREE_UNSUPPORTED) == seen + 1


def test_referee_scores_a_node_as_the_host_stack_does():
    """The referee's float64 score of a placement IS the host stack's
    final score: the device's plan of a stanza job, step by step, against
    the scores the host stack records for the same nodes."""
    from nomad_tpu.tpu import referee as ref_mod

    fleet = cluster.make_fleet(dict(CONFIG["cluster"], nodes=48), 11)
    spec = jobs.job_spec(dict(TEMPLATES[0], count=60), "score")
    host, _ = _harness_eval(fleet, spec, "binpack")
    host_scores = {}
    for allocs in host.plans[0].node_allocation.values():
        for a in allocs:
            k = int(re.search(r"\[(\d+)\]$", a.name).group(1))
            host_scores[k] = (a.node_id, system.recorded_score(a))
    assert len(host_scores) == 60
    _, captured = _harness_eval(fleet, spec, "tpu_binpack", flag_all=True)
    enc, job, ctx, outs = captured["args"]
    chosen = outs[0]
    ids = [n.id for n in enc.nodes]
    for k in range(60):
        node_id, want = host_scores[k]
        assert ids[int(chosen[k])] == node_id, k
        step = ref_mod._Float64Step(enc, job, ctx, chosen, k)
        assert step(int(chosen[k])) == want, k
        # the step's candidates, rebuilt on the host: a stanza job draws
        # from every feasible node, the one it chose among them
        drawn = step.candidates(0, int(outs[2][k]))
        assert int(chosen[k]) in drawn and len(drawn) > 40


@pytest.mark.parametrize("kind", ["service_stanzas", "service_plain"])
def test_a_cut_eval_goes_on_from_the_replayed_carry(kind):
    """Where float64 picks the rival, the eval is cut there and the rest
    is dispatched again over the carry replayed to that step. The replay
    is the step's own ``carry_update``: cut after the scan's OWN picks,
    the rest comes out as the scan's own tail, nodes, scores and pulls."""
    from nomad_tpu.tpu import referee as ref_mod
    from nomad_tpu.tpu.engine import TpuPlacementEngine

    fleet = cluster.make_fleet(dict(CONFIG["cluster"], nodes=48), 13)
    spec = jobs.job_spec(dict(TEMPLATES[KINDS[kind]], count=40), "cut")
    _, captured = _harness_eval(fleet, spec, "tpu_binpack", flag_all=True)
    enc, _job, _ctx, outs = captured["args"]
    chosen, scores, pulls = outs[0], outs[1], outs[2]
    assert (chosen >= 0).all()
    engine = TpuPlacementEngine.shared()
    for k in (0, 17, 38):
        rest_enc = ref_mod._replayed_rest(enc, chosen, pulls, k)
        rest = [np.asarray(o) for o in engine.run_scan_single(rest_enc)]
        assert rest_enc.p == 39 - k
        assert rest[0].tolist() == chosen[k + 1:].tolist(), k
        assert rest[1].tolist() == scores[k + 1:].tolist(), k
        assert rest[2].tolist() == pulls[k + 1:].tolist(), k


def test_an_overruled_step_is_served_and_the_rest_follows_it():
    """A referee that float64 makes overrule: the rival takes the step,
    the head is kept, and the tail is what the scan gives from there."""
    from nomad_tpu.tpu import referee as ref_mod
    from nomad_tpu.tpu.engine import TpuPlacementEngine

    fleet = cluster.make_fleet(dict(CONFIG["cluster"], nodes=48), 13)
    spec = jobs.job_spec(dict(TEMPLATES[10], count=30), "over")
    _, captured = _harness_eval(fleet, spec, "tpu_binpack", flag_all=True)
    enc, job, ctx, outs = captured["args"]
    chosen = outs[0]
    engine = TpuPlacementEngine.shared()
    rival = np.full(30, -1, np.int32)
    other = int((chosen[5] + 3) % enc.n_real)
    rival[5] = other | (other << 15)
    real = ref_mod._Float64Step.__call__
    # float64 "prefers" the rival at step 5 of the whole eval only
    ref_mod._Float64Step.__call__ = (
        lambda self, n: 1.0 if (n == other and self.enc.p == 30) else 0.5)
    over0 = counter("nomad.tpu_engine.near_tie_overruled")
    try:
        got = ref_mod.referee(enc, job, ctx, tuple(outs[:5]) + (rival,),
                              engine.run_scan_single)
    finally:
        ref_mod._Float64Step.__call__ = real
    assert counter("nomad.tpu_engine.near_tie_overruled") == over0 + 1
    served_nodes = np.asarray(got[0])
    assert len(served_nodes) == 30 and all(len(g) == 30 for g in got)
    assert served_nodes[:5].tolist() == chosen[:5].tolist()
    assert served_nodes[5] == other
    cut = chosen.copy()
    cut[5] = other
    tail = engine.run_scan_single(ref_mod._replayed_rest(enc, cut, outs[2], 5))
    assert served_nodes[6:].tolist() == np.asarray(tail[0]).tolist()


# -- (c) a partial commit is completed on the refreshed snapshot ----------


def _stanza_job(job_id, count, cpu=2100, mem=256):
    return system.program_job(jobs.job_spec(
        dict(TEMPLATES[0], count=count, cpu=cpu, mem=mem), job_id))


def test_partly_committed_stanza_eval_is_refreshed_not_nacked():
    """Two stanza jobs planned on one snapshot collide on the best nodes:
    the loser's plan commits in part, its encode cannot be patched (spread
    counts), and the eval goes back to a worker at once. No nack, no
    redelivery counted, the job whole, no placement twice."""
    lifecycle.reset()
    nacked0 = counter("nomad.pipeline.nacked")
    refreshed0 = counter("nomad.pipeline.refresh_retry")
    partial0 = counter("nomad.pipeline.partial_commit")
    server, fleet = start(nodes=8, seed=5, num_schedulers=2,
                          ring_decorrelate=False)
    try:
        server.device_batcher.window_s = 0.5
        state = server.fsm.state
        # a node holds one 2,100 MHz task of 3,900: the two jobs want the
        # same three nodes
        for name in ("ref-a", "ref-b"):
            server.register_job(_stanza_job(name, 3))
        wait_for(lambda: all(system.committed_count(state, j) >= 3
                             for j in ("ref-a", "ref-b"))
                 and system.quiescent(server), 90, "both jobs whole")
        assert counter("nomad.pipeline.partial_commit") > partial0
        assert counter("nomad.pipeline.refresh_retry") > refreshed0
        assert counter("nomad.pipeline.nacked") == nacked0
        seen = set()
        for name in ("ref-a", "ref-b"):
            allocs = system.run_allocs(state, name)
            assert len(allocs) == 3
            assert len({a.name for a in allocs}) == 3
            seen |= {a.node_id for a in allocs}
        assert len(seen) == 6   # one task a node: nobody over capacity
        recs = [r for r in lifecycle.raw_records()
                if r["job_id"] in ("ref-a", "ref-b")]
        # one delivery each, and the refreshed one ran at least twice in it
        assert {r["attempt"] for r in recs} == {1}
        assert max(r["attempts"] for r in recs) >= 2
        again = [r for r in recs if r["attempts"] >= 2][0]
        assert "refresh_wait" in {name for name, _a, _b in again["stages"]}
        assert server.eval_broker.stats()["total_waiting"] == 0
    finally:
        assert system.teardown(server) == []


@pytest.mark.parametrize("kind,limit", [
    ("service", DEFAULT_REFRESH_ATTEMPTS), ("batch", REFRESH_ATTEMPTS["batch"]),
    ("system", DEFAULT_REFRESH_ATTEMPTS)])
def test_refresh_is_bounded_as_upstream_bounds_it(kind, limit):
    """``limit`` refreshes in a row without progress and the broker hands
    the eval back for a nack; progress resets the count; the delivery
    count never moves and the eval is READY at once, at the refreshed
    index."""
    assert (kind, limit) in (("service", 5), ("batch", 2), ("system", 5))
    broker = EvalBroker()
    broker.set_enabled(True)
    ev = mock.eval()
    ev.type = kind
    broker.enqueue(ev)

    def deliver():
        got, token = broker.dequeue([kind], timeout=1.0)
        assert got is not None and got.id == ev.id
        assert broker.evals[ev.id] == 1      # the same delivery
        return got, token

    got, token = deliver()
    for i in range(3):                       # progress: never exhausted
        assert broker.refresh(ev.id, token, 100 + i, progress=True)
        assert broker.stats()["total_waiting"] == 0
        got, token = deliver()
        assert got.snapshot_index == 100 + i
    for _ in range(limit - 1):               # stalls up to the bound
        assert broker.refresh(ev.id, token, 7, progress=False)
        got, token = deliver()
    assert not broker.refresh(ev.id, token, 7, progress=False)
    assert broker.outstanding(ev.id) == token    # nothing done: the caller nacks
    broker.nack(ev.id, token)
    assert broker.stats()["total_waiting"] == 1  # the nack's own delay
    assert ev.id not in broker.refreshes
    with pytest.raises(Exception):
        broker.refresh(ev.id, "stale", 1, progress=True)
    broker.set_enabled(False)


# -- (d) concurrent stanza jobs --------------------------------------------


def test_sixteen_concurrent_stanza_jobs_all_commit_and_none_is_nacked():
    nacked0 = counter("nomad.pipeline.nacked")
    failed0 = counter("nomad.tpu_engine.dispatch_fallback_host")
    server, fleet = start(nodes=64, seed=9, num_schedulers=8, device_batch=16)
    try:
        state = server.fsm.state
        names = [f"crowd-{i}" for i in range(16)]
        built = [system.program_job(jobs.job_spec(
            dict(TEMPLATES[i % 10], count=100), name))
            for i, name in enumerate(names)]
        threads = [threading.Thread(target=server.register_job, args=(j,))
                   for j in built]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wait_for(lambda: all(system.committed_count(state, n) >= 100
                             for n in names) and system.quiescent(server),
                 240, "16 jobs whole")
        assert counter("nomad.pipeline.nacked") == nacked0
        assert counter("nomad.tpu_engine.dispatch_fallback_host") == failed0
        for n in names:
            allocs = system.run_allocs(state, n)
            assert len(allocs) == 100 and len({a.name for a in allocs}) == 100
        assert server.eval_broker.stats()["total_waiting"] == 0
    finally:
        assert system.teardown(server) == []


# -- (e) a tail of two rides the device ------------------------------------


def test_two_placement_tail_is_served_by_the_device():
    """Under the production threshold (24) a warm batcher takes an eval
    of any length: the host stack keeps only what arrives before the
    first dispatch."""
    server, fleet = start(nodes=32, seed=4, device_min_placements=24)
    try:
        state = server.fsm.state
        spec = jobs.job_spec(dict(TEMPLATES[0], count=40), "tail")
        server.register_job(system.program_job(spec))
        system._wait(lambda: system.committed_count(state, "tail") >= 40
                     and system.quiescent(server), 120, "tail 40")
        assert server.device_batcher.has_warmed()
        host0 = counter("nomad.tpu_engine.small_eval_host")
        device0 = counter("nomad.tpu_engine.small_eval_device_retry")
        server.register_job(system.program_job(dict(spec, count=42)))
        system._wait(lambda: system.committed_count(state, "tail") >= 42
                     and system.quiescent(server), 120, "tail 42")
        assert counter("nomad.tpu_engine.small_eval_host") == host0
        assert counter("nomad.tpu_engine.small_eval_device_retry") == device0 + 1
        paths = [r["path"] for r in lifecycle.raw_records()
                 if r["job_id"] == "tail"]
        assert paths and set(paths) == {"device"}
    finally:
        assert system.teardown(server) == []
