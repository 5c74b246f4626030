"""Eval lifecycle spans: enqueue -> dequeue -> invoke -> submit -> apply -> ack.

THE span store of the served path. Each DELIVERY ATTEMPT of an
evaluation gets one ``EvalTrace`` record, stamped in place by the broker,
the worker, the scheduler (host/device path tag), the batcher and the
plan applier; the intervals between the stamps are its ``stages``, each
written by ONE call at its site::

    with lifecycle.stage("encode", eval_id):
        ...

which appends ``(name, t0, t1)`` to the eval's record, feeds
``utils/phases`` under the same name while a bench has phases enabled,
and feeds the per-wave pipeline ring under the name the attribution
engine reads (``_STAGE_RING``). Beside the records sits a bounded ring of
per-dispatch records (``on_dispatch``/``dispatch_records``): one per
device dispatch, with the stamps that tie it to the run of its program
in a profiler trace. Every stamp here, in ``utils/phases`` and in the
batcher reads one clock, ``phases.now`` (``time.perf_counter``): the one
the benchmark's mark ties to the profiler's. Records move from an in-flight table to
a bounded ring buffer on ack/nack, so memory is O(inflight + ring) no
matter how long the server runs. A nacked eval's re-enqueue (after the
broker's compounding delay) opens a FRESH record; the broker's delivery
counter rides along as ``attempt`` — the OCC retry count.

Everything here is a dict op under one lock: cheap enough to stay on in
production, which is the point (round 5's 40x collapse was invisible
because nothing always-on recorded per-eval latency). Exported via the
``/v1/trace`` agent endpoint and as ``nomad.trace.*`` gauges on
``/v1/metrics`` (publish_gauges, called from the server's stats sweep).

Reference anchors: nomad/worker.go:245 (invoke_scheduler timing),
nomad/plan_apply.go:185,369,400 (submit/evaluate/apply timing) — the
same stages, joined per evaluation instead of aggregated per call.
"""
from __future__ import annotations

import itertools
import threading
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Tuple, Union

from ..utils import metric_names, metrics, phases
from ..utils.lock_witness import module_witness_lock
from ..utils.race_witness import tracked_deque, tracked_dict
from . import context as _xcontext

_DONE_CAP = 2048
_DISPATCH_CAP = 4096

_clock = phases.now


class EvalTrace:
    """One delivery attempt of one evaluation (all times ``phases.now``)."""

    __slots__ = (
        "eval_id", "job_id", "namespace", "type", "triggered_by", "priority",
        "attempt", "worker_id", "path",
        "enqueue_t", "dequeue_t", "invoke_start_t", "invoke_end_t",
        "submit_t", "evaluate_start_t", "apply_t", "commit_t", "end_t",
        "outcome", "trace_ctx",
        "snapshot_index", "commit_index", "waves", "stages",
        "attempts", "refresh_t",
    )

    def __init__(self, eval_id: str, job_id: str, namespace: str,
                 type_: str, triggered_by: str, priority: int,
                 enqueue_t: float) -> None:
        self.eval_id = eval_id
        self.job_id = job_id
        self.namespace = namespace
        self.type = type_
        self.triggered_by = triggered_by
        self.priority = priority
        self.attempt = 0
        self.worker_id: Optional[int] = None
        self.path: Optional[str] = None  # "host" | "device"
        self.enqueue_t = enqueue_t
        self.dequeue_t: Optional[float] = None
        self.invoke_start_t: Optional[float] = None
        self.invoke_end_t: Optional[float] = None
        self.submit_t: Optional[float] = None
        # the applier began evaluate_plan for this eval's (first) plan:
        # the start of its first ``plan_evaluate`` stage
        self.evaluate_start_t: Optional[float] = None
        self.apply_t: Optional[float] = None
        # the raft apply that committed its (last) plan returned, and the
        # index it returned: the program's own per-job commit time
        self.commit_t: Optional[float] = None
        self.commit_index: Optional[int] = None
        self.end_t: Optional[float] = None
        self.outcome: Optional[str] = None  # "ack" | "nack" | "failed" | "flush"
        # raft index of the snapshot the worker scheduled on (what
        # Plan.snapshot_index carries and the commit drops)
        self.snapshot_index: Optional[int] = None
        # sequence numbers of the dispatches that served it (one per
        # re-dispatch) and its (name, t0, t1) stages, in completion order
        self.waves: List[int] = []
        self.stages: List[Tuple[str, float, float]] = []
        # scheduling attempts inside this ONE delivery: 1, and one more
        # for each refresh after a partial commit (broker.refresh); the
        # record stays open across them, so its stages repeat
        self.attempts = 1
        self.refresh_t: Optional[float] = None
        # carried distributed-trace context ({"trace_id","span_id"}) so
        # the record's phase spans land in the cross-process trace
        self.trace_ctx: Optional[Dict[str, str]] = None

    def total_ms(self, now: Optional[float] = None) -> float:
        end = self.end_t if self.end_t is not None else (now or _clock())
        return (end - self.enqueue_t) * 1000.0

    def to_dict(self, now: Optional[float] = None) -> Dict[str, object]:
        def ms(a: Optional[float], b: Optional[float]) -> Optional[float]:
            if a is None or b is None:
                return None
            return round((b - a) * 1000.0, 3)

        return {
            "eval_id": self.eval_id,
            "job_id": self.job_id,
            "namespace": self.namespace,
            "type": self.type,
            "triggered_by": self.triggered_by,
            "priority": self.priority,
            "attempt": self.attempt,
            "attempts": self.attempts,
            "worker_id": self.worker_id,
            "path": self.path,
            "outcome": self.outcome,
            "queue_ms": ms(self.enqueue_t, self.dequeue_t),
            "invoke_wait_ms": ms(self.dequeue_t, self.invoke_start_t),
            "invoke_ms": ms(self.invoke_start_t, self.invoke_end_t),
            "plan_queue_ms": ms(self.submit_t, self.evaluate_start_t),
            "submit_to_apply_ms": ms(self.submit_t, self.apply_t),
            "apply_to_end_ms": ms(self.apply_t, self.end_t),
            "enqueue_to_commit_ms": ms(self.enqueue_t, self.commit_t),
            "total_ms": round(self.total_ms(now), 3),
            "snapshot_index": self.snapshot_index,
            "commit_index": self.commit_index,
            "waves": list(self.waves),
            "stages": [
                {"stage": name, "at_ms": ms(self.enqueue_t, t0),
                 "ms": ms(t0, t1)}
                for name, t0, t1 in self.stages
            ],
        }

    def raw(self) -> Dict[str, object]:
        """Raw stamps on ``phases.now``'s clock (attribution and the
        benchmark's readers join these with pipeline spans, dispatch
        records and phases on the same clock; to_dict() only exposes
        durations)."""
        return {
            "eval_id": self.eval_id,
            "job_id": self.job_id,
            "type": self.type,
            "attempt": self.attempt,
            "attempts": self.attempts,
            "path": self.path,
            "outcome": self.outcome,
            "enqueue_t": self.enqueue_t,
            "dequeue_t": self.dequeue_t,
            "invoke_start_t": self.invoke_start_t,
            "invoke_end_t": self.invoke_end_t,
            "submit_t": self.submit_t,
            "evaluate_start_t": self.evaluate_start_t,
            "apply_t": self.apply_t,
            "commit_t": self.commit_t,
            "end_t": self.end_t,
            "snapshot_index": self.snapshot_index,
            "commit_index": self.commit_index,
            "wave": self.waves[-1] if self.waves else None,
            "waves": list(self.waves),
            "stages": list(self.stages),
        }


_lock = module_witness_lock("lifecycle._lock")
_inflight: Dict[str, EvalTrace] = tracked_dict("lifecycle._inflight", {})
_done: "deque[EvalTrace]" = tracked_deque("lifecycle._done", maxlen=_DONE_CAP)
_counts: Dict[str, int] = {"ack": 0, "nack": 0, "failed": 0, "flush": 0}
# one record per device dispatch (on_dispatch), newest last
_dispatches: "deque[Dict[str, object]]" = deque(maxlen=_DISPATCH_CAP)
_wave_seq = itertools.count(1)
# since when the in-flight table has been empty (None while it is not):
# closed into a ``no_ready_eval`` phases interval by the next enqueue
_idle_since: Optional[float] = _clock()

# -- pipeline stage spans ---------------------------------------------------
#
# The eval-lifecycle pipeline (nomad_tpu/pipeline/) decomposes the leader's
# placement path into stages; each stage execution for one wave (wave id ==
# eval id) records a [start, end) span here. Unlike utils/phases (union wall
# shares, bench-window only), these are per-wave and always on: the overlap
# stress test reads raw spans to prove wave N+1's encode interleaves wave
# N's device dispatch, and the OCC-storm test counts encode spans per wave
# to prove re-dispatch skipped the encode stage.

PIPELINE_STAGES = ("encode", "dispatch", "evaluate", "commit")
_PIPE_CAP = 4096

#: aux stage name for worker dequeue idle: a scheduler worker that polls
#: the broker and finds nothing records its whole contiguous idle period
#: as ONE span (coalesced at the worker, one span per busy->idle->busy
#: transition, so 64 workers cannot flood the ring). These spans are what
#: lets attribution decompose the busy-vs-window residual explicitly
#: instead of leaving it unattributed (r05's invisible 498s).
IDLE_STAGE = "idle"

_pipe_open: Dict[str, int] = {s: 0 for s in PIPELINE_STAGES}
_pipe_done: Dict[str, "deque"] = {
    s: deque(maxlen=_PIPE_CAP) for s in PIPELINE_STAGES
}
_pipe_counts: Dict[str, int] = {s: 0 for s in PIPELINE_STAGES}
# measurement epoch: externally-timed spans (pipeline_record) are clamped
# to start no earlier than the last reset(), so a worker's idle
# accumulation that straddles a bench's warmup reset cannot drag the
# attribution makespan back into the warmup window
_pipe_epoch: float = 0.0


def reset() -> None:
    """Drop all records (tests / broker re-enable)."""
    # re-mint the rings through the factories so a race witness armed
    # after import still gets tracked tables (the import-time ones
    # predate arming)
    global _inflight, _done, _pipe_epoch, _idle_since
    with _lock:
        _inflight = tracked_dict("lifecycle._inflight", {})
        _done = tracked_deque("lifecycle._done", maxlen=_DONE_CAP)
        _dispatches.clear()
        _idle_since = _clock()
        for k in _counts:
            _counts[k] = 0
        # aux stages (wait_min_index, raft_fsm, ...) registered via
        # setdefault must not survive a reset either
        for table in (_pipe_open, _pipe_done, _pipe_counts):
            for s in [k for k in table if k not in PIPELINE_STAGES]:
                del table[s]
        for s in PIPELINE_STAGES:
            _pipe_open[s] = 0
            _pipe_done[s].clear()
            _pipe_counts[s] = 0
        _pipe_epoch = _clock()


# -- stamping API (call sites: broker, worker, scheduler, applier) ---------


def on_enqueue(evaluation) -> None:
    """Eval entered a READY heap: open a record (no-op if one is already
    in flight for this id — e.g. requeue-after-ack dedup races)."""
    rec = EvalTrace(
        evaluation.id, getattr(evaluation, "job_id", ""),
        getattr(evaluation, "namespace", ""), getattr(evaluation, "type", ""),
        getattr(evaluation, "triggered_by", ""),
        getattr(evaluation, "priority", 0), _clock(),
    )
    rec.trace_ctx = getattr(evaluation, "trace_ctx", None)
    global _idle_since
    with _lock:
        idle_t0, _idle_since = _idle_since, None
        _inflight.setdefault(evaluation.id, rec)
    if idle_t0 is not None:
        phases.record("no_ready_eval", idle_t0, rec.enqueue_t)


def on_dequeue(eval_id: str, attempt: int) -> None:
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is None:
            return
        if rec.dequeue_t is None:
            rec.dequeue_t = _clock()
            rec.attempt = attempt
        elif rec.refresh_t is not None:
            # the READY heap again, between two attempts of one delivery
            rec.stages.append(("refresh_wait", rec.refresh_t, _clock()))
            rec.refresh_t = None


def on_refresh(eval_id: str) -> None:
    """The delivery's plan committed in part and the eval goes back to
    a worker (broker.refresh): the record stays open, one attempt more."""
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.attempts += 1
            rec.refresh_t = _clock()


def on_worker(eval_id: str, worker_id: int) -> None:
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.worker_id = worker_id


def set_path(eval_id: str, path: str) -> None:
    """Tag which placement path the scheduler took: ``host`` (python
    iterator stack) or ``device`` (TPU batched scan)."""
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.path = path


def on_invoke_start(eval_id: str) -> None:
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.invoke_start_t = _clock()


def on_invoke_end(eval_id: str) -> None:
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.invoke_end_t = _clock()


def on_snapshot(eval_id: str, index: int) -> None:
    """The worker took its state snapshot at raft index ``index``."""
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.snapshot_index = index


def on_plan_submit(eval_id: str) -> None:
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None and rec.submit_t is None:
            rec.submit_t = _clock()


def on_apply(eval_id: str, commit_t: Optional[float] = None,
             commit_index: Optional[int] = None) -> None:
    """Plan applier resolved this eval's plan (committed or rejected).
    ``commit_t``/``commit_index``: when the raft apply that committed it
    returned, and with which index (a re-dispatched eval's last commit
    overwrites its first)."""
    with _lock:
        rec = _inflight.get(eval_id)
        if rec is not None:
            rec.apply_t = _clock()
            if commit_t is not None:
                rec.commit_t = commit_t
                rec.commit_index = commit_index


def eval_trace_ids(eval_id: str,
                   trace_ctx: Optional[Dict[str, str]]) -> Tuple[str, Optional[str]]:
    """(trace_id, parent_span_id) for an eval's spans: the carried
    context when the eval was created inside a trace, else a trace id
    derived from the eval id so an untraced eval's spans still group
    into one tree (roots, not orphans)."""
    ctx = trace_ctx or {}
    trace_id = ctx.get("trace_id") or eval_id.replace("-", "")[:16]
    return trace_id, ctx.get("span_id")


def _emit_trace_spans(rec: EvalTrace) -> None:
    """Emit the record's broker/applier-side phase spans into the
    cross-process span ring (trace/context.py). Worker-side phases
    (wait_min_index, invoke) are emitted by the worker in ITS process —
    in follower mode those stamps never reach this record at all."""
    trace_id, parent = eval_trace_ids(rec.eval_id, rec.trace_ctx)
    skew = _xcontext.wall_from_monotonic(0.0)
    attrs: Dict[str, object] = {
        "eval_id": rec.eval_id, "outcome": rec.outcome,
        "attempt": rec.attempt,
    }

    def emit(name: str, a: Optional[float], b: Optional[float]) -> None:
        if a is None or b is None or b < a:
            return
        _xcontext.record_span(
            name, a + skew, b + skew, trace_id=trace_id,
            parent_id=parent, attrs=attrs,
        )

    emit("eval.queue_wait", rec.enqueue_t,
         rec.dequeue_t if rec.dequeue_t is not None else rec.end_t)
    emit("eval.commit_wait", rec.submit_t, rec.apply_t)
    emit("eval.finalize", rec.apply_t, rec.end_t)


def _close(eval_id: str, outcome: str) -> None:
    global _idle_since
    with _lock:
        rec = _inflight.pop(eval_id, None)
        if rec is None:
            return
        rec.end_t = _clock()
        rec.outcome = outcome
        _done.append(rec)
        _counts[outcome] = _counts.get(outcome, 0) + 1
        if not _inflight:
            _idle_since = rec.end_t
    # outside _lock: span recording takes the context ring's own lock
    _emit_trace_spans(rec)


def on_ack(eval_id: str) -> None:
    _close(eval_id, "ack")


def on_nack(eval_id: str, failed: bool = False) -> None:
    """Delivery failed. ``failed=True`` means the delivery limit was hit
    (eval routed to the failed queue — no fresh record will open)."""
    _close(eval_id, "failed" if failed else "nack")


def on_flush() -> None:
    """Broker flushed (leadership lost): close every in-flight record."""
    global _idle_since
    with _lock:
        now = _clock()
        flushed = list(_inflight.values())
        for rec in flushed:
            rec.end_t = now
            rec.outcome = "flush"
            _done.append(rec)
            _counts["flush"] += 1
        _inflight.clear()
        if _idle_since is None:
            _idle_since = now
    for rec in flushed:
        _emit_trace_spans(rec)


# -- stage and pipeline-ring stamping ---------------------------------------

#: stage name -> the pipeline ring's name for it (the names
#: trace/attribution.py and the overlap / retry-reuse tests read). A
#: stage not listed here is kept on the eval's record and in phases only.
_STAGE_RING: Dict[str, str] = {
    "encode": "encode",
    "device_wait": "dispatch",
    "plan_evaluate": "evaluate",
    "raft_fsm": "commit",
    "wait_index": "wait_min_index",
}

EvalIds = Union[str, Iterable[str], None]


class _Span:
    """What ``stage`` yields: the interval's stamps, ``t1`` set on exit."""

    __slots__ = ("t0", "t1")

    def __init__(self, t0: float) -> None:
        self.t0 = t0
        self.t1: Optional[float] = None


def pipeline_now() -> float:
    """The clock every span here is recorded on (``phases.now``)."""
    return _clock()


# the eval whose stage is open on this thread (innermost single-eval
# ``stage``): lets the device paths name the eval they serve in their
# dispatch record without threading an id through every signature
_tls = threading.local()


def current_eval() -> Optional[str]:
    return getattr(_tls, "eval_id", None)


def _ring_open(ring: str) -> None:
    with _lock:
        _pipe_open[ring] = _pipe_open.get(ring, 0) + 1


def _ring_append(ring: str, wave_ids: Tuple[str, ...], t0: float,
                 t1: float) -> None:
    """One (wave_id, t0, t1) span per id in ``ring``; caller holds _lock.
    Spans are clamped to the last reset() so an accumulation straddling
    a bench's warmup reset cannot stretch the measured window backwards."""
    t0 = max(t0, _pipe_epoch)
    if t1 <= t0:
        return
    _pipe_done.setdefault(ring, deque(maxlen=_PIPE_CAP)).extend(
        (wave_id, t0, t1) for wave_id in wave_ids)
    _pipe_counts[ring] = _pipe_counts.get(ring, 0) + len(wave_ids)


@contextmanager
def stage(name: str, eval_id: EvalIds = None, *, tag: Optional[str] = None):
    """THE span call of the served path: one interval, one call.

    Appends ``(name, t0, t1)`` to the in-flight record of ``eval_id`` (one
    id, or several for an interval they share: the applier's batched raft
    apply), feeds ``utils/phases`` under ``name`` while phases are
    enabled, and feeds the pipeline ring under ``_STAGE_RING[name]``
    keyed by eval id. With no eval to name (``Server.raft_apply`` from
    any thread) ``tag`` keys an aux ring span under ``name`` itself.
    Yields the span, whose ``t0``/``t1`` callers may reuse for the
    cross-process trace."""
    if eval_id is None:
        ids: Tuple[str, ...] = ()
        ring, keys = (name, (tag,)) if tag is not None else (None, ())
    else:
        ids = (eval_id,) if isinstance(eval_id, str) else tuple(eval_id)
        ring, keys = _STAGE_RING.get(name), ids
    outer = current_eval()
    if len(ids) == 1:
        _tls.eval_id = ids[0]
    span = _Span(_clock())
    if ring is not None:
        _ring_open(ring)
    try:
        yield span
    finally:
        span.t1 = t1 = _clock()
        _tls.eval_id = outer
        with _lock:
            for eid in ids:
                rec = _inflight.get(eid)
                if rec is not None:
                    rec.stages.append((name, span.t0, t1))
                    if name == "plan_evaluate" and rec.evaluate_start_t is None:
                        rec.evaluate_start_t = span.t0
            if ring is not None:
                _pipe_open[ring] = max(0, _pipe_open.get(ring, 0) - 1)
                _ring_append(ring, keys, span.t0, t1)
        phases.record(name, span.t0, t1)


@contextmanager
def pipeline_stage(ring: str, wave_id: str):
    """Record one execution of a ring stage for one wave, on the ring
    alone (no eval record, no phase): for callers outside the served
    path. Depth (open count) is visible to gauges while the stage runs;
    the completed span lands in the per-stage ring on exit."""
    t0 = _clock()
    _ring_open(ring)
    try:
        yield
    finally:
        t1 = _clock()
        with _lock:
            _pipe_open[ring] = max(0, _pipe_open.get(ring, 0) - 1)
            _ring_append(ring, (wave_id,), t0, t1)


def pipeline_record(ring: str, wave_id: str, t0: float, t1: float) -> None:
    """Record an externally-timed ring span (times from pipeline_now());
    used by scheduler workers flushing coalesced ``idle`` dequeue-wait
    periods."""
    with _lock:
        _ring_append(ring, (wave_id,), t0, t1)


# -- per-dispatch records ---------------------------------------------------

#: every key a dispatch record has; a path that lacks a stamp (the forced
#: kernel and the single scan have no gather) leaves it None
DISPATCH_FIELDS = (
    "wave", "source", "batcher", "eval_ids", "b", "b_pad", "p_pad", "n_pad",
    "steps", "n_steps", "padded_steps", "closed_by", "d2h_bytes",
    "h2d_arrays", "d2h_arrays",
    "t_first_enqueue", "t_start", "t_stack", "t_called", "t_ready",
    "t_host", "t_handed",
)


def next_wave() -> int:
    """The next dispatch sequence number (process-wide, never reused)."""
    return next(_wave_seq)


def on_dispatch(**fields) -> None:
    """Keep one device dispatch's record (``DISPATCH_FIELDS``) in the
    bounded ring and note its ``wave`` on the in-flight record of every
    eval it served."""
    rec = dict.fromkeys(DISPATCH_FIELDS)
    rec.update(fields)
    with _lock:
        _dispatches.append(rec)
        for eid in rec["eval_ids"] or ():
            ev = _inflight.get(eid)
            if ev is not None:
                ev.waves.append(rec["wave"])


def dispatch_records() -> List[Dict[str, object]]:
    """The last ``_DISPATCH_CAP`` dispatch records, oldest first (copies:
    a record is never written again once it is in the ring, so only the
    list is taken under the lock)."""
    with _lock:
        recs = list(_dispatches)
    return [dict(r) for r in recs]


def pipeline_spans(stage: Optional[str] = None) -> List[Tuple[str, str, float, float]]:
    """Completed (stage, wave_id, t0, t1) spans, oldest first. The overlap
    and retry-reuse tests read these raw."""
    with _lock:
        stages = [stage] if stage is not None else list(_pipe_done)
        out = []
        for s in stages:
            out.extend((s, w, a, b) for (w, a, b) in _pipe_done.get(s, ()))
    out.sort(key=lambda r: r[2])
    return out


def pipeline_summary() -> Dict[str, Dict[str, object]]:
    """Per-stage depth / throughput / latency percentiles."""
    with _lock:
        snap = {
            s: (list(_pipe_done.get(s, ())), _pipe_open.get(s, 0),
                _pipe_counts.get(s, 0))
            for s in set(PIPELINE_STAGES) | set(_pipe_done)
        }
    out: Dict[str, Dict[str, object]] = {}
    for s, (spans, depth, count) in snap.items():
        lat = sorted((b - a) * 1000.0 for (_, a, b) in spans)
        out[s] = {
            "depth": depth,
            "count": count,
            "latency_ms_p50": round(_percentile(lat, 0.50), 3),
            "latency_ms_p95": round(_percentile(lat, 0.95), 3),
        }
    return out


# -- read side -------------------------------------------------------------


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def summary() -> Dict[str, object]:
    now = _clock()
    with _lock:
        durations = sorted(r.total_ms() for r in _done)
        inflight = list(_inflight.values())
        counts = dict(_counts)
    slowest = max((r.total_ms(now) for r in inflight), default=0.0)
    return {
        "inflight": len(inflight),
        "completed": len(durations),
        "outcomes": counts,
        "eval_ms_p50": round(_percentile(durations, 0.50), 3),
        "eval_ms_p95": round(_percentile(durations, 0.95), 3),
        "eval_ms_p99": round(_percentile(durations, 0.99), 3),
        "slowest_inflight_ms": round(slowest, 3),
    }


def raw_records() -> List[Dict[str, object]]:
    """Raw-stamp dicts for every completed + in-flight record, oldest
    completion first (the attribution engine's input)."""
    with _lock:
        out = [r.raw() for r in _done]
        out.extend(r.raw() for r in _inflight.values())
    return out


def quick_stats() -> Dict[str, object]:
    """Cheap per-tick snapshot for the flight recorder: counts and open
    stage depths only — no percentile sorts (summary() and
    pipeline_summary() sort thousands of spans, too hot for a 250ms
    cadence)."""
    with _lock:
        return {
            "inflight": len(_inflight),
            "completed": len(_done),
            "outcomes": dict(_counts),
            "pipeline_depth": dict(_pipe_open),
            "pipeline_count": dict(_pipe_counts),
        }


def slowest_inflight(n: int = 5) -> List[Dict[str, object]]:
    """The n oldest in-flight records (watchdog dump material)."""
    now = _clock()
    with _lock:
        recs = sorted(_inflight.values(), key=lambda r: r.enqueue_t)[:n]
        return [r.to_dict(now) for r in recs]


def snapshot(recent: int = 64) -> Dict[str, object]:
    """The /v1/trace payload: summary + in-flight + recent completions."""
    now = _clock()
    with _lock:
        inflight = [r.to_dict(now) for r in
                    sorted(_inflight.values(), key=lambda r: r.enqueue_t)]
        done = [r.to_dict(now) for r in list(_done)[-recent:]]
    out = summary()
    out["inflight_evals"] = inflight
    out["recent"] = done
    out["pipeline"] = pipeline_summary()
    return out


def publish_gauges() -> None:
    """Push trace tail-latency gauges into the metrics sink (the server
    calls this from its periodic stats sweep, so /v1/metrics carries
    them without a /v1/trace round trip)."""
    s = summary()
    metrics.set_gauge("nomad.trace.eval_ms.p50", s["eval_ms_p50"])
    metrics.set_gauge("nomad.trace.eval_ms.p95", s["eval_ms_p95"])
    metrics.set_gauge("nomad.trace.eval_ms.p99", s["eval_ms_p99"])
    metrics.set_gauge("nomad.trace.slowest_inflight_ms",
                      s["slowest_inflight_ms"])
    metrics.set_gauge("nomad.trace.inflight", s["inflight"])
    flat: Dict[str, object] = {}
    for stage, row in pipeline_summary().items():
        flat[f"{stage}.depth"] = row["depth"]
        flat[f"{stage}.count"] = row["count"]
        flat[f"{stage}.latency_ms_p95"] = row["latency_ms_p95"]
    metric_names.publish_family("nomad.trace.pipeline", flat)
