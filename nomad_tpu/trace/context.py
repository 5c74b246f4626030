"""Cross-process trace context: TraceContext propagation + span ring.

Every observability layer before this one (lifecycle records, pipeline
spans, the flight recorder) sees exactly ONE process. The production
shape is a 3-OS-process wire-raft cluster, so a request that crosses a
process boundary — eval submit → leader forward → broker dequeue →
follower worker → Plan.Submit → ack — simply vanished from the trace.
This module is the missing carrier:

  TraceContext   (trace_id, span_id, parent_id) — 16-hex ids. The
                 current context rides a ``contextvars.ContextVar`` so
                 it follows the logical request through nested calls
                 without threading an argument through every layer.
  wire format    ``inject()`` returns a plain ``{"trace_id",
                 "span_id"}`` dict; the RPC transport carries it in the
                 request envelope's ``trace`` field (rpc/codec.py) and
                 eval payloads carry it in ``Evaluation.trace_ctx`` so
                 the SAME trace_id survives the raft log and a broker
                 dequeue by a different process.
  span ring      completed spans land in a bounded deque with a
                 monotonically increasing ``seq`` — ``export(after)``
                 is a cursor drain (the ``Trace.Export`` RPC), so a
                 collector polling N replicas never double-counts and
                 eviction only loses the tail it was too slow to read.
  spill          optional crash-proof JSONL spill (append + flush per
                 span, same discipline as trace/flight.py): a
                 SIGKILLed replica still leaves its spans on disk.

Span times are WALL clock (``time.time()``) — cross-process stitching
needs a common axis, and trace/stitch.py estimates per-process clock
offset from client/server span pairs rather than trusting it. The
in-process lifecycle/pipeline layers stay on ``utils.phases.now``
(``time.perf_counter``); :func:`wall_from_monotonic` converts when they
emit spans here.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
import uuid
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from ..utils import phases as _phases
from ..utils.lock_witness import module_witness_lock

#: ring capacity: at ~300B/span this bounds the table at ~20MB while
#: retaining the full span set of a chaos run when the collector drains
#: on a 1s cadence
RING_CAP = 65536


class TraceContext:
    """One node of the span tree: ids only, no timing (timing lives on
    the recorded span dicts)."""

    __slots__ = ("trace_id", "span_id", "parent_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str] = None) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id

    def to_wire(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"TraceContext(trace={self.trace_id} span={self.span_id} "
                f"parent={self.parent_id})")


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


_current: "contextvars.ContextVar[Optional[TraceContext]]" = \
    contextvars.ContextVar("nomad_trace_ctx", default=None)

_lock = module_witness_lock("trace.context._lock")
_spans: "deque[Dict[str, object]]" = deque(maxlen=RING_CAP)
_seq = 0
_dropped = 0
_process: Optional[str] = None
_spill_fh = None


# -- process identity / spill ----------------------------------------------


def set_process(name: str) -> None:
    """Name this process in every span it records (replica node id in
    multi-process runs; defaults to ``pid:<pid>``)."""
    global _process
    with _lock:
        _process = name


def process_name() -> str:
    with _lock:
        if _process is None:
            return f"pid:{os.getpid()}"
        return _process


def configure_spill(path: Optional[str]) -> None:
    """Open (or close, with None) the crash-proof JSONL spill."""
    global _spill_fh
    with _lock:
        if _spill_fh is not None:
            try:
                _spill_fh.close()
            except OSError:
                pass
            _spill_fh = None
        if path:
            try:
                _spill_fh = open(path, "a", encoding="utf-8")
            except OSError:
                _spill_fh = None


def reset() -> None:
    """Drop all spans and state (tests)."""
    global _seq, _dropped, _process
    configure_spill(None)
    with _lock:
        _spans.clear()
        _seq = 0
        _dropped = 0
        _process = None


# -- context propagation ----------------------------------------------------


def current() -> Optional[TraceContext]:
    return _current.get()


def activate(ctx: Optional[Dict[str, str]]):
    """Enter a context carried over the wire (an RPC envelope's
    ``trace`` field, an ``Evaluation.trace_ctx``): subsequent spans in
    this thread parent to the carried span. Returns a token for
    :func:`deactivate`; None input is a no-op returning None."""
    if not ctx or not ctx.get("trace_id"):
        return None
    return _current.set(
        TraceContext(ctx["trace_id"], ctx.get("span_id") or _new_id())
    )


def deactivate(token) -> None:
    if token is not None:
        _current.reset(token)


def inject() -> Optional[Dict[str, str]]:
    """The current context as a wire dict, or None outside any trace."""
    ctx = _current.get()
    return ctx.to_wire() if ctx is not None else None


# -- span recording ---------------------------------------------------------


def wall_from_monotonic(t: float) -> float:
    """Convert a stamp of the in-process span clock (``phases.now``, the
    monotonic clock lifecycle records and stages are on) to the
    wall-clock axis spans are recorded on."""
    return t + (time.time() - _phases.now())


def record_span(name: str, start: float, end: float, *,
                kind: str = "internal",
                trace_id: Optional[str] = None,
                span_id: Optional[str] = None,
                parent_id: Optional[str] = None,
                attrs: Optional[Dict[str, object]] = None) -> Dict[str, object]:
    """Record an externally-timed span (wall-clock seconds). Defaults
    parent/trace to the ambient context when ids are not given."""
    global _seq, _dropped
    ctx = _current.get()
    if trace_id is None:
        trace_id = ctx.trace_id if ctx is not None else _new_id()
    if parent_id is None and span_id is None and ctx is not None:
        parent_id = ctx.span_id
    span: Dict[str, object] = {
        "trace_id": trace_id,
        "span_id": span_id or _new_id(),
        "parent_id": parent_id,
        "name": name,
        "kind": kind,
        "process": process_name(),
        "start": start,
        "end": end,
    }
    if attrs:
        span["attrs"] = attrs
    with _lock:
        _seq += 1
        span["seq"] = _seq
        if len(_spans) == _spans.maxlen:
            _dropped += 1
        _spans.append(span)
        fh = _spill_fh
    if fh is not None:
        try:
            fh.write(json.dumps(span, sort_keys=True, default=str) + "\n")
            fh.flush()
        except (OSError, ValueError):
            pass
    return span


@contextmanager
def span(name: str, kind: str = "internal",
         ctx: Optional[TraceContext] = None,
         attrs: Optional[Dict[str, object]] = None):
    """Open a child span of ``ctx`` (default: the ambient context; a new
    root trace when there is none), make it ambient for the body, record
    it on exit. Yields the mutable attrs dict so the body can stamp
    byte counts / error tags."""
    parent = ctx if ctx is not None else _current.get()
    trace_id = parent.trace_id if parent is not None else _new_id()
    me = TraceContext(trace_id, _new_id(),
                      parent.span_id if parent is not None else None)
    token = _current.set(me)
    span_attrs: Dict[str, object] = dict(attrs) if attrs else {}
    t0 = time.time()
    try:
        yield span_attrs
    except BaseException as e:
        span_attrs.setdefault("error", type(e).__name__)
        raise
    finally:
        _current.reset(token)
        record_span(
            name, t0, time.time(), kind=kind, trace_id=me.trace_id,
            span_id=me.span_id, parent_id=me.parent_id,
            attrs=span_attrs or None,
        )


# -- read side --------------------------------------------------------------


def export(after_seq: int = 0, limit: int = RING_CAP) -> Dict[str, object]:
    """Cursor drain for the ``Trace.Export`` RPC: spans with
    ``seq > after_seq``, plus the next cursor. Bounded and idempotent —
    a collector that crashes and re-polls with its last cursor never
    double-counts."""
    with _lock:
        out = [s for s in _spans if s["seq"] > after_seq]
        next_seq = _seq
        dropped = _dropped
    if limit >= 0:
        out = out[:limit]
    if out:
        next_seq = out[-1]["seq"]
    return {
        "process": process_name(),
        "next_seq": next_seq,
        "dropped": dropped,
        "spans": out,
    }


def snapshot(recent: Optional[int] = None) -> List[Dict[str, object]]:
    with _lock:
        out = list(_spans)
    if recent is not None and recent >= 0:
        out = out[-recent:] if recent else []
    return out


def stats() -> Dict[str, object]:
    """Cheap counters for flight-recorder probes."""
    with _lock:
        return {"spans": len(_spans), "seq": _seq, "dropped": _dropped}
