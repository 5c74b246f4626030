"""Wall-clock phase attribution for the end-to-end scheduling pipeline.

The reference measures pipeline stages with per-call timers
(``nomad.plan.evaluate``, ``nomad.plan.apply`` — plan_apply.go:369/:400,
``nomad.worker.invoke_scheduler`` — worker.go:245); summing those across
worker THREADS overstates wall time badly under the GIL (concurrent
threads' intervals overlap). This module records raw [start, end) spans
per phase and reports the UNION length inside a measurement window: "how
much wall time had >= 1 thread inside phase X". That is the number that
answers "where does the end-to-end second go": the bench publishes
measured phase shares.

Zero overhead unless enabled; the bench enables it around its timed
window. Phases tracked across the system path:

  encode         per-eval problem encoding (engine.encode_eval, GIL)
  device         the engine's own forced-kernel round trip (H2D,
                 kernel, D2H in one bracket)
  pad_stack      batch padding/stacking before dispatch (host)
  h2d_launch     batched dispatch: the scan call (H2D of the stacked
                 planes + launch) until it returns
  kernel_wait    batched dispatch: block_until_ready on the outputs
  d2h            batched dispatch: the five outputs become numpy arrays
  apply          decode results -> plan blocks (engine._apply_*, GIL)
  plan_evaluate  applier re-check against snapshot (plan_apply, GIL)
  raft_fsm       raft apply -> FSM -> state store commit (GIL)
  snapshot       worker's shared state-snapshot clone (worker._process)
  reconcile      desired-vs-existing alloc diff (generic_sched)
  rank           host placement iterator stack pull: feasibility +
                 scoring per candidate (rank.BinPackIterator.next —
                 covers the whole upstream iterator chain)
  proposed       per-candidate proposed-alloc rebuild (context.py)
  dense_mat      dense-block slot materialization (state_store)
  place          host placement loop: select + alloc construction glue
                 around the rank pulls (generic/system scheduler)
  engine_gate    device-path gate checks + encode attempts + fallback
                 decision (tpu/integration.py; engine phases nest inside)
  device_wait    worker parked in the device dispatch block — the
                 batcher's gather window + queue + device round trip
                 until its wave's results land. r05's ~500s
                 busy-vs-window gap lived here, untracked;
                 device/pad_stack nest inside its union.
  plan_submit    worker parked on the plan queue future (worker)
  wait_index     worker parked on raft replication before snapshotting
  raft_fsm       raft log append -> FSM -> state store commit (every
                 Server.raft_apply, plan commits included)

WAITS (recorded through ``record``; listed in ``wall_shares`` under their
names, counted neither into ``busy`` nor ``any_host``): they name an
idle device for what it is.

  gather         a request is queued and the batcher's dispatcher holds
                 the gather open (first enqueue -> dispatch start)
  no_ready_eval  trace/lifecycle's in-flight table is empty: no eval
                 between its READY enqueue and its ack (a nacked eval
                 waiting out the broker's delay is not ready)

META-PHASES (excluded from ``any_host``/``busy``, which aggregate only
fine phases): ``worker_busy`` brackets the whole of a worker's eval
processing and exists so ``coverage()`` can answer "what fraction of
measured worker busy time do the fine phases explain" — the ISSUE 4
self-check against round 5's 17%-busy blindness, where the host
iterator stack burned wall time no phase accounted for.

Hot-loop spans (rank/proposed/dense_mat run per candidate, thousands of
times per eval) COALESCE: a span starting within _COALESCE_GAP of the
previous same-phase span's end merges into it, bounding memory at
O(distinct bursts) instead of O(calls) with at most _COALESCE_GAP of
union-length overestimate per merge.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Tuple
from .lock_witness import module_witness_lock

_lock = module_witness_lock("phases._lock")
_intervals: Dict[str, List[Tuple[float, float]]] = {}
_enabled = False

# phases that measure a measurement (a window, not work); never summed
# into the busy/any_host aggregates
_META = frozenset({"worker_busy"})
# intervals in which nothing works: named in wall_shares, never busy
_WAITS = frozenset({"gather", "no_ready_eval"})
# brackets on the dispatcher thread that stand for device-side time
_DEVICE = frozenset({"device", "h2d_launch", "kernel_wait", "d2h"})

# merge same-phase spans closer than this (seconds); ~10k coalesced
# hot-loop calls collapse into a handful of burst intervals
_COALESCE_GAP = 2e-4


def enable() -> None:
    """Clear history and start recording."""
    global _enabled
    with _lock:
        _intervals.clear()
        _enabled = True


def disable() -> None:
    global _enabled
    with _lock:
        _enabled = False


@contextmanager
def track(name: str):
    """Record one [start, end) span under ``name`` (no-op when disabled)."""
    if not _enabled:
        yield
        return
    t0 = now()
    try:
        yield
    finally:
        record(name, t0, now())


def record(name: str, t0: float, t1: float) -> None:
    """Record an interval timed elsewhere on ``now()``'s clock under
    ``name`` (no-op when disabled)."""
    if not _enabled:
        return
    with _lock:
        if _enabled:
            spans = _intervals.setdefault(name, [])
            if spans and t0 - spans[-1][1] < _COALESCE_GAP:
                last = spans[-1]
                spans[-1] = (min(last[0], t0), max(last[1], t1))
            else:
                spans.append((t0, t1))


#: THE clock of every span the served path keeps: phases, lifecycle
#: records and stages, the batcher's dispatch records. The benchmark's
#: mark ties it to the profiler's clock.
now = time.perf_counter


def _union_len(spans: List[Tuple[float, float]], lo: float, hi: float) -> float:
    clipped = sorted(
        (max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi
    )
    total = 0.0
    cur_a = cur_b = None
    for a, b in clipped:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def wall_shares(t0: float, t1: float) -> Dict[str, float]:
    """Seconds of the [t0, t1] window during which >= 1 thread was inside
    each phase (interval union — NOT a thread-sum), plus:

      any_host   union over every host-side fine phase (all but the
                 device-side brackets, waits and meta-phases)
      busy       union over every fine phase (waits and meta-phases
                 excluded)
      window     t1 - t0
      untracked  window - busy: wall seconds during which NO fine phase
                 had a thread inside it. r05 shipped a headline where
                 this residual was 498s of a 600s window and invisible —
                 the gap is now an explicit row so a busy-vs-window
                 mismatch can never again go unreported.
    """
    with _lock:
        snap = {k: list(v) for k, v in _intervals.items()}
    out = {k: round(_union_len(v, t0, t1), 3) for k, v in snap.items()}
    idle = _META | _WAITS
    host = [s for k, v in snap.items()
            if k not in _DEVICE and k not in idle for s in v]
    every = [s for k, v in snap.items() if k not in idle for s in v]
    out["any_host"] = round(_union_len(host, t0, t1), 3)
    out["busy"] = round(_union_len(every, t0, t1), 3)
    out["window"] = round(t1 - t0, 3)
    out["untracked"] = round(max(0.0, out["window"] - out["busy"]), 3)
    return out


def _merged(spans: List[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """Sorted, disjoint, window-clipped intervals."""
    clipped = sorted(
        (max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi
    )
    out: List[Tuple[float, float]] = []
    for a, b in clipped:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _intersect_len(xs: List[Tuple[float, float]],
                   ys: List[Tuple[float, float]]) -> float:
    """Total overlap length of two disjoint-sorted interval lists."""
    total = 0.0
    i = j = 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def coverage(t0: float, t1: float) -> Dict[str, float]:
    """Phase-attribution coverage self-check (ISSUE 4): what fraction of
    measured worker busy wall time (the ``worker_busy`` meta-phase
    union) do the fine phases explain?

      worker_busy   union seconds any worker spent processing an eval
      tracked_busy  seconds of that during which >= 1 fine phase was
                    also active (anywhere — the device phase runs on the
                    dispatcher thread while the worker blocks, and still
                    explains the worker's wait)
      coverage      tracked_busy / worker_busy  (1.0 when never busy)

    Round 5's blindness was coverage ~0.17: the host iterator stack
    burned wall time no phase claimed. The stress suite asserts >= 0.9.
    """
    with _lock:
        snap = {k: list(v) for k, v in _intervals.items()}
    busy = _merged(snap.get("worker_busy", []), t0, t1)
    fine = [s for k, v in snap.items()
            if k not in _META and k not in _WAITS for s in v]
    tracked = _intersect_len(_merged(fine, t0, t1), busy)
    busy_len = sum(b - a for a, b in busy)
    return {
        "worker_busy": round(busy_len, 3),
        "tracked_busy": round(tracked, 3),
        "coverage": round(tracked / busy_len, 4) if busy_len else 1.0,
    }
