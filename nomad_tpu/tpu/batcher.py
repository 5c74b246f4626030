"""DeviceBatcher: gathers concurrent evals into ONE device dispatch.

The production realization of SURVEY §2.6 row 1 — the TPU-native analog of
the reference's N scheduler workers per server (nomad/server.go:1307
setupWorkers, worker.go:244). Host workers still dequeue and run the
scheduler logic concurrently; when each reaches its placement step it
submits an ``EncodedEval`` here and blocks. A dispatcher thread gathers the
requests that arrive within a small window, pads them to shared bucketed
shapes along a leading eval axis and runs the eval-batched scan — one
device dispatch for the whole batch, amortizing host→device transfer and
dispatch latency. Unsharded, a dispatch crosses the host-device boundary
once each way: the evals are written into one flat buffer per dtype
(tpu/wire.py), the program (engine._build_wire_scan) unpacks them and
hands back one array. With an ("evals", "nodes") mesh configured the 48
stacked arrays and the evals' step counts go up one by one, sharded
(engine._build_batched_scan).

Per-eval semantics are untouched: the batched scan vmaps the exact
single-eval parity step, so each eval's plan is identical to what the
single dispatch produces; cross-eval conflicts resolve in the plan applier
exactly as with the reference's optimistically-concurrent workers.
"""
from __future__ import annotations

import collections
import logging
import threading
import weakref
from typing import Dict, List, Optional, Tuple

import numpy as np

import itertools

from ..chaos.injector import fire as chaos_fire
from . import wire
from .engine import (
    EncodedEval,
    _build_batched_scan,
    _build_wire_scan,
    _round_up,
)
from .intscore import E27_ONE as _E27_NEUTRAL
from ..trace import lifecycle as _lifecycle
from ..utils import phases as _phases
from ..utils.lock_witness import witness_condition, witness_lock
from ..utils.race_witness import tracked_dict

logger = logging.getLogger("nomad_tpu.tpu.batcher")

# every constructed batcher, weakly held, so the engine's atexit
# shutdown path (TpuPlacementEngine.shutdown) can stop dispatcher and
# warm-compile threads deterministically instead of letting interpreter
# teardown race them into the runtime (the multichip dryrun's rc 139)
_LIVE: "weakref.WeakSet" = weakref.WeakSet()
# tells one batcher's dispatch records from another's in lifecycle's ring
_SERIAL = itertools.count(1)
# how many layouts' host buffers the dispatcher keeps for reuse (the least
# recently used goes first): a served cluster cycles through a handful —
# step buckets x batch buckets — and a 64-wide bucket of full-wave evals
# is ~130 MB of them
_WIRE_BUFS_KEPT = 8


def shutdown_all() -> None:
    """Stop every live batcher and join its warm-compile threads."""
    for b in list(_LIVE):
        try:
            b.stop()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            logger.debug("batcher stop failed at shutdown", exc_info=True)


def _pow2ceil(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def pad_encoded(enc: EncodedEval, n_pad: int, g_pad: int, s_pad: int,
                v_pad: int, p_pad: int, dtype,
                d_pad: int = 0, k_pad: Optional[int] = None,
                aff_pad: Optional[int] = None,
                evd_pad: Optional[int] = None,
                fac_pad: Optional[int] = None,
                dpd_pad: Optional[int] = None,
                dpv_pad: Optional[int] = None,
                fnd_pad: Optional[int] = None,
                prec_pad: Optional[int] = None,
                pregp_pad: Optional[int] = None) -> tuple:
    """Pad one eval's arrays to the batch's shared bucketed dims:
    ``(static, carry, xs, p_real)``, the last the eval's own step count.

    Padding is semantically inert by construction:
      - nodes beyond n_real are infeasible and outside the ring window
      - task-group slots >= g (a co-batched eval has more groups) are born
        with failed=True in the carry; no step of this eval points at one
      - placement steps beyond p are masked by index on the device
        (``step >= p_real``), which runs the wave's longest eval and no
        further: the scan body skips them (skip_step) and mutates nothing
      - spread rows beyond s are inactive; the invalid vocab bucket is
        remapped from v-1 to v_pad-1
      - capacity dims beyond the eval's own (device dims of co-batched
        device jobs) pad zero ask against zero totals: 0 <= 0 fits
    """
    (totals, reserved, asks, feat_packed, aff_score, desired_counts,
     dh_job, dh_tg, limits, spread_vids, spread_desired, spread_weights,
     spread_has_targets, spread_active, sum_spread_weights, n_real,
     e_ask, dp_vids, dp_limit, dp_applies,
     pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf) = enc.static
    (used0, tg_counts0, job_counts0, spread_counts0, spread_entry0,
     offset0, failed0, e_base0, dp_counts0,
     pre_alive0, pre_remaining0, pre_counts0) = enc.carry
    (tg_idx, penalty_idx, evict_node, evict_res, evict_tg,
     limit_p, sum_sw_p, ev_factor, rev_factor, forced_node) = enc.xs

    n0, g0, s0, v0, p0 = enc.n_pad, enc.g, enc.s, enc.v, enc.p
    d0 = totals.shape[1]
    if d_pad <= 0:
        d_pad = d0
    if k_pad is None:
        k_pad = penalty_idx.shape[1]
    if aff_pad is None:
        aff_pad = aff_score.shape[0]
    if evd_pad is None:
        evd_pad = evict_res.shape[1]
    if fac_pad is None:
        fac_pad = ev_factor.shape[1]
    if dpd_pad is None:
        dpd_pad = dp_vids.shape[0]
    if dpv_pad is None:
        dpv_pad = dp_counts0.shape[1]
    if fnd_pad is None:
        fnd_pad = forced_node.shape[1]
    if prec_pad is None:
        prec_pad = pre_res.shape[1]
    if pregp_pad is None:
        pregp_pad = pre_counts0.shape[0]
    dn, dg, ds, dv, dp = (n_pad - n0, g_pad - g0, s_pad - s0,
                          v_pad - v0, p_pad - p0)
    dd = d_pad - d0
    assert min(dn, dg, ds, dv, dp, dd) >= 0
    assert k_pad >= penalty_idx.shape[1] and aff_pad >= aff_score.shape[0]

    def pad(arr, widths, fill=0):
        if all(w == (0, 0) for w in widths):
            return np.asarray(arr, dtype=arr.dtype)
        return np.pad(arr, widths, constant_values=fill)

    f = lambda a: np.asarray(a, dtype)  # noqa: E731 — common float cast

    # spread_vids: remap this eval's invalid bucket (v0-1) onto the shared
    # one (v_pad-1) BEFORE padding, then pad new cells as invalid too
    vids = np.where(spread_vids >= v0 - 1, v_pad - 1, spread_vids)
    vids = pad(vids, ((0, dg), (0, ds), (0, dn)), v_pad - 1)

    static = (
        pad(f(totals), ((0, dn), (0, dd))),
        # int-mode evals fold reserved into totals and pass it ZERO-height
        # (rows only — the D axis must still pad so the batch stacks)
        pad(f(reserved), ((0, dn if reserved.shape[0] else 0), (0, dd))),
        pad(f(asks), ((0, dg), (0, dd))),
        # packed feature plane (intscore.pack_feat_planes): padded TG rows
        # and padded nodes get 0 = infeasible with no affinity lane
        pad(feat_packed, ((0, dg), (0, dn)), 0),
        # aff_score may have a ZERO G axis (shape-specialized absent
        # affinities): the batch target is 0 when every co-batched eval
        # lacks affinities (keeping the specialization), else g_pad —
        # padded zero rows are inert either way
        pad(f(aff_score), ((0, aff_pad - aff_score.shape[0]), (0, dn))),
        pad(desired_counts, ((0, dg),), 1),
        pad(dh_job, ((0, dg),), False),
        pad(dh_tg, ((0, dg),), False),
        pad(limits, ((0, dg),), 0),
        vids.astype(np.int32),
        pad(f(spread_desired), ((0, dg), (0, ds), (0, dv)), -1.0),
        pad(f(spread_weights), ((0, dg), (0, ds))),
        pad(spread_has_targets, ((0, dg), (0, ds)), False),
        pad(spread_active, ((0, dg), (0, ds)), False),
        pad(f(sum_spread_weights), ((0, dg),)),
        np.int32(n_real),
        # Q27 exponential ask factors (int mode; zero-sized in float
        # batches). Padded cells get the neutral factor — padded nodes
        # are infeasible and padded TG slots pre-failed anyway.
        pad(e_ask, ((0, (g_pad - e_ask.shape[0]) if e_ask.shape[0] else 0),
                    (0, (n_pad - e_ask.shape[1]) if e_ask.shape[0] else 0),
                    (0, 0)), _E27_NEUTRAL),
        # distinct_property: remap this eval's MISSING bucket onto the
        # batch's (dpv_pad-1) before padding; padded constraint rows
        # apply to no TG
        pad(
            np.where(dp_vids >= dp_counts0.shape[1] - 1, dpv_pad - 1, dp_vids)
            if dp_vids.shape[0] else dp_vids.reshape(0, n0),
            ((0, dpd_pad - dp_vids.shape[0]), (0, dn)), dpv_pad - 1,
        ),
        pad(dp_limit, ((0, dpd_pad - dp_limit.shape[0]),), 1),
        pad(dp_applies, ((0, dg), (0, dpd_pad - dp_applies.shape[1])), False),
        # preemption candidate axis (tpu/preempt.py): ZERO-width when no
        # co-batched eval preempts (the step's eviction block compiles
        # away); mixed batches widen with inert slots — eligibility stays
        # False, so the greedy pass never takes them and pre_met stays
        # False (cap_ok falls back to fits) for widened evals
        pad(pre_res, ((0, dn), (0, prec_pad - pre_res.shape[1]), (0, 0)), 0),
        pad(pre_prio, ((0, dn), (0, prec_pad - pre_prio.shape[1])), 0),
        pad(pre_elig, ((0, dn), (0, prec_pad - pre_elig.shape[1])), False),
        pad(pre_mp, ((0, dn), (0, prec_pad - pre_mp.shape[1])), 0),
        pad(pre_gid, ((0, dn), (0, prec_pad - pre_gid.shape[1])), 0),
        pad(pre_evf, ((0, dn), (0, prec_pad - pre_evf.shape[1]), (0, 0)),
            _E27_NEUTRAL),
    )
    carry = (
        pad(f(used0), ((0, dn), (0, dd))),
        pad(tg_counts0, ((0, dg), (0, dn)), 0),
        pad(job_counts0, ((0, dn),), 0),
        pad(f(spread_counts0), ((0, dg), (0, ds), (0, dv))),
        pad(spread_entry0, ((0, dg), (0, ds), (0, dv)), False),
        np.int32(offset0),
        # TG slots only a co-batched eval forces are pre-failed
        pad(failed0, ((0, dg),), True),
        pad(e_base0, ((0, dn if e_base0.shape[0] else 0), (0, 0)),
            _E27_NEUTRAL),
        pad(dp_counts0, ((0, dpd_pad - dp_counts0.shape[0]),
                         (0, dpv_pad - dp_counts0.shape[1])), 0),
        pad(pre_alive0, ((0, dn), (0, prec_pad - pre_alive0.shape[1])), False),
        # pre_remaining rides a zero-HEIGHT row axis when this eval has no
        # candidate tables; a preempt batch needs full rows (zeros inert:
        # widened evals' eligibility is all-False)
        (pad(pre_remaining0, ((0, dn), (0, 0)), 0)
         if pre_remaining0.shape[0]
         else np.zeros((n_pad if prec_pad else 0, 3), np.int64)),
        pad(pre_counts0, ((0, pregp_pad - pre_counts0.shape[0]),), 0),
    )
    xs = (
        pad(tg_idx, ((0, dp),), 0),  # padded steps are masked by index
        # K axis may be zero (no reschedule history) — pad to the batch's
        # K with -1 sentinels, which match nothing
        pad(penalty_idx, ((0, dp), (0, k_pad - penalty_idx.shape[1])), -1),
        pad(evict_node, ((0, dp),), -1),
        # eviction axes may be ZERO-width (no destructive updates in the
        # whole batch — the step's evict path compiles away); a mixed
        # batch widens with inert fills (evict_node stays -1)
        pad(f(evict_res), ((0, dp), (0, evd_pad - evict_res.shape[1]))),
        pad(evict_tg, ((0, dp),), -1),
        pad(limit_p, ((0, dp),), 0),
        pad(f(sum_sw_p), ((0, dp),), 1.0),
        pad(ev_factor, ((0, dp), (0, fac_pad - ev_factor.shape[1])), _E27_NEUTRAL),
        pad(rev_factor, ((0, dp), (0, fac_pad - rev_factor.shape[1])), _E27_NEUTRAL),
        pad(forced_node, ((0, dp), (0, fnd_pad - forced_node.shape[1])), -1),
    )
    return static, carry, xs, np.int32(p0)


class _Request:
    __slots__ = ("enc", "eval_id", "event", "result", "error", "t_enqueue")

    def __init__(self, enc: EncodedEval) -> None:
        self.enc = enc
        # made on the worker's thread, inside its device_wait stage
        self.eval_id = _lifecycle.current_eval()
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = _phases.now()


class DeviceBatcher:
    """Gather-window batcher in front of the eval-batched placement scan.

    ``run(enc)`` blocks the calling worker until its eval's slice of the
    batched result is ready. The dispatcher thread starts lazily on first
    use and stops with ``stop()``.

    What ends a gather is the count of announced evals still en route,
    and nothing else. The server's workers announce (``expect()``) every
    service and batch eval once its raft-index wait is over, BEFORE the
    snapshot's host-work permit (server/worker.py:Worker._process), so a
    cohort dequeued together is counted whole before its first member
    arrives; engine.compute_placements takes the token over and either
    arrives with it (``run(expected=True)``) or withdraws it
    (``cancel_expected()``: the host stack, a raise). A
    planner that announces nothing (harness and test planners, a
    scheduler's second attempt) has compute_placements announce before
    encode. A worker that has the device's answer and finds its next
    eval waiting in the broker (a flood larger than the worker pool)
    announces that one at once (Worker.announce_next), so the count does
    not fall to 0 between two evals of one worker and the flood's waves
    stay whole. System and core evals, the redispatcher and anything
    under a host algorithm never announce.
    The dispatcher takes what is queued after every arrival or
    withdrawal and dispatches the moment nothing announced is
    outstanding: a lone eval does not wait at all. A gather waits only
    for evals announced BEFORE it took its first request; one announced
    later rides the next wave. Otherwise, once evals take longer from
    announcement to arrival than they are apart (a host starved of the
    GIL), every arrival finds a newer announcement and one gather holds a
    whole window's evals on one snapshot. ``window_ms`` caps a hold (an
    announced eval that stalls). A batcher that was never announced to
    (unit tests, raw use) keeps the plain fixed window.
    """

    def __init__(self, max_batch: int = 8, window_ms: float = 1.0,
                 mesh=None, queue_max: int = 4096) -> None:
        self.max_batch = max(1, int(max_batch))
        self.window_s = max(0.0, float(window_ms)) / 1000.0
        self.mesh = mesh
        # Bounded request queue: the async pipeline lets encode run ahead
        # of dispatch, so the gather queue needs a ceiling — a wedged
        # dispatcher must surface as worker backpressure (blocking put),
        # not unbounded growth. The default is generous (orders of
        # magnitude above worker count); queue_max <= 0 means unbounded.
        self.queue_max = int(queue_max)
        self._pending: "collections.deque[_Request]" = collections.deque()  # guarded-by: _lock
        self._scan = None
        self._scan_lock = witness_lock("batcher.DeviceBatcher._scan_lock")  # prewarm + dispatcher race
        # (shape key, b_pad) -> WireLayout, shared with the prewarm threads
        # (guarded-by: _lock); layout -> the DISPATCHER's host buffers,
        # touched by that one thread only
        self._layouts: Dict[tuple, wire.WireLayout] = {}
        self._wire_bufs: Dict[wire.WireLayout, wire.WireBuffers] = {}
        # padded-shape key -> set of batch buckets already compiled/warming
        self._warmed: Dict[tuple, set] = {}
        self._warm_threads: List[threading.Thread] = []
        self._lock = witness_lock("batcher.DeviceBatcher._lock")
        # every arrival (run), withdrawal (cancel_expected) and take (the
        # dispatcher's) notifies it, under _lock: it wakes a gather, and a
        # worker that waits for room in a full queue
        self._wake = witness_condition(
            "batcher.DeviceBatcher._wake", self._lock)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._serial = next(_SERIAL)
        # observability — the server publishes these as
        # nomad.device_batcher.* gauges in its stats sweep (/v1/metrics).
        # Written by the dispatcher thread AND by scheduler workers on the
        # forced-kernel path (engine.compute_system_placements), so every
        # read-modify-write takes _lock (enforced by nomad-lint).
        self.stats = tracked_dict("batcher.DeviceBatcher.stats", {  # guarded-by: _lock
            "dispatches": 0,
            # of those, the ones of a single eval slot (b_pad == 1): they
            # run the program without a batch axis (engine._batched_scan_fn)
            "lone_dispatches": 0,
            "evals": 0,
            "max_batch_seen": 0,
            "padded_evals": 0,
            # wave-fill accounting: gathers = gather rounds closed,
            # full_gathers = rounds that filled max_batch. The r05 DNF
            # shipped 21 dispatches averaging ~16 evals against a 64 cap
            # with nothing recording the fill ratio; bench stamps these
            # on every config artifact now.
            "gathers": 0,
            "full_gathers": 0,
            # gather latency (enqueue -> dispatch start): an operator
            # watching /v1/metrics sees directly whether batching is
            # adding scheduling latency
            "gather_wait_ms_total": 0.0,
            "gather_wait_ms_max": 0.0,
            # gathers that waited for at least one announced eval, and
            # what those cost from their first request to the close
            "gathers_held": 0,
            "gather_held_ms_total": 0.0,
            # per-dispatch timing split: host pad/stack (the evals written
            # into the wire buffers) vs compute (the call, then the one
            # wait for the output on the host: H2D, launch, kernel and the
            # output's copy down) vs transfer (the one host array split
            # into the six results; on the mesh path compute ends at a
            # fence and transfer is six copies down). Totals of the
            # stamps each dispatch's record keeps (trace/lifecycle
            # .on_dispatch), which splits compute further and feeds
            # dispatch_profile()
            "pad_stack_ms_total": 0.0,
            "compute_ms_total": 0.0,
            "transfer_ms_total": 0.0,
            "d2h_bytes_total": 0,
            # how many arrays crossed the host-device boundary: one per
            # dtype group up (2-4) and one down a dispatch on the wire
            # path, 48 and 5 on the mesh path
            "h2d_arrays_total": 0,
            "d2h_arrays_total": 0,
            # placement steps the evals asked for against the steps the
            # padded batch ran (b_pad x the wave's longest eval, which is
            # the bound of the device's loop): what the batch axis pads
            "steps": 0,
            "padded_steps": 0,
            # steps whose runner-up lay inside the near-tie band
            "near_ties": 0,
            # degradations that keep the eval alive but hide a device
            # problem unless counted: a batched dispatch that raised and
            # was retried eval-by-eval on the single scan, and a sibling
            # bucket whose background compile failed. Zero in a healthy
            # run; chip_smoke.py fails on either. (Published with the rest
            # of this dict as nomad.device_batcher.* gauges.)
            "batch_fallbacks": 0,
            "prewarm_failures": 0,
        })
        # Demand-aware gather (guarded-by: _lock): the count of announced
        # evals still en route (expect()), which is what a gather holds
        # for. Armed lazily on the first expect() so raw batchers (unit
        # tests, forced-kernel paths that never announce) keep the fixed
        # window.
        self._expected = 0
        # every announcement ever made; less _expected, those that have
        # arrived or been withdrawn since (what a gather counts up to)
        self._announced_n = 0
        self._demand_aware = False
        _LIVE.add(self)

    # -- lifecycle -------------------------------------------------------

    def _ensure_started(self) -> None:
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._dispatch_loop, name="device-batcher",
                    daemon=True,
                )
                self._thread.start()

    def stop(self, timeout: Optional[float] = 5) -> None:
        """Stop the dispatcher and join warm-compile threads. The default
        bounded join keeps production/atexit shutdown from hanging on a
        wedged compile; pass timeout=None for a DETERMINISTIC full join
        (the multichip dryrun's clean-exit contract — a prewarm thread
        still inside the runtime at interpreter teardown segfaults)."""
        self._stop.set()
        with self._wake:
            self._wake.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
        # join outstanding warm-compile threads: a prewarm mid-compile at
        # interpreter teardown segfaults inside the runtime
        self.wait_warm(timeout=timeout)
        # release anyone still parked
        with self._wake:
            parked = list(self._pending)
            self._pending.clear()
            self._wake.notify_all()
        for req in parked:
            req.error = RuntimeError("device batcher stopped")
            req.event.set()

    # -- worker-facing ---------------------------------------------------

    def queue_depth(self) -> int:
        """Requests gathered but not yet dispatched — the pipeline's
        dispatch-stage depth gauge (published as
        nomad.pipeline.batcher_queue_depth in the server stats sweep)."""
        return len(self._pending)

    def has_warmed(self) -> bool:
        """True once at least one batch has dispatched — i.e. compile
        buckets exist and a follow-up eval of a seen shape pays for its
        own steps alone (the device's loop stops at the wave's longest
        eval). The engine's warm-bucket retry gate (compute_placements)
        reroutes small OCC retries here."""
        with self._lock:
            return self.stats["dispatches"] > 0

    def expect(self, n: int = 1) -> None:
        """Announce ``n`` evals en route that will submit here. A gather
        holds (up to window_ms) while announced evals are outstanding, so
        a cohort of concurrently snapshotting and encoding evals forms
        ONE wave, and closes the moment none is. The server's worker
        announces a service or batch eval after its index wait and before
        the snapshot permit, or already with the last eval's answer in
        hand when the broker holds its next one (Worker.announce_next);
        engine.compute_placements announces before encode for a planner
        that did not. Every expect() must be
        balanced by run(expected=True) or cancel_expected() — the worker
        and the engine each do so in a try/finally; a leaked expectation
        holds a gather until the next arrival passes it over, or the
        window_ms cap, never a hang."""
        with self._lock:
            self._demand_aware = True
            self._expected += n
            self._announced_n += n

    def cancel_expected(self) -> None:
        """Withdraw one expect() (the eval places nothing, went to the
        host stack, or raised). Wakes a holding
        gather, which may have been waiting for this eval alone."""
        with self._wake:
            self._expected = max(0, self._expected - 1)
            self._wake.notify_all()

    def run(self, enc: EncodedEval, expected: bool = False):
        """Submit one encoded eval; blocks until its results are ready.
        Returns (chosen, scores, pulls, skipped, evict) numpy arrays of
        length enc.p (already sliced back from the padded batch).
        The eval whose stage is open on the calling thread
        (``lifecycle.current_eval``) is named in its dispatch's record.

        ``expected=True`` consumes one prior expect() announcement
        (arrival: the demand token converts into a queued request).

        Robust against a concurrent stop(): the wait loop re-ensures the
        dispatcher is alive, so a request that slipped into the queue
        after stop() drained it is picked up by the restarted thread
        rather than parking its worker forever."""
        try:
            # chaos hook: a fault here is a failed/slow device round trip
            # for THIS eval — the engine's dispatch guard reroutes it to
            # the host iterator path (parity-identical placements,
            # reference latency)
            chaos_fire("device_dispatch", evals=enc.p)
            self._ensure_started()
            req = _Request(enc)
        except BaseException:
            # a chaos-failed dispatch must not leave a phantom
            # expectation holding future gathers open
            if expected:
                self.cancel_expected()
            raise
        with self._wake:
            while 0 < self.queue_max <= len(self._pending):
                self._wake.wait(0.5)
            # one step under the lock: the token becomes the queued
            # request, so the gather this wakes finds the eval it held for
            self._pending.append(req)
            if expected:
                self._expected = max(0, self._expected - 1)
            self._wake.notify_all()
        while not req.event.wait(timeout=0.5):
            self._ensure_started()
        if req.error is not None:
            raise req.error
        return req.result

    # -- dispatcher ------------------------------------------------------

    def _gather(self) -> Tuple[List[_Request], str]:
        """One batch and what closed it (for the dispatch's record).
        Waits for a first request (an empty batch when stopped), then
        after every arrival or withdrawal takes what is queued, without
        waiting, and decides: full, or past the window cap, or nothing
        announced before the first request was taken still en route ->
        dispatch; else sleep until the next arrival or withdrawal. The
        evals it waits for are counted, not named: as many arrivals and
        withdrawals as there were announcements when it began, which is
        exact while evals arrive in the order they were announced in and
        closes a little early when they do not (a stalled or leaked
        announcement is passed over by the next arrival). Never
        announced to, the batch waits out the window."""
        batch: List[_Request] = []
        held = False
        deadline = None
        due = None
        with self._wake:
            while not self._pending:
                if self._stop.is_set():
                    return batch, ""
                self._wake.wait(0.2)
            while True:
                while self._pending and len(batch) < self.max_batch:
                    batch.append(self._pending.popleft())
                self._wake.notify_all()   # there is room in the queue
                if len(batch) >= self.max_batch:
                    closed_by = "full"
                    break
                if self.window_s <= 0:
                    closed_by = "no_window"
                    break
                if deadline is None:
                    deadline = _phases.now() + self.window_s
                remaining = deadline - _phases.now()
                if remaining <= 0:
                    closed_by = "window"
                    break
                if self._demand_aware:
                    if due is None:
                        due = self._announced_n
                    if self._announced_n - self._expected >= due:
                        closed_by = (
                            "demand_drained" if held else "nothing_announced")
                        break
                    held = True
                self._wake.wait(remaining)
            self.stats["gathers"] += 1
            if len(batch) >= self.max_batch:
                self.stats["full_gathers"] += 1
            if held:
                self.stats["gathers_held"] += 1
                self.stats["gather_held_ms_total"] += (
                    _phases.now() - batch[0].t_enqueue) * 1000.0
        return batch, closed_by

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            batch, closed_by = self._gather()
            if not batch:
                continue
            # dtype-homogeneous sub-batches: co-batching must never change
            # an eval's arithmetic (f32 evals upcast could select
            # differently than they would alone). int32 = the exact
            # integer parity spec; floats = throughput modes.
            for dtype in (np.int32, np.float64, np.float32):
                group = [r for r in batch if r.enc.dtype == dtype]
                if group:
                    self._run_batch_safe(group, closed_by)

    def _run_batch_safe(self, batch: List[_Request],
                        closed_by: Optional[str] = None) -> None:
        try:
            self._run_batch(batch, closed_by)
        except Exception:  # noqa: BLE001 — confine the blast radius
            logger.warning(
                "batched dispatch failed; retrying %d evals individually",
                len(batch), exc_info=True,
            )
            with self._lock:
                self.stats["batch_fallbacks"] += 1
            # an upload of the failed dispatch may still be reading them
            self._wire_bufs.clear()
            from .engine import TpuPlacementEngine

            engine = TpuPlacementEngine.shared()
            for req in batch:
                try:
                    req.result = engine.run_scan_single(req.enc)
                except Exception as e:  # noqa: BLE001 — the engine's
                    # dispatch guard degrades this eval to the host stack
                    req.error = e
                req.event.set()

    def _scan_fn(self):
        """The program a dispatch runs: the wire scan
        (engine._build_wire_scan: packed buffers in, one array out), or,
        with a mesh configured, the unpacked batched scan
        (engine._build_batched_scan) sharded over it. Double-checked
        lock: the prewarm thread and the dispatcher both initialize
        lazily, and losing a duplicate build would orphan the loser's
        jit compile cache."""
        scan = self._scan
        if scan is None:
            with self._scan_lock:
                if self._scan is None:
                    if self.mesh is not None:
                        from ..parallel.sharding import batched_scan_shardings

                        self._scan = _build_batched_scan(
                            in_shardings=batched_scan_shardings(self.mesh))
                    else:
                        self._scan = _build_wire_scan()
                scan = self._scan
        return scan

    def _buckets(self) -> List[int]:
        mid = max(1, self.max_batch // 4)
        out = [1]
        if mid not in out:
            out.append(mid)
        if self.max_batch not in out:
            out.append(self.max_batch)
        if self.mesh is not None:
            ep = self.mesh.shape.get("evals", 1)
            out = sorted({((b + ep - 1) // ep) * ep for b in out})
        return out

    def _layout(self, key: tuple, b_pad: int,
                dims: Dict[str, int]) -> wire.WireLayout:
        with self._lock:
            layout = self._layouts.get((key, b_pad))
            if layout is None:
                layout = self._layouts[(key, b_pad)] = wire.WireLayout(
                    key, b_pad, dims)
        return layout

    def _prewarm_siblings(self, shape_key: tuple, current_b_pad: int,
                          call_at) -> None:
        """First sight of a padded shape: compile its OTHER batch buckets
        on a background thread by running the scan on inert copies of one
        eval (``call_at(b)`` runs it at bucket ``b``). The persistent XLA
        cache makes repeats across restarts cheap, but even a cache HIT
        load is seconds — hide it off the dispatch path. Device time for
        the warming calls interleaves with real dispatches at the
        runtime's discretion; correctness is unaffected (results
        discarded)."""
        with self._lock:
            warmed = self._warmed.setdefault(shape_key, set())
            todo = [
                b for b in self._buckets()
                if b != current_b_pad and b not in warmed
            ]
            warmed.add(current_b_pad)
            if not todo:
                return
            warmed.update(todo)

        def warm() -> None:
            for b in todo:
                try:
                    call_at(b)
                except Exception:  # noqa: BLE001 — warming never fails a
                    # dispatch: the bucket compiles on its first real use
                    logger.warning("bucket prewarm failed (b=%d)", b,
                                   exc_info=True)
                    with self._lock:
                        self.stats["prewarm_failures"] += 1

        t = threading.Thread(target=warm, name="batcher-prewarm", daemon=True)
        with self._lock:
            self._warm_threads.append(t)
        t.start()

    def wait_warm(self, timeout: Optional[float] = None) -> None:
        """Block until outstanding bucket-warming finishes (benches /
        boot sequences that want compiles out of their timed window).
        Tracking mutations stay under the lock so a warm thread spawned
        concurrently is never dropped unjoined."""
        while True:
            with self._lock:
                pending = [t for t in self._warm_threads if t.is_alive()]
                self._warm_threads = pending
            if not pending:
                return
            for t in pending:
                t.join(timeout=timeout)
            if timeout is not None:
                # one bounded pass only
                with self._lock:
                    self._warm_threads = [
                        t for t in self._warm_threads if t.is_alive()
                    ]
                return

    def dispatch_profile(self) -> Dict[str, object]:
        """Per-dispatch timing split + a roofline note for the batched
        placement scan, from this batcher's dispatch records in
        trace/lifecycle's ring (the last 4,096): where does a dispatch's
        wall time go — host pad/stack (the evals written into the wire
        buffers), H2D + launch (the call: one upload per dtype group),
        kernel wait (until the ONE output array is on the host: the
        kernel and its copy down) and transfer (D2H: that one array split
        into the six results, host work only; on the mesh path six
        copies down after a fence) — and how many arrays cross the
        boundary each way? The note names the binding leg so
        four-rounds-flat throughput plateaus read as "kernel wait-bound at
        X ms/dispatch" instead of a bare number."""
        with self._lock:
            s = dict(self.stats)
        recs = [r for r in _lifecycle.dispatch_records()
                if r["batcher"] == self._serial]
        n = len(recs)
        if n == 0:
            return {"dispatches": s["dispatches"],
                    "note": "no dispatches recorded"}

        def avg_ms(a: str, b: str) -> float:
            return sum(r[b] - r[a] for r in recs) * 1000.0 / n

        pad = avg_ms("t_start", "t_stack")
        launch = avg_ms("t_stack", "t_called")
        kernel = avg_ms("t_called", "t_ready")
        xfer = avg_ms("t_ready", "t_host")
        d2h_bytes = sum(r["d2h_bytes"] for r in recs)
        # the copy down ends inside the kernel-wait leg on the wire path,
        # so the rate is over both legs: a floor, not the link's
        gbps = d2h_bytes / ((kernel + xfer) * n / 1e3) / 1e9 \
            if kernel + xfer > 0 else 0.0
        h2d_arrays = sum(r["h2d_arrays"] for r in recs) / n
        d2h_arrays = sum(r["d2h_arrays"] for r in recs) / n
        legs = {"pad/stack (host)": pad, "H2D + launch": launch,
                "kernel wait (device)": kernel, "transfer (D2H)": xfer}
        bound = max(legs, key=legs.get)
        total = pad + launch + kernel + xfer
        evals = sum(r["b"] for r in recs)
        note = (
            f"{bound}-bound: {legs[bound]:.2f}ms of {total:.2f}ms per "
            f"dispatch (pad/stack {pad:.2f}ms, H2D + launch {launch:.2f}ms "
            f"in {h2d_arrays:.1f} arrays, kernel wait {kernel:.2f}ms, "
            f"transfer {xfer:.2f}ms in {d2h_arrays:.1f} arrays, "
            f"{gbps:.4f} GB/s D2H, {evals / n:.1f} evals/dispatch)"
        )
        return {
            "dispatches": s["dispatches"],
            "evals": s["evals"],
            "recorded": n,
            "pad_stack_ms_avg": round(pad, 3),
            "h2d_launch_ms_avg": round(launch, 3),
            "kernel_wait_ms_avg": round(kernel, 3),
            "compute_ms_avg": round(launch + kernel, 3),
            "transfer_ms_avg": round(xfer, 3),
            "d2h_bytes_total": s["d2h_bytes_total"],
            "d2h_gbps": round(gbps, 6),
            "h2d_arrays_avg": round(h2d_arrays, 2),
            "d2h_arrays_avg": round(d2h_arrays, 2),
            "useful_steps_pct": round(
                100.0 * sum(r["steps"] for r in recs)
                / max(1, sum(r["padded_steps"] for r in recs)), 2),
            "note": note,
        }

    @staticmethod
    def _batch_dims(encs: List[EncodedEval]) -> Dict[str, int]:
        """The batch's shared bucketed dims (pad_encoded's keywords)."""
        # pow2 buckets bound recompiles. A wave of one-group evals keeps
        # a group axis of ONE, and every per-group row select of the step
        # folds into a reshape; a mixed wave widens, as every axis does
        g_pad = _pow2ceil(max(e.g for e in encs))
        # S stays ZERO when no co-batched eval has spreads (the
        # compiled step skips the whole spread machinery); mixed
        # batches widen — same pattern as the affinity axis
        s_raw = max(e.s for e in encs)
        # COARSE placement-count buckets (64/256/1024, pow2 beyond): a
        # fresh compile (even a persistent-cache load) per pow2 bucket
        # costs seconds, and a padded step costs NOTHING: the device's
        # loop stops at the wave's longest eval (p_real), so the bucket
        # sizes only the xs and the output buffers. A retried partial
        # eval of 1-16 placements rides the 64 program at a bound of its
        # own p. 257..1024 collapses into ONE bucket: a mid-run OCC retry
        # of a few hundred placements must ride the wave cohort's warm
        # 1024 bucket, not stall the dispatcher on a fresh 512 compile.
        p_raw = max(e.p for e in encs)
        d_pad = max(e.static[0].shape[1] for e in encs)
        # absent-feature axes stay ZERO when the whole batch lacks them
        # (the compiled step skips those ops); mixed batches widen
        aff_raw = max(e.static[4].shape[0] for e in encs)
        evd_raw = max(e.xs[3].shape[1] for e in encs)
        # preemption candidate axis: zero when no co-batched eval preempts
        prec_raw = max(e.static[20].shape[1] for e in encs)
        prec_pad = _pow2ceil(prec_raw) if prec_raw else 0
        return {
            "n_pad": max(_round_up(e.n_real) for e in encs),
            "g_pad": g_pad,
            "s_pad": _pow2ceil(s_raw) if s_raw else 0,
            "v_pad": _pow2ceil(max(max(e.v for e in encs), 2)),
            "p_pad": (
                64 if p_raw <= 64 else 256 if p_raw <= 256
                else 1024 if p_raw <= 1024 else _pow2ceil(p_raw)
            ),
            "d_pad": d_pad,
            "k_pad": max(e.xs[1].shape[1] for e in encs),
            "aff_pad": g_pad if aff_raw else 0,
            "evd_pad": d_pad if evd_raw else 0,
            "fac_pad": max(e.xs[7].shape[1] for e in encs),
            "dpd_pad": max(e.static[17].shape[0] for e in encs),
            "dpv_pad": max(e.carry[8].shape[1] for e in encs),
            "fnd_pad": max(e.xs[9].shape[1] for e in encs),
            "prec_pad": prec_pad,
            "pregp_pad": (
                _pow2ceil(max(max(e.carry[11].shape[0] for e in encs), 1))
                if prec_pad else 0
            ),
        }

    def _bucket(self, b: int) -> int:
        """Three batch buckets — 1, max/4, max. Unrestricted pow2 buckets
        each cost a tens-of-seconds XLA compile; but padding every small
        batch to max wastes real device time (per-step cost grows with
        the batch axis). Compiles are amortized by the persistent cache."""
        mid = max(1, self.max_batch // 4)
        return 1 if b == 1 else (mid if b <= mid else self.max_batch)

    def _run_batch(self, batch: List[_Request],
                   closed_by: Optional[str] = None) -> None:
        """One dispatch, bracketed leg by leg: a TraceAnnotation each, so
        the profiler's trace carries the dispatch above the device line,
        by wave, and stamps on phases.now's clock, which become this
        dispatch's record and its phases:

        ``t_start`` → ``t_stack`` (pad_stack): the evals written, padded,
        into the wire buffers. → ``t_called`` (h2d_launch): the call — one
        upload per dtype group and the launch. → ``t_ready``
        (kernel_wait): the ONE wait, reading the output array, so it
        covers the kernel and the copy down (starting that copy when the
        call returns, ``copy_to_host_async``, bought nothing on the v5e:
        7.857 against 7.856 ms a lone dispatch). → ``t_host`` (d2h): that
        array split into the six results, host work only. →
        ``t_handed``: every worker released. On the mesh path ``t_ready``
        is a fence on the device's outputs and d2h is six copies down."""
        import jax

        annotate = jax.profiler.TraceAnnotation
        wave = _lifecycle.next_wave()
        t_start = _phases.now()
        encs = [r.enc for r in batch]
        dims = self._batch_dims(encs)
        dtype = encs[0].dtype  # dispatch loop groups by dtype
        b = len(encs)
        b_pad = self._bucket(b)
        p_pad = dims["p_pad"]

        with annotate("nomad.pad_stack", wave=wave):
            if self.mesh is not None:
                # ROADMAP D9: the mesh shards arrays by their node axis
                # (parallel/sharding.py's positional specs), which a flat
                # buffer does not have: this path keeps the 49 stacked
                # arrays up, a fence, and six copies down
                args, b_pad, n_pad = self._pad_and_stack(
                    encs, dims, dtype, b_pad)
                h2d_arrays = len(jax.tree_util.tree_leaves(args))
                d2h_arrays = 6
            else:
                bufs = self._pack(encs, dims, dtype, b_pad)
                args = (bufs.layout,) + bufs.arrays
                n_pad = bufs.layout.n_pad
                h2d_arrays, d2h_arrays = len(bufs.arrays), 1
            scan = self._scan_fn()
        t_stack = _phases.now()
        with annotate("nomad.h2d_launch", wave=wave):
            out = scan(*args)
        t_called = _phases.now()
        with annotate("nomad.kernel_wait", wave=wave):
            if self.mesh is None:
                host = np.asarray(out)
            else:
                # the fence: np.asarray below then times ONLY the D2H copy
                out = jax.block_until_ready(out[1])
        t_ready = _phases.now()
        with annotate("nomad.d2h", wave=wave):
            if self.mesh is None:
                d2h_bytes = host.nbytes
                chosen, scores, pulls, skipped, evict, rival = (
                    wire.split_outputs(bufs.layout, host))
            else:
                chosen, scores, pulls, skipped, evict, rival = (
                    np.asarray(a) for a in out)
                d2h_bytes = (chosen.nbytes + scores.nbytes + pulls.nbytes
                             + skipped.nbytes + evict.nbytes + rival.nbytes)
        t_host = _phases.now()
        steps = sum(e.p for e in encs)
        # the bound the device's loop ran: the wave's longest eval
        n_steps = max(e.p for e in encs)
        padded_steps = b_pad * n_steps
        # steps whose runner-up lay inside the near-tie band: what the
        # engine's referee will look at (intscore.NEAR_TIE_BAND_Q30)
        near_ties = int(sum(
            np.count_nonzero(rival[bi, :e.p] >= 0)
            for bi, e in enumerate(encs)))
        t_first_enqueue = min(r.t_enqueue for r in batch)

        with self._lock:
            self.stats["dispatches"] += 1
            self.stats["lone_dispatches"] += int(b_pad == 1)
            self.stats["evals"] += b
            self.stats["padded_evals"] += b_pad - b
            self.stats["max_batch_seen"] = max(self.stats["max_batch_seen"], b)
            self.stats["pad_stack_ms_total"] += (t_stack - t_start) * 1000.0
            self.stats["compute_ms_total"] += (t_ready - t_stack) * 1000.0
            self.stats["transfer_ms_total"] += (t_host - t_ready) * 1000.0
            self.stats["d2h_bytes_total"] += d2h_bytes
            self.stats["h2d_arrays_total"] += h2d_arrays
            self.stats["d2h_arrays_total"] += d2h_arrays
            self.stats["steps"] += steps
            self.stats["padded_steps"] += padded_steps
            self.stats["near_ties"] += near_ties
            for req in batch:
                wait_ms = (t_start - req.t_enqueue) * 1000.0
                if wait_ms > 0:
                    self.stats["gather_wait_ms_total"] += wait_ms
                    self.stats["gather_wait_ms_max"] = max(
                        self.stats["gather_wait_ms_max"], wait_ms
                    )

        for bi, req in enumerate(batch):
            p = req.enc.p
            req.result = (
                chosen[bi, :p], scores[bi, :p], pulls[bi, :p], skipped[bi, :p],
                evict[bi, :p], rival[bi, :p],
            )
            req.event.set()
        t_handed = _phases.now()

        _lifecycle.on_dispatch(
            wave=wave, source="batcher", batcher=self._serial,
            eval_ids=[r.eval_id for r in batch if r.eval_id is not None],
            b=b, b_pad=b_pad, p_pad=p_pad,
            # the mesh may have widened the node axis past the batch's
            n_pad=n_pad, steps=steps, n_steps=n_steps,
            padded_steps=padded_steps, near_ties=near_ties,
            closed_by=closed_by,
            d2h_bytes=d2h_bytes, h2d_arrays=h2d_arrays,
            d2h_arrays=d2h_arrays, t_first_enqueue=t_first_enqueue,
            t_start=t_start, t_stack=t_stack, t_called=t_called,
            t_ready=t_ready, t_host=t_host, t_handed=t_handed,
        )
        _phases.record("gather", t_first_enqueue, t_start)
        _phases.record("pad_stack", t_start, t_stack)
        _phases.record("h2d_launch", t_stack, t_called)
        _phases.record("kernel_wait", t_called, t_ready)
        _phases.record("d2h", t_ready, t_host)

    def _pack(self, encs: List[EncodedEval], dims: Dict[str, int], dtype,
              b_pad: int) -> wire.WireBuffers:
        """Write the batch into the dispatcher's buffers of its layout:
        one pass, each array straight to its padded slot. The buffers are
        reused from dispatch to dispatch: this thread is the only one that
        touches them, and it packs the next batch only after the ONE wait
        on the previous output, which the device produces after it has
        read every upload. A dispatch that raises forgets its buffers
        (``_run_batch_safe``), so none is rewritten with a copy in flight."""
        key = wire.shape_key(encs[0], dims, dtype)
        layout = self._layout(key, b_pad, dims)
        bufs = self._wire_bufs.pop(layout, None)
        if bufs is None:
            bufs = wire.WireBuffers(layout)
            while len(self._wire_bufs) >= _WIRE_BUFS_KEPT:
                del self._wire_bufs[next(iter(self._wire_bufs))]
        self._wire_bufs[layout] = bufs  # most recently used last
        wire.pack(bufs, encs)

        def call_at(b: int, enc=encs[0]) -> None:
            # the warm thread's own buffers: the dispatcher's are in use
            sibling = wire.WireBuffers(self._layout(key, b, dims))
            wire.pack(sibling, [enc])
            np.asarray(self._scan_fn()(sibling.layout, *sibling.arrays))

        # Warm the SIBLING batch buckets of this shape in the background
        # (precompile pinned buckets): the first dispatch of a new shape
        # pays its own compile/cache-load synchronously, but the
        # follow-up waves (smaller tails, single-eval retries) must not
        # stall multi-second on theirs.
        self._prewarm_siblings(key, b_pad, call_at)
        return bufs

    def _pad_and_stack(self, encs: List[EncodedEval], dims: Dict[str, int],
                       dtype, b_pad: int):
        """The mesh path's host side: every eval padded (pad_encoded), the
        batch stacked array by array along a leading eval axis rounded to
        the mesh's eval axis, the node axis to its node axis."""
        from jax.tree_util import tree_leaves, tree_map

        ep = self.mesh.shape.get("evals", 1)
        b_pad = ((b_pad + ep - 1) // ep) * ep
        nn = self.mesh.shape.get("nodes", 1)
        dims = dict(dims, n_pad=((dims["n_pad"] + nn - 1) // nn) * nn)
        padded = [pad_encoded(e, dtype=dtype, **dims) for e in encs]
        one = padded[0]

        def call_at(b: int) -> None:
            stacked = tree_map(lambda a: np.stack([a] * b), one)
            np.asarray(self._scan_fn()(*stacked)[1][0])

        self._prewarm_siblings(
            tuple((a.shape, str(a.dtype)) for a in tree_leaves(one)),
            b_pad, call_at)

        while len(padded) < b_pad:
            padded.append(one)  # inert copies; results discarded
        stacked = tree_map(lambda *a: np.stack(a), *padded)
        return stacked, b_pad, dims["n_pad"]
