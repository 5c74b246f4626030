"""Near ties, decided as float64 decides them.

The scan scores in Q30 fixed point (intscore.py), which tracks upstream's
float64 score to a few parts in 10**7. Where two candidates lie closer
than that, Q30 can order them the other way, and the guarantee is
upstream's order. The step therefore names, per placement, the candidates
that lie inside ``intscore.NEAR_TIE_BAND_Q30`` of its winner (``rival``:
the nearest, the farthest and whether more crowd it; -1 where there is
none: nearly every step). For those steps alone this module scores those
nodes as the host stack does — the same Python float arithmetic as
``scheduler/rank.py`` and ``scheduler/spread.py``, term by term, on the
plan's own snapshot plus the eval's placements so far — and where float64
picks another node the eval's result is CUT at that step: the rival takes
the placement, the carry is replayed to there exactly as the step's
``carry_update`` would have left it, and the rest is dispatched again
through the warm program. About seven steps in 1,000
are looked at and two in 10**5 overruled (PERF.md). Where three or more
crowd one band, every candidate the step's limit iterator drew from is
scored.

What is not refereed keeps the device's pick and is counted
(``nomad.tpu_engine.referee_unsupported``): an eval with evictions,
preemption tables, distinct_hosts, distinct_property or a failed step, and
every integer eval of a fleet padded beyond 2**15 nodes (the step's one
int32 holds two node indices of 15 bits: there it names no rival).
"""
from __future__ import annotations

import logging

import numpy as np

from ..scheduler.feasible import matches_affinity
from ..scheduler.propertyset import get_property
from ..scheduler.rank import BIN_PACKING_MAX_FIT_SCORE
from ..scheduler.spread import (
    desired_counts,
    even_spread_boost,
    targeted_spread_boost,
)
from ..structs.funcs import score_fit
from ..structs.structs import AllocatedTaskResources, ComparableResources
from ..trace import lifecycle
from ..utils import metrics
from .encode import DIM_CPU, DIM_MEM, subset_encoded_rows
from .intscore import (
    E27_BITS,
    FEAT_AFF_BIT,
    FEAT_FEAS_BIT,
    NEAR_TIE_BAND_Q30,
    RIVAL_BITS,
    RIVAL_CROWDED,
    TERM_ONE,
)

logger = logging.getLogger("nomad_tpu.tpu.referee")

NEAR_TIE_STEPS = "nomad.tpu_engine.near_tie_steps"
NEAR_TIE_CROWDED = "nomad.tpu_engine.near_tie_crowded"
NEAR_TIE_OVERRULED = "nomad.tpu_engine.near_tie_overruled"
REFEREE_UNSUPPORTED = "nomad.tpu_engine.referee_unsupported"

# indices into EncodedEval.static / .carry / .xs (engine._make_step)
(_TOTALS, _ASKS, _FEAT, _AFF, _DH_JOB, _DH_TG, _SPREAD_VIDS, _SPREAD_ACTIVE,
 _N_REAL, _E_ASK, _DP_VIDS) = 0, 2, 3, 4, 6, 7, 9, 13, 15, 16, 17
_USED, _TG_COUNTS, _JOB_COUNTS, _SP_COUNTS, _SP_ENTRY, _OFFSET, _E_BASE = (
    0, 1, 2, 3, 4, 5, 7)
_TG_IDX, _PENALTY, _EVICT_NODE, _SUM_SW = 0, 1, 2, 6


def _supported(enc, chosen, skipped) -> bool:
    return (
        enc.pre_allocs is None
        and enc.dtype == np.int32
        and not (np.asarray(enc.xs[_EVICT_NODE]) >= 0).any()
        and enc.static[_DP_VIDS].shape[0] == 0
        and not np.asarray(enc.static[_DH_JOB]).any()
        and not np.asarray(enc.static[_DH_TG]).any()
        and bool((chosen >= 0).all()) and not bool(skipped.any())
    )


class _Float64Step:
    """The host stack's final score of a node at ONE step of an eval:
    BinPack, JobAntiAffinity, NodeReschedulingPenalty, NodeAffinity and
    Spread in the stack's order, then the mean (rank.py, spread.py), over
    the plan's snapshot and the eval's placements before the step."""

    def __init__(self, enc, job, ctx, chosen, k: int) -> None:
        self.enc, self.job, self.ctx, self.k = enc, job, ctx, k
        tg_idx = np.asarray(enc.xs[_TG_IDX])
        self.g = g = int(tg_idx[k])
        self.tg = job.task_groups[g]
        before, before_g = chosen[:k], tg_idx[:k]
        asks = np.asarray(enc.static[_ASKS])
        # what every node holds when the step begins, this ask included
        placed = asks[before_g].astype(np.float64)
        self.util = np.asarray(enc.carry[_USED], np.int64) + asks[g] + np.stack(
            [np.bincount(before, placed[:, d], enc.n_pad)
             for d in range(asks.shape[1])], axis=1).astype(np.int64)
        mine = before[before_g == g]
        self.collisions = (np.asarray(enc.carry[_TG_COUNTS])[g]
                           + np.bincount(mine, minlength=enc.n_pad))
        penalty = np.asarray(enc.xs[_PENALTY])
        self.penalty = set(penalty[k].tolist()) if penalty.shape[-1] else ()
        self.affinities = list(job.affinities) + list(self.tg.affinities)
        for task in self.tg.tasks:
            self.affinities.extend(task.affinities)
        self.sum_aff = sum(abs(float(a.weight)) for a in self.affinities)
        # SpreadIterator: the job's spreads, then the group's (its property
        # sets' order); the encode's rows are the group's first
        n_tg = len(self.tg.spreads)
        self.spreads = []
        sum_sw = float(np.asarray(enc.xs[_SUM_SW])[k])
        for spread, si in ([(sp, n_tg + i) for i, sp in enumerate(job.spreads)]
                           + [(sp, i) for i, sp in enumerate(self.tg.spreads)]):
            vids = np.asarray(enc.static[_SPREAD_VIDS])[g, si]
            counts = np.maximum(
                np.asarray(enc.carry[_SP_COUNTS])[g, si], 0).astype(np.int64)
            entry = np.array(np.asarray(enc.carry[_SP_ENTRY])[g, si])
            counts += np.bincount(vids[mine], minlength=len(counts))
            entry[vids[mine]] = True
            self.spreads.append((spread, vids, counts, entry,
                                 desired_counts(spread, self.tg.count), sum_sw))

    def candidates(self, start: int, pulled: int) -> list:
        """The nodes the step's limit iterator drew from: the feasible
        ones of the ``pulled`` ring positions from ``start``."""
        enc = self.enc
        n_real = max(int(enc.static[_N_REAL]), 1)
        window = (start + np.arange(min(pulled, n_real))) % n_real
        feat = np.asarray(enc.static[_FEAT])[self.g, window]
        fits = (self.util[window]
                <= np.asarray(enc.static[_TOTALS])[window]).all(axis=1)
        return window[((feat >> FEAT_FEAS_BIT) & 1 == 1) & fits].tolist()

    def shortlist(self, cands: list, f_w: float) -> list:
        """Those of ``cands`` that can stand at or above ``f_w``: the same
        terms over all of them at once in numpy, which is the scalar
        arithmetic to ~1e-9 (the affinity term is read from its Q30 value)
        and is used to leave nodes OUT, never to decide between them."""
        enc, g = self.enc, self.g
        c = np.asarray(cands, np.int64)
        res = np.asarray(enc.table.reserved, np.float64)[c, :2]
        cap = np.asarray(enc.table.totals, np.float64)[c, :2] - res
        free = 1.0 - (res + self.util[c, :2]) / cap
        total = np.clip(20.0 - np.power(10.0, free[:, 0])
                        - np.power(10.0, free[:, 1]), 0.0, 18.0) / 18.0
        terms = np.ones(len(c))
        coll = self.collisions[c]
        total += np.where(coll > 0, -(coll + 1.0) / float(self.tg.count), 0.0)
        terms += coll > 0
        if self.penalty:
            hit = np.isin(c, list(self.penalty))
            total -= hit
            terms += hit
        aff = np.asarray(enc.static[_AFF])
        if aff.shape[0]:
            has = (np.asarray(enc.static[_FEAT])[g, c] >> FEAT_AFF_BIT) & 1
            total += np.where(has == 1, aff[g, c] / float(TERM_ONE), 0.0)
            terms += has
        if self.spreads:
            boost = np.zeros(len(c))
            for row in self.spreads:
                vids = row[1][c]
                for v in np.unique(vids).tolist():
                    n = int(c[np.argmax(vids == v)])
                    boost[vids == v] += self._spread(enc.nodes[n], n, *row)
            total += boost
            terms += boost != 0.0
        return c[total / terms >= f_w - 1e-8].tolist()

    def __call__(self, n: int) -> float:
        node = self.enc.nodes[n]
        util = ComparableResources()
        util.add(node.comparable_reserved_resources())
        util.add(ComparableResources(flattened=AllocatedTaskResources(
            cpu_shares=int(self.util[n, DIM_CPU]),
            memory_mb=int(self.util[n, DIM_MEM]))))
        scores = [score_fit(node, util) / BIN_PACKING_MAX_FIT_SCORE]
        collisions = int(self.collisions[n])
        if collisions > 0:
            scores.append(-1.0 * float(collisions + 1) / float(self.tg.count))
        if n in self.penalty:
            scores.append(-1.0)
        if self.affinities:
            total = 0.0
            for aff in self.affinities:
                if matches_affinity(self.ctx, aff, node):
                    total += float(aff.weight)
            if total != 0.0:
                scores.append(total / self.sum_aff)
        if self.spreads:
            spread_score = 0.0
            for row in self.spreads:
                spread_score += self._spread(node, n, *row)
            if spread_score != 0.0:
                scores.append(spread_score)
        return sum(scores) / len(scores)

    @staticmethod
    def _spread(node, n, spread, vids, counts, entry, desired, sum_sw) -> float:
        """One spread's term for node ``n``: spread.py's own arithmetic
        over the counts the encode and the eval's placements give."""
        value, ok = get_property(node, spread.attribute)
        if not ok:
            return -1.0
        current = int(counts[vids[n]])
        if not spread.spread_target:
            seen = counts[:-1][entry[:-1]]
            if not seen.size:
                return 0.0
            return even_spread_boost(current, int(seen.min()), int(seen.max()))
        return targeted_spread_boost(
            desired, value, current + 1, spread.weight, sum_sw)


def _replayed_rest(enc, chosen, pulls, k: int):
    """The eval from step k+1 on: the rows left, over the carry as the
    step's ``carry_update`` leaves it after ``chosen[:k+1]``."""
    from .engine import EncodedEval

    tg_idx = np.asarray(enc.xs[_TG_IDX])[:k + 1]
    nodes = chosen[:k + 1]
    asks = np.asarray(enc.static[_ASKS])
    carry = list(enc.carry)
    used = np.array(carry[_USED])
    np.add.at(used, nodes, asks[tg_idx].astype(used.dtype))
    tg_counts = np.array(carry[_TG_COUNTS])
    np.add.at(tg_counts, (tg_idx, nodes), 1)
    job_counts = np.array(carry[_JOB_COUNTS])
    np.add.at(job_counts, nodes, 1)
    sp_counts = np.array(carry[_SP_COUNTS])
    sp_entry = np.array(carry[_SP_ENTRY])
    vids = np.asarray(enc.static[_SPREAD_VIDS])
    active = np.asarray(enc.static[_SPREAD_ACTIVE])
    for si in range(vids.shape[1]):
        on = active[tg_idx, si]
        v = vids[tg_idx[on], si, nodes[on]]
        np.add.at(sp_counts, (tg_idx[on], si, v), 1)
        sp_entry[tg_idx[on], si, v] = True
    # the running Q27 product, in placement order (intscore.e_sel_py)
    e_base = np.array(carry[_E_BASE])
    e_ask = np.asarray(enc.static[_E_ASK])
    for g, n in zip(tg_idx.tolist(), nodes.tolist()):
        e_base[n] = (e_base[n].astype(np.int64)
                     * e_ask[g, n].astype(np.int64)) >> E27_BITS
    n_real = max(int(enc.static[_N_REAL]), 1)
    carry[_USED], carry[_TG_COUNTS], carry[_JOB_COUNTS] = (
        used, tg_counts, job_counts)
    carry[_SP_COUNTS], carry[_SP_ENTRY], carry[_E_BASE] = (
        sp_counts, sp_entry, e_base)
    carry[_OFFSET] = np.int32(
        (int(carry[_OFFSET]) + int(pulls[:k + 1].sum())) % n_real)
    xs, missing = subset_encoded_rows(
        enc.xs, enc.missing_list, range(k + 1, enc.p))
    return EncodedEval(
        n_real=enc.n_real, n_pad=enc.n_pad, g=enc.g, s=enc.s, v=enc.v,
        p=enc.p - k - 1, dtype=enc.dtype, static=enc.static,
        carry=tuple(carry), xs=xs, missing_list=missing, nodes=enc.nodes,
        table=enc.table, start_ns=enc.start_ns, dense_ok=enc.dense_ok)


def referee(enc, job, ctx, outs, dispatch, eval_id=None):
    """``outs`` (the scan's six results for ``enc``) with every near tie
    decided in float64, under a ``referee`` stage of ``eval_id``'s record
    where there is one to decide. ``dispatch(enc)`` runs the rest of a cut
    eval: the batcher's ``run`` or the single scan."""
    if enc.n_pad > 1 << RIVAL_BITS and enc.dtype == np.int32:
        # two node indices do not fit the step's one int32: it named none
        metrics.incr_counter(REFEREE_UNSUPPORTED)
        return outs
    if not (np.asarray(outs[5]) >= 0).any():
        return outs
    with lifecycle.stage("referee", eval_id):
        return _settle(enc, job, ctx, outs, dispatch)


def _settle(enc, job, ctx, outs, dispatch):
    chosen, scores, pulls, skipped, evict, rival = (
        np.asarray(o) for o in outs)
    flagged = np.nonzero(rival >= 0)[0]
    metrics.incr_counter(NEAR_TIE_STEPS, int(flagged.size))
    if not _supported(enc, chosen, skipped):
        metrics.incr_counter(REFEREE_UNSUPPORTED)
        return outs
    n_real = max(int(enc.static[_N_REAL]), 1)
    index = (1 << RIVAL_BITS) - 1
    for k in flagged.tolist():
        w = int(chosen[k])
        score = _Float64Step(enc, job, ctx, chosen, k)
        start = (int(enc.carry[_OFFSET]) + int(pulls[:k].sum())) % n_real
        f_w = score(w)
        if int(rival[k]) & RIVAL_CROWDED:
            # more than two in the band: every candidate of the step, of
            # which none can stand above the winner by more than the band
            metrics.incr_counter(NEAR_TIE_CROWDED)
            rivals = [(f, n) for f, n in
                      ((score(n), n) for n in score.shortlist(
                          score.candidates(start, int(pulls[k])), f_w)
                       if n != w)
                      if f - f_w <= 2.0 * NEAR_TIE_BAND_Q30 / TERM_ONE]
        else:
            rivals = [(score(n), n) for n in
                      {int(rival[k]) & index, (int(rival[k]) >> RIVAL_BITS) & index}]
        # float64's pick; its ties go to the ring's first, as
        # MaxScoreIterator keeps the first maximum it is handed
        f_r, _ring, r = max((f, -((n - start) % n_real), n)
                            for f, n in rivals + [(f_w, w)])
        if r == w:
            continue
        metrics.incr_counter(NEAR_TIE_OVERRULED)
        # enough to stage the pair again (tests/test_c1m_parity.py): per
        # node its totals, reserved, what it holds at the step (this ask
        # included) and how many of the job's placements are among that
        logger.info(
            "near tie overruled: %s job %s (%d of ask %s, %d stanzas) step "
            "%d: device %d (score60 %d, float64 %r) float64 %d (%r); %s",
            job.type, job.id, score.tg.count,
            np.asarray(enc.static[_ASKS])[score.g].tolist(),
            len(score.spreads) + len(score.affinities), k, w,
            int(scores[k]), f_w, r, f_r,
            [(n, np.asarray(enc.table.totals)[n].tolist(),
              np.asarray(enc.table.reserved)[n].tolist(),
              score.util[n].tolist(), int(score.collisions[n]))
             for n in (w, r)])
        chosen = chosen.copy()
        scores = scores.copy()
        chosen[k] = r
        scores[k] = int(round(f_r * 60.0 * TERM_ONE))
        head = (chosen[:k + 1], scores[:k + 1], pulls[:k + 1],
                skipped[:k + 1], evict[:k + 1],
                np.full(k + 1, -1, rival.dtype))
        if k + 1 == enc.p:
            return head
        rest_enc = _replayed_rest(enc, chosen, pulls, k)
        rest = dispatch(rest_enc)
        if (np.asarray(rest[5]) >= 0).any():
            rest = _settle(rest_enc, job, ctx, rest, dispatch)
        return tuple(np.concatenate([a, np.asarray(b)[:rest_enc.p]])
                     for a, b in zip(head, rest))
    return outs
