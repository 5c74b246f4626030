"""The plain reference: Nomad's generic placement stack, written out.

This file imports nothing of the program. It restates, in float64 numpy,
what upstream Nomad's scheduler does for one service or batch evaluation
(scheduler/stack.go GenericStack, rank.go, spread.go, select.go,
structs/funcs.go ScoreFit) in the deterministic mode the configurations
state: no shuffle, candidate ring started at ``crc32(eval id) % nodes``.

    for each missing alloc, in name-index order:
        walk the ring of ready nodes in the job's datacenters from where
        the last walk stopped; drop nodes that fail the job's constraint or
        cannot fit the ask beside what is already there (reserved + allocs
        in the snapshot + this plan so far);
        score each survivor: BestFit-v3 binpack, job anti-affinity, node
        affinity, targeted spread; the final score is the mean of the
        terms that apply;
        stop after `limit` survivors (2 for batch, ceil(log2 n) for
        service, every node when the group has spread or affinity
        stanzas; up to 3 survivors scoring <= 0 are set aside and offered
        last); place on the first survivor with the highest score.

Inputs are plain arrays and dicts made by cluster.py / jobs.py from the
seed and by the comparison from what the state store holds; nothing here
is taken from the program's encoders or kernels.
"""
from __future__ import annotations

import math
import zlib

import numpy as np

MAX_SKIP = 3            # stack.go maxSkip
SKIP_THRESHOLD = 0.0    # stack.go skipScoreThreshold
NO_LIMIT = 2 ** 31 - 1


def ring_start(eval_id: str, n: int) -> int:
    """Where the candidate ring starts for this evaluation."""
    return (zlib.crc32(eval_id.encode()) & 0x7FFFFFFF) % n if n else 0


class Replay:
    """One evaluation of one job against one snapshot of the fleet.

    ``fleet`` is cluster.Fleet (arrays in registration order); ``usage`` the
    (cpu, mem, disk) arrays used by live allocs in the snapshot, reserved
    excluded; ``spec`` a job dict of jobs.py. ``dtype`` is float64 as the
    configuration states; the control of the comparison passes a lower one.
    """

    def __init__(self, fleet, usage, spec, eval_id, dtype=np.float64,
                 ring_from_eval=True, with_spread=True):
        self.f = fleet
        self.spec = spec
        self.dt = dtype
        dcs = [fleet.dc_names.index(d) for d in spec["datacenters"]
               if d in fleet.dc_names]
        self.base = np.flatnonzero(np.isin(fleet.dc, dcs))
        self.n = len(self.base)
        # ring_from_eval=False is a control of the comparison: the ring
        # started at node 0 for every evaluation, so ties fall to the lowest
        # node instead of the evaluation's own first
        self.offset = ring_start(eval_id, self.n) if ring_from_eval else 0
        b = self.base
        self.static_ok = (fleet.linux[b] if spec["linux_only"]
                          else np.ones(self.n, bool))
        # capacity bookkeeping in exact integers, on the ring's own order
        self.tot = np.stack([fleet.cpu[b], fleet.mem[b], fleet.disk[b]])
        self.res = np.stack([fleet.rcpu[b], fleet.rmem[b], fleet.rdisk[b]])
        self.used = self.res + np.stack([u[b] for u in usage]).astype(np.int64)
        self.ask = np.array([spec["cpu"], spec["mem"], spec["disk"]], np.int64)
        self.count = int(spec["count"])
        self.mine = np.zeros(self.n, np.int64)      # this job's allocs per node
        self.dc_of = fleet.dc[b]
        self.per_dc = np.zeros(len(fleet.dc_names), np.int64)
        self.linux = fleet.linux[b]
        stanzas = bool(spec.get("spread") or spec.get("affinity"))
        if stanzas:
            self.limit = NO_LIMIT
        elif spec["kind"] == "batch":
            self.limit = 2
        else:
            self.limit = max(2, int(math.ceil(math.log2(self.n)))) if self.n else 2
        self.desired = None
        # with_spread=False is a control of the comparison: the spread
        # stanza's boost left out of the score
        if spec.get("spread") and with_spread:
            sp = spec["spread"]
            desired = np.full(len(fleet.dc_names), np.nan)
            total = 0.0
            for value, pct in sp["targets"].items():
                d = (float(pct) / 100.0) * float(self.count)
                total += d
                if value in fleet.dc_names:
                    desired[fleet.dc_names.index(value)] = d
            if 0 < total < float(self.count):          # implicit "*" target
                desired[np.isnan(desired)] = float(self.count) - total
            self.desired = desired
            # one spread stanza: weight / sum of weights
            self.spread_w = float(sp["weight"]) / float(sp["weight"])

    # -- scoring ---------------------------------------------------------

    def scores(self, c):
        """Final score of ring positions ``c`` for the next placement."""
        dt = self.dt
        ten = dt(10.0)
        node = (self.tot[:2, c] - self.res[:2, c]).astype(dt)
        util = (self.used[:2, c] + self.ask[:2, None]).astype(dt)
        free = dt(1.0) - util / node
        fit = np.clip(dt(20.0) - (ten ** free[0] + ten ** free[1]),
                      dt(0.0), dt(18.0)) / dt(18.0)
        total = fit
        terms = np.ones(len(c), dt)
        coll = self.mine[c]
        hit = coll > 0
        total = total + np.where(hit, -(coll + 1).astype(dt) / dt(self.count),
                                 dt(0.0))
        terms = terms + hit
        if self.spec.get("affinity"):
            aff = self.spec["affinity"]
            match = self.linux[c] if aff["linux"] else ~self.linux[c]
            w = dt(aff["weight"]) / dt(abs(aff["weight"]))
            total = total + np.where(match, w, dt(0.0))
            terms = terms + match
        if self.desired is not None:
            d = self.desired[self.dc_of[c]]
            usedc = (self.per_dc[self.dc_of[c]] + 1).astype(dt)
            with np.errstate(divide="ignore", invalid="ignore"):
                boost = np.where(np.isnan(d), dt(-1.0),
                                 ((d - usedc) / d) * dt(self.spread_w))
            boost = boost.astype(dt)
            nz = boost != 0
            total = total + np.where(nz, boost, dt(0.0))
            terms = terms + nz
        return total / terms

    def candidates(self):
        """Ring positions that pass the constraint and fit, in walk order,
        with how many ring entries the walk consumes to reach each."""
        ok = self.static_ok & np.all(
            self.used + self.ask[:, None] <= self.tot, axis=0)
        rolled = np.roll(ok, -self.offset)
        steps = np.flatnonzero(rolled)
        return (steps + self.offset) % self.n, steps + 1

    # -- one placement ---------------------------------------------------

    def step(self):
        """Choose the next placement. Returns (ring position or -1, the
        positions offered, their scores). Does not commit."""
        cand, consumed = self.candidates()
        if len(cand) == 0:
            return -1, cand, np.zeros(0, self.dt)
        if self.limit == NO_LIMIT:
            sc = self.scores(cand)
            if sc.max() > SKIP_THRESHOLD:
                # every survivor is offered; the ones set aside score <= 0
                # and cannot win; the first of the highest wins
                self._advance(self.n)
                return int(cand[int(np.argmax(sc))]), cand, sc
            return self._walk(cand, consumed, sc)
        # the walk pulls at most `limit` offers and MAX_SKIP set-asides
        head = self.limit + MAX_SKIP
        return self._walk(cand[:head], consumed[:head], self.scores(cand[:head]))

    def _walk(self, cand, consumed, sc):
        """select.go LimitIterator under MaxScoreIterator, transcribed call
        for call over the survivors ``cand`` (scores ``sc``)."""
        pulled, dry = 0, False

        def source():
            nonlocal pulled, dry
            if pulled >= len(cand):
                dry = True
                return None
            pulled += 1
            return pulled - 1

        skipped, skipped_at, seen, offered = [], 0, 0, []

        def next_option():
            nonlocal skipped_at
            k = source()
            if k is None and skipped_at < len(skipped):
                k = skipped[skipped_at]
                skipped_at += 1
            return k

        while seen != self.limit:
            k = next_option()
            if k is None:
                break
            while (k is not None and sc[k] <= SKIP_THRESHOLD
                   and len(skipped) < MAX_SKIP):
                skipped.append(k)
                k = source()
            seen += 1
            if k is None:
                k = next_option()
                if k is None:
                    break
            offered.append(k)
        self._advance(self.n if dry else int(consumed[pulled - 1]))
        if not offered:
            return -1, cand[:0], sc[:0]
        best = offered[0]
        for k in offered[1:]:
            if sc[k] > sc[best]:
                best = k
        return int(cand[best]), cand[offered], sc[offered]

    def _advance(self, consumed):
        self.offset = (self.offset + consumed) % self.n

    def commit(self, pos):
        """Record a placement on ring position ``pos`` in the plan."""
        self.used[:, pos] += self.ask
        self.mine[pos] += 1
        self.per_dc[self.dc_of[pos]] += 1

    def position_of(self, node_index):
        hit = np.flatnonzero(self.base == node_index)
        return int(hit[0]) if len(hit) else -1


def follow(fleet, usage, spec, eval_id, path, stop_at=None, **variant):
    """Walk one evaluation along ``path`` (fleet node index per name
    index): at every step the reference, built with ``variant``, chooses
    from the state the path had reached, and then the path's node is
    committed. Returns (the fleet node chosen at each step or -1, the
    score it gave that node). ``stop_at(step, chosen)`` true ends the walk
    after that step."""
    r = Replay(fleet, usage, spec, eval_id, **variant)
    picks, scores = [], []
    for k, node in enumerate(path):
        pos, _offered, sc = r.step()
        picks.append(int(r.base[pos]) if pos >= 0 else -1)
        scores.append(float(sc.max()) if len(sc) else math.nan)
        want = r.position_of(node)
        if want < 0 or (stop_at is not None and stop_at(k, picks[-1])):
            break
        r.commit(want)
    return picks, scores


def differences(nodes, scores, ref_nodes, ref_scores) -> tuple:
    """(steps at which ``nodes`` is not the reference's choice; the widest
    gap between ``scores`` and the reference's score of the same node, over
    the steps that agree)."""
    mismatches, gap = 0, 0.0
    for k, (pick, score) in enumerate(zip(ref_nodes, ref_scores)):
        if nodes[k] != pick:
            mismatches += 1
        elif scores is not None:
            gap = max(gap, abs(float(scores[k]) - score))
    return mismatches, gap


def compare(fleet, usage, spec, eval_id, served, served_scores,
            stop_at_first=False):
    """Follow the plan the program served (node and recorded score per name
    index) with the reference in float64. Returns (mismatches, exact; the
    widest gap between the score the program recorded for a placement and
    the reference's; steps compared)."""
    stop = (lambda k, pick: pick != served[k]) if stop_at_first else None
    picks, scores = follow(fleet, usage, spec, eval_id, served, stop)
    return differences(served, served_scores, picks, scores) + (len(picks),)
