"""The ``tpu_binpack`` placement engine.

Replaces the reference's per-node iterator chain
(GenericScheduler.computePlacements -> GenericStack.Select -> BinPackIterator,
scheduler/generic_sched.go:426 / rank.go:176) with ONE ``jax.jit``'d
``lax.scan`` over the evaluation's placement sequence. Each scan step scores
every node at once:

  feasibility  = class-mask  &  capacity-fit  &  distinct-hosts   (vector ops)
  score terms  = binpack (BestFit-v3) + job-anti-affinity + reschedule
                 penalty + node affinity + spread                  (vector ops)
  selection    = exact emulation of the ring-ordered LimitIterator
                 (log2 N window, skip<=3 below 0.0) + MaxScore     (cumsums,
                 masked argmax)

and the carry threads the intra-eval mutation the reference gets from
ProposedAllocs (context.go:120): used capacity, per-TG/job alloc counts,
spread value counts, the source-iterator ring offset, and failed-TG
coalescing. In deterministic mode the engine is plan-for-plan identical to
the host pipeline; tests/test_tpu_parity.py fuzzes that equivalence.

The node axis is the scale axis: all [N]-shaped arrays may be sharded over a
``jax.sharding.Mesh`` (see nomad_tpu/parallel/), with XLA inserting the
all-reduce/argmax collectives.
"""
from __future__ import annotations

import logging
import math
import os
import time as _time
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..structs.structs import (
    ALLOC_CLIENT_PENDING,
    ALLOC_DESIRED_RUN,
    AllocatedResources,
    AllocatedSharedResources,
    AllocatedTaskResources,
    Allocation,
)
from ..structs.network import NetworkIndex
from .encode import (
    DIM_CPU,
    DIM_MBITS,
    DIM_MEM,
    MAX_PENALTY_NODES,
    NodeTable,
    TGSpec,
    UnsupportedByEngine,
    _distinct_property_arrays,
    build_node_table,
    build_tg_spec,
    job_device_dims,
)

logger = logging.getLogger("nomad_tpu.tpu.engine")

MAX_SKIP = 3

# GIL convoy guard shared with the scheduler's other host phases
# (utils/hostwork.py): encode/apply are pure-Python, so letting hundreds
# of worker threads enter them at once only buys context-switch thrash.
from ..utils import phases as _phases
from ..utils.hostwork import HOST_WORK_SEM as _HOST_WORK_SEM


class EncodedEval:
    """One evaluation's placement problem as dense numpy arrays, plus the
    host-side context needed to materialize results into a Plan. Produced
    by ``TpuPlacementEngine.encode_eval``; consumed by the single-eval scan
    or stacked with other evals by the DeviceBatcher."""

    __slots__ = (
        "n_real", "n_pad", "g", "s", "v", "p", "dtype",
        "static", "carry", "xs",
        "missing_list", "nodes", "table", "start_ns", "dense_ok",
        "pre_allocs",
    )

    def __init__(self, *, n_real, n_pad, g, s, v, p, dtype,
                 static, carry, xs, missing_list, nodes, table, start_ns,
                 dense_ok=False, pre_allocs=None):
        self.n_real = n_real
        self.n_pad = n_pad
        self.g = g
        self.s = s
        self.v = v
        self.p = p
        self.dtype = dtype
        self.static = static
        self.carry = carry
        self.xs = xs
        self.missing_list = missing_list
        self.nodes = nodes
        self.table = table
        self.start_ns = start_ns
        # True when every placement qualifies for the dense plan->FSM
        # path (fresh, no networks/devices/canaries): results stay as
        # arrays end to end (structs.DenseTGPlacements)
        self.dense_ok = dense_ok
        # Device-side preemption (tpu/preempt.py): per-node candidate
        # Allocation lists parallel to the encoded candidate slots, for
        # mapping eviction-set output columns back to real allocs. None
        # when the eval encodes no preemption.
        self.pre_allocs = pre_allocs


def _pad_preempt_arrays(pre_tables, n_pad, n_real, node_c2):
    """Pad one eval's PreemptTables (encode.build_preempt_tables) to the
    node grid and derive the Q27 eviction-free factors. ``None`` tables
    yield width-0 arrays — the step's whole eviction block compiles away
    (``has_pre`` is a shape test). Returns the 6 static entries followed
    by the 3 carry seeds."""
    if pre_tables is None:
        return (
            np.zeros((n_pad, 0, 4), np.int32), np.zeros((n_pad, 0), np.int32),
            np.zeros((n_pad, 0), bool), np.zeros((n_pad, 0), np.int32),
            np.zeros((n_pad, 0), np.int32), np.zeros((n_pad, 0, 2), np.int32),
            np.zeros((n_pad, 0), bool), np.zeros((0, 3), np.int64),
            np.zeros(0, np.int32),
        )
    from .intscore import E27_ONE, e27_np, xq_np

    c_w = pre_tables.c
    pre_res = np.zeros((n_pad, c_w, 4), np.int32)
    pre_res[:n_real] = pre_tables.res4
    pre_prio = np.zeros((n_pad, c_w), np.int32)
    pre_prio[:n_real] = pre_tables.prio
    pre_elig = np.zeros((n_pad, c_w), bool)
    pre_elig[:n_real] = pre_tables.elig
    pre_mp = np.zeros((n_pad, c_w), np.int32)
    pre_mp[:n_real] = pre_tables.mp
    pre_gid = np.zeros((n_pad, c_w), np.int32)
    pre_gid[:n_real] = pre_tables.gid
    # Eviction FREES capacity: Q27 factor e27(+res/cap) per candidate on
    # cpu/mem — same convention as the destructive-update ev_factor.
    # Padded nodes / empty slots hold the neutral factor.
    pre_evf = np.full((n_pad, c_w, 2), E27_ONE, np.int32)
    for d in (0, 1):
        pre_evf[:, :, d] = e27_np(
            xq_np(pre_res[:, :, d].astype(np.int64),
                  np.maximum(node_c2[:, d], 1)[:, None])
        ).astype(np.int32)
    pre_alive0 = np.ones((n_pad, c_w), bool)
    pre_remaining0 = np.zeros((n_pad, 3), np.int64)
    pre_remaining0[:n_real] = pre_tables.remaining3
    pre_counts0 = pre_tables.counts0.astype(np.int32)
    return (pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf,
            pre_alive0, pre_remaining0, pre_counts0)


# The one in-checkout compile cache directory used when the environment
# names none. Fixed (never under $HOME, /tmp, a pid or a timestamp): a
# cache that moves between runs never hits.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_cache_enabled = False


def _enable_persistent_compile_cache() -> None:
    """Persistent XLA compilation cache: scan compiles are tens of seconds
    per shape bucket, and the server process restarts far more often than
    the bucket set changes. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    already uses that directory and this sets none in code; otherwise the
    cache lives in DEFAULT_COMPILE_CACHE_DIR."""
    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def _round_up(n: int, multiple: int = 128) -> int:
    if n <= multiple:
        # small clusters: pad to next power of two to bound recompiles
        p = 8
        while p < n:
            p *= 2
        return p
    return ((n + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# The jit'd scan (pure function of arrays)
# ---------------------------------------------------------------------------


# the near-tie key, an int32: (distance >> 1) << 15 | node index (the
# band's 60 * 2**10 halves into 15 bits); two indices and the crowded bit
# ride one int32 out (intscore.RIVAL_BITS). _MIX: odd multipliers of the
# twin test's wrapping sum
_I32_MAX = (1 << 31) - 1
_MIX = (-1640531527, 506961463, 668265263, 374761393)


def _select(final, feasible, iota, n_real, offset, limit, skip_step,
            totals, util):
    """A step's ``select``: the ring-ordered LimitIterator emulation and
    MaxScore over one node plane (``final``, the score60 int64 of int
    mode or the float score; ``iota`` the node index in the plane's
    shape). Returns ``(chosen, best_score, pulls, offset, rival)``:
    ``chosen`` -1 where no candidate is left or the step is skipped, the
    ring offset advanced by the nodes the limit pulled, and ``rival`` the
    near-tie flag of the refereed path (-1 elsewhere).

    Three reductions to a scalar on the refereed path, two off it (the
    ring cumsum aside): ``before``, the ring offset's sum; ONE
    lexicographic reduction that keeps the highest score and, on equal
    scores, the lowest rank, carrying the node index and the count of
    pulled nodes along; and the near-tie reduction, which reads the
    winner's score."""
    import jax.numpy as jnp
    from jax import lax as jlax

    from .intscore import (
        NEAR_TIE_BAND60,
        PACK_COUNT_MAX,
        RIVAL_BITS,
        pack_count_lanes,
        unpack_count_hi,
        unpack_count_lo,
    )

    assert NEAR_TIE_BAND60 >> 1 < 1 << (31 - RIVAL_BITS)
    i32 = jnp.int32
    node_shape = final.shape
    nodes = tuple(range(len(node_shape)))
    n_pad = math.prod(node_shape)
    int_mode = jnp.issubdtype(final.dtype, jnp.integer)

    # -- ring-ordered limit (no permutation) ---------------------------------
    # Ring prefix sums at natural index i: with S = natural inclusive
    # cumsum, T = total, o = offset, the ring-order cumsum is
    # S(i) - S(o-1) for i >= o and S(i) + (T - S(o-1)) for i < o —
    # elementwise, so the LimitIterator emulation needs no gathers.
    #
    # ONE packed int32 ring cumsum carries everything: the low-score
    # and feasible count planes ride 16-bit lanes of one int32 plane
    # (intscore.pack_count_lanes). Lane exactness: both totals are
    # bounded by n_pad < 2**15, so the low lane never carries into the
    # high lane, and every SELECTED ring branch is lane-wise
    # non-negative (i >= o selects S(i) - S(o-1) with [0..o-1] a
    # subset of [0..i]; i < o selects S(i) + the suffix sum — both
    # >= 0 per lane), so no borrow crosses lanes either. The skip
    # prefix is then min(low_cum, MAX_SKIP) (skipped = the first
    # MAX_SKIP low entries in ring order) and the source prefix is
    # feas_cum - skip_cum. (int64 field-packing would lift the 2**15
    # bound, but int64 prefix sums are pathologically slow on this
    # backend — int32 lanes are free.)
    valid = iota < n_real
    nr = jnp.maximum(n_real, 1)

    feas_v = feasible & valid
    # threshold 0 is exact in both modes (int: score60 <= 0 iff the
    # rational score <= 0; float: the host's 0.0 skip threshold)
    low = feas_v & (final <= 0)

    def ring_cumsum(a_int):
        # the natural order is the flat one: a folded plane is the
        # same words in the same order, so the flat view is free
        s_flat = jnp.cumsum(a_int.reshape(n_pad))
        s_nat = s_flat.reshape(node_shape)
        total = s_flat[-1]
        before = jnp.sum(jnp.where(iota < offset, a_int, 0), dtype=i32)
        ring = jnp.where(
            iota >= offset, s_nat - before, s_nat + (total - before)
        )
        return ring, total

    if n_pad < PACK_COUNT_MAX:
        packed_cum, packed_total = ring_cumsum(pack_count_lanes(low, feas_v))
        low_cum = unpack_count_lo(packed_cum)
        feas_cum = unpack_count_hi(packed_cum)
        low_total = unpack_count_lo(packed_total)
        feas_total = unpack_count_hi(packed_total)
    else:
        # lanes would overflow on a >32K-node pad: two plain cumsums
        low_cum, low_total = ring_cumsum(low.astype(i32))
        feas_cum, feas_total = ring_cumsum(feas_v.astype(i32))

    skipped = low & (low_cum <= MAX_SKIP)
    skip_cum = jnp.minimum(low_cum, MAX_SKIP)
    ret = feas_v & ~skipped
    ret_i = ret.astype(i32)
    ret_cum = feas_cum - skip_cum
    ret_excl = ret_cum - ret_i

    pulled = valid & (ret_excl < limit)
    src_cand = ret & pulled
    ret_total = feas_total - jnp.minimum(low_total, MAX_SKIP)
    backlog_n = jnp.maximum(limit - ret_total, 0)
    skip_i = skipped.astype(i32)
    skip_excl = skip_cum - skip_i
    backlog_cand = skipped & (skip_excl < backlog_n)
    cand = src_cand | backlog_cand

    # -- the winner: ONE lexicographic reduction -----------------------------
    # ranks are unique across candidates (source ranks < ret_total <=
    # backlog ranks), so (max score, min rank) names one node exactly.
    # Every non-candidate is the same sentinel (neg_inf, MAX, MAX), and
    # two candidates never tie on both keys: the join is a maximum under
    # a total order, associative and commutative over the whole plane.
    # The node index rides with the kept side; the pulled count is a sum
    # beside it. A candidate's rank is under n_pad, so "any candidate" is
    # "the kept rank is not the sentinel". Int mode compares the int64
    # score60 as two keys, its high half signed and its low half
    # unsigned: int32 compares, a step 1.0-1.3 us faster on a v5e than one
    # int64 compare (PERF.md §7).
    rank = jnp.where(src_cand, ret_excl, ret_total + skip_excl)
    big = i32(_I32_MAX)
    if int_mode:
        neg_inf = jnp.iinfo(jnp.int64).min // 4
    else:
        neg_inf = jnp.asarray(-jnp.inf, final.dtype)
    cand_scores = jnp.where(cand, final, neg_inf)
    rest = (jnp.where(cand, rank, big), jnp.where(cand, iota, big),
            pulled.astype(i32))
    if int_mode:
        keys = ((cand_scores >> 32).astype(i32),
                cand_scores.astype(jnp.uint32))
        key_inits = (i32(neg_inf >> 32), jnp.uint32(neg_inf & 0xFFFFFFFF))
    else:
        keys, key_inits = (cand_scores,), (neg_inf,)
    n_keys = len(keys)

    def keep_max(a, b):
        # (*score keys, rank, index, pulled): the higher score, else the
        # lower rank, keeps its keys, rank and index; pulled adds up
        def a_first(k):
            if k == n_keys:
                return a[k] < b[k]
            return (a[k] > b[k]) | ((a[k] == b[k]) & a_first(k + 1))

        take_a = a_first(0)
        return tuple(jlax.select(take_a, x, y)
                     for x, y in zip(a[:-1], b[:-1])) + (a[-1] + b[-1],)

    *best_keys, best_rank, index, n_pulled = jlax.reduce(
        keys + rest, key_inits + (big, big, i32(0)), keep_max, nodes)
    if int_mode:
        best_hi, best_lo = best_keys
        best_score = ((best_hi.astype(jnp.int64) << 32)
                      | best_lo.astype(jnp.int64))
    else:
        (best_score,) = best_keys
    any_cand = best_rank != big
    chosen = jnp.where(any_cand & (~skip_step), index, -1)

    pulls = jnp.where(skip_step, 0, n_pulled).astype(i32)
    offset = jnp.where(skip_step, offset, (offset + pulls) % nr).astype(i32)

    # -- near tie: the candidates inside the band ----------------------------
    # Q30 orders two nodes as float64 does only while their scores lie
    # farther apart than the two roundings (intscore.py:
    # NEAR_TIE_BAND_Q30). The step names the candidates whose score is
    # UNDER the winner's by no more than the band: the nearest, the
    # farthest, and whether more than these two crowd it; or -1, on
    # nearly every step. The host scores them in float64
    # (tpu/referee.py) and the device's pick stands unless float64 orders
    # them the other way. Candidates that tie with the winner to the last
    # bit are its twins wherever they hold the winner's cpu and memory,
    # capacity and use (an idle fleet is all twins): they score the same
    # in both arithmetics and tie to the ring's first in both. Whether all
    # of them do is ONE comparison: the four numbers ride a wrapping int32
    # sum of odd multiples (two different nodes share one once in 2**32),
    # and its min and max over the tied set differ when a tie is not a
    # twin, which also reads "crowded". All of it is one reduction after
    # the winner's, which it reads. The key costs a step 5 us on a v5e
    # however its reductions are written (PERF.md §6): its arithmetic over
    # the node plane, not them.
    if not (int_mode and n_pad <= (1 << RIVAL_BITS)):
        return chosen, best_score, pulls, offset, i32(-1)
    winners = cand & (cand_scores == best_score)
    mix = (totals[0] * i32(_MIX[0]) + totals[1] * i32(_MIX[1])
           + util[0] * i32(_MIX[2]) + util[1] * i32(_MIX[3]))
    delta = best_score - cand_scores
    in_band = (delta > 0) & (delta <= NEAR_TIE_BAND60)
    key = ((delta >> 1).astype(i32) << RIVAL_BITS) | iota
    operands = [
        (jnp.where(winners, mix, _I32_MAX), _I32_MAX, jnp.minimum),
        (jnp.where(winners, mix, -_I32_MAX - 1), -_I32_MAX - 1, jnp.maximum),
        (jnp.where(in_band, key, _I32_MAX), _I32_MAX, jnp.minimum),
        (jnp.where(in_band, key, 0), 0, jnp.maximum),
        (in_band.astype(i32), 0, jnp.add)]
    arrays, inits, joins = zip(*operands)
    mix_lo, mix_hi, nearest, farthest, members = jlax.reduce(
        arrays, tuple(i32(v) for v in inits),
        lambda a, b: tuple(j(x, y) for j, x, y in zip(joins, a, b)), nodes)
    uneven = mix_lo != mix_hi
    index_bits = i32((1 << RIVAL_BITS) - 1)
    rival = jnp.where(
        ((members > 0) | uneven) & any_cand & (~skip_step),
        (nearest & index_bits) | ((farthest & index_bits) << RIVAL_BITS)
        | (((members > 2) | uneven).astype(i32) << (2 * RIVAL_BITS)),
        i32(-1),
    )
    return chosen, best_score, pulls, offset, rival


def _make_step():
    """The per-placement scan body, shared by the single-eval scan, the
    eval-batched scan (vmapped over independent evals — the production
    multi-eval path) and the dryrun. Pure function of arrays.

    TPU-shaped by construction (empirically profiled on the real chip):
      - NO gathers/scatters: dynamic row-selects (``asks[g]``-style) and
        carry updates become one-hot ``where``+``sum``/outer-product adds —
        batched gathers cost ~ms each on TPU while the one-hot forms fuse
        into elementwise kernels.
      - NO dot_general: f64 has no MXU path, so one-hot einsums would
        lower to sequential while-loops; ``where``+``sum`` reduces stay on
        the VPU.
      - NO permutation: the ring-ordered LimitIterator emulation uses
        offset-adjusted NATURAL cumsums (ring prefix at natural index i is
        an elementwise function of one natural cumsum and two scalars),
        and tie-breaks select via rank equality, never ``perm[idx]``.
    All transformations are exact (integer adds / one-hot sums with a
    single non-zero term), so outputs are bit-identical to the direct
    indexed formulation — fuzz-asserted against the host pipeline in
    tests/test_tpu_parity.py.

    ``static`` and ``carry`` arrive in the step's own layout
    (``_step_layout``, applied once by every caller, outside its loop):
    node planes with the node axis LAST, one axis of ``n_pad`` or, in a
    program without a batch axis, two, ``(n_pad // 128, 128)``. The step
    reads the node shape off ``job_counts`` and is written for either:
    small arrays meet node planes through ``col``, reductions over nodes
    name ``nodes``.

    The stages of a step carry ``jax.named_scope`` names, so a profiler
    trace groups the scan's operations by what they are for (metadata
    only: the lowered program is bit for bit the unscoped one):
    ``evict_prev`` (the previous alloc's eviction), ``row_select`` (the
    task group's rows), ``feasibility``, ``preemption`` (the greedy sweep
    and the chosen node's eviction set), ``affinity`` (reschedule penalty,
    job anti-affinity), ``spread``, ``binpack_score``, ``score_mean``
    (term presence and the mean), ``select`` (ring-ordered limit and
    first maximum), ``carry_update`` (the placement's one-hot adds and a
    failed placement's revert)."""
    import jax
    import jax.numpy as jnp
    from jax import lax as jlax

    from .intscore import (
        FEAT_AFF_BIT,
        FEAT_FEAS_BIT,
        pack_presence_lanes,
        unpack_feat_lane,
    )

    def step(static, carry, x, past_end=False):
        (totals, reserved, asks, feat_packed, aff_score, desired_counts,
         dh_job, dh_tg, limits, spread_vids, spread_desired, spread_weights,
         spread_has_targets, spread_active, sum_spread_weights, n_real,
         e_ask, dp_vids, dp_limit, dp_applies,
         pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf) = static
        (used, tg_counts, job_counts, spread_counts, spread_entry, offset,
         failed, e_base, dp_counts, pre_alive, pre_remaining, pre_counts) = carry
        (tg_idx, penalty_idx, evict_node, evict_res, evict_tg, limit_p,
         sum_sw_p, ev_factor, rev_factor, forced_node) = x

        # node planes lie node-axis LAST (``_step_layout``): one axis of
        # n_pad, or two, (n_pad // 128, 128), where the program folded it
        node_shape = job_counts.shape
        nodes = tuple(range(-len(node_shape), 0))
        n_pad = math.prod(node_shape)
        g_count = asks.shape[0]
        v_plus = spread_desired.shape[-1]
        fdt = totals.dtype
        # int mode (deterministic/parity): the exact integer spec of
        # tpu/intscore.py. e_base/e_ask carry the Q27 incremental
        # exponentials; float (throughput) mode passes them zero-sized.
        int_mode = jnp.issubdtype(fdt, jnp.integer)
        i64 = jnp.int64
        g = tg_idx

        iota_g = jnp.arange(g_count, dtype=jnp.int32)
        sel_g = (iota_g == g)                       # [G] one-hot of the TG
        iota = jnp.arange(n_pad, dtype=jnp.int32).reshape(node_shape)
        iota_v = jnp.arange(v_plus, dtype=jnp.int32)

        def col(arr):
            # a small array against node planes: one unit axis per node axis
            return arr.reshape(arr.shape + (1,) * len(node_shape))

        def pick_g(arr, fill=0):
            # arr[g] without gather/dot: one-hot mask + sum (exactly one
            # non-zero term, so float results are exact). Sum-promotion
            # (int32 -> int64 under x64) is cast back so carries keep
            # their dtypes.
            shape = (g_count,) + (1,) * (arr.ndim - 1)
            out = jnp.sum(jnp.where(sel_g.reshape(shape), arr, fill), axis=0)
            return out.astype(arr.dtype)

        # ``past_end``: the batched program's mask of a step at or past the
        # eval's own count (the wave's bound is its longest eval's)
        skip_step = jnp.any(sel_g & failed) | past_end

        with jax.named_scope("evict_prev"):
            # -- eviction of the previous alloc (one-hot adds) -----------------
            # shape specialization: an eval with NO destructive updates (the
            # common case — every fresh placement) encodes evict_res with a
            # ZERO trailing axis, and the entire eviction/revert machinery
            # (~15 array passes per step) compiles away.
            has_evict = evict_res.shape[-1] > 0
            if has_evict:
                do_evict = (evict_node >= 0) & (~skip_step)
                ev_node = jnp.maximum(evict_node, 0)
                ev_tg = jnp.maximum(evict_tg, 0)
                oh_ev_node = (iota == ev_node)              # [*N]
                oh_ev_nodef = oh_ev_node.astype(fdt)
                sel_evg = (iota_g == ev_tg)                 # [G]

                def pick_evg(arr, fill=0):
                    shape = (g_count,) + (1,) * (arr.ndim - 1)
                    out = jnp.sum(jnp.where(sel_evg.reshape(shape), arr, fill), axis=0)
                    return out.astype(arr.dtype)

                evict_vec = jnp.where(do_evict, evict_res, 0)  # [D]
                used = used - col(evict_vec) * oh_ev_nodef
                dec_tg = jnp.where(do_evict & (evict_tg >= 0), 1, 0)
                tg_counts = tg_counts - (col(sel_evg) & oh_ev_node) * dec_tg
                job_counts = job_counts - oh_ev_node * jnp.where(do_evict, 1, 0)
                # The evicted alloc's spread usage clears too (host: propertyset
                # cleared_values from plan.node_update; floor-at-zero at read).
                ev_active = pick_evg(spread_active, False)       # [S]
                ev_dec = jnp.where(do_evict & (evict_tg >= 0) & ev_active, 1, 0).astype(fdt)
                vids_evg = pick_evg(spread_vids)                 # [S, *N]
                ev_vid = jnp.sum(jnp.where(oh_ev_node, vids_evg, 0), axis=nodes)
                oh_ev_vid = (iota_v[None, :] == ev_vid[:, None]).astype(fdt)  # [S, V]
                spread_counts = spread_counts - jnp.where(
                    sel_evg[:, None, None], (oh_ev_vid * ev_dec[:, None])[None, :, :], 0
                )
                # eviction frees capacity -> multiply the node's Q27
                # exponential by the precomputed per-placement factor
                if e_base.size:
                    from .intscore import E27_BITS, E27_ONE

                    ev_f = jnp.where(do_evict, ev_factor, E27_ONE).astype(i64)  # [2]
                    eb_ev = (e_base.astype(i64) * col(ev_f)) >> E27_BITS
                    e_base = jnp.where(
                        oh_ev_node, eb_ev, e_base.astype(i64)
                    ).astype(jnp.int32)
                # (distinct_property + in-eval evictions never encode together
                # — the host PropertySet cleared-refund quirk can't be
                # replayed by exact counters; encode gates that combination)

        with jax.named_scope("row_select"):
            # -- row selects ---------------------------------------------------
            ask = pick_g(asks)                               # [D]
            # ONE packed uint8 feature plane carries feasibility and affinity
            # presence (intscore.pack_feat_planes): one pick_g pass where the
            # unpacked layout needed two
            feat_g = pick_g(feat_packed)                     # [*N] uint8
            feas_g = unpack_feat_lane(feat_g, FEAT_FEAS_BIT)
            tg_counts_g = pick_g(tg_counts)                  # [*N]
            desired_g = pick_g(desired_counts).astype(fdt)
            dh_job_g = jnp.any(sel_g & dh_job)
            dh_tg_g = jnp.any(sel_g & dh_tg)
            # shape specialization (compile-time): a job without affinities
            # encodes aff_score with a ZERO G axis, so the f64 pick and the
            # score term vanish from the compiled step entirely (the packed
            # plane's affinity lane is all-zero and never read)
            if aff_score.shape[0] == 0:
                aff = jnp.zeros(node_shape, fdt)
                aff_p = jnp.zeros(node_shape, bool)
            else:
                aff = pick_g(aff_score)
                aff_p = unpack_feat_lane(feat_g, FEAT_AFF_BIT)

        with jax.named_scope("feasibility"):
            # -- feasibility ---------------------------------------------------
            # int mode folds reserved into totals at encode (the scoring
            # exponentials are precomputed factors, so nothing else needs the
            # split) and passes a ZERO-height reserved — one [N, D] add less
            # per step
            if reserved.size:
                util = used + reserved + col(ask)  # [D, *N]
            else:
                util = used + col(ask)
            fits = jnp.all(util <= totals, axis=0)  # superset + bandwidth check

            # job-level distinct_hosts: any co-located alloc of the job rejects;
            # tg-level requires both a job and task-group collision
            dh_mask = jnp.where(
                dh_job_g,
                job_counts == 0,
                jnp.where(dh_tg_g, ~((tg_counts_g > 0) & (job_counts > 0)), True),
            )

        with jax.named_scope("preemption"):
            # -- device-side preemption (tpu/preempt.py) -----------------------
            # shape specialization: non-preempting evals encode the candidate
            # axis C as ZERO width and the whole greedy sweep compiles away.
            # When present, a node whose capacity check fails may be rescued
            # by an eviction set of lower-priority allocs (the reference's
            # PreemptForTaskGroup): cap_ok = fits | pre_met. Preemption never
            # rescues class/constraint/distinct-hosts infeasibility — those
            # masks still AND in below, matching the host stack ordering.
            has_pre = pre_res.shape[1] > 0
            if has_pre:
                from .preempt import CQ_BITS, PENALTY_UNIT, greedy_select_jnp

                # the candidate tables stay as the wire has them, node axis
                # first ([N, C, ...]): ``_step_layout`` folds no such eval
                assert len(node_shape) == 1

                gp_w = pre_counts.shape[0]
                iota_gp = jnp.arange(gp_w, dtype=jnp.int32)
                # num preemptions already planned for each candidate's
                # (job, ns, tg) group — the reference's maxParallel penalty
                oh_gid = pre_gid[:, :, None] == iota_gp[None, None, :]
                num_pre = jnp.sum(
                    jnp.where(oh_gid, pre_counts[None, None, :], 0), axis=-1
                ).astype(jnp.int32)                                    # [N, C]
                pen = jnp.where(
                    (pre_mp > 0) & (num_pre >= pre_mp),
                    (((num_pre + 1) - pre_mp).astype(i64) * PENALTY_UNIT)
                    << CQ_BITS,
                    i64(0),
                )
                ask3 = ask[:3].astype(i64)                             # cpu/mem/disk
                pre_res3 = pre_res[:, :, :3].astype(i64)
                sel_ord, pre_met = greedy_select_jnp(
                    ask3, pre_res3, pre_prio, pen,
                    pre_alive & pre_elig, pre_remaining,
                )
                cap_ok = fits | pre_met
            else:
                cap_ok = fits

        with jax.named_scope("feasibility"):
            feasible = feas_g & cap_ok & dh_mask  # [*N]
            # system-scheduler mode: the candidate node is FIXED per placement
            # (one alloc per eligible node, system_sched.go:268-286); a
            # zero-width axis (generic evals) compiles the restriction away
            if forced_node.shape[-1]:
                fnode = forced_node[0]
                feasible = feasible & ((fnode < 0) | (iota == fnode))

            # distinct_property (feasible.go:353): per-constraint value-count
            # carry, same mechanism as spread counts but FILTERING — a node is
            # infeasible when its value's count reached the allowed limit or
            # the property is missing. D == 0 compiles all of this away.
            if dp_vids.shape[0]:
                v2 = dp_counts.shape[-1]
                iota_v2 = jnp.arange(v2, dtype=jnp.int32)
                oh_dpv = dp_vids[:, None] == col(iota_v2)  # [D, V2, *N]
                dp_cnts = jnp.maximum(dp_counts, 0)  # cleared-value floor
                dp_cnt_n = jnp.sum(
                    jnp.where(oh_dpv, col(dp_cnts), 0), axis=1
                )  # [D, *N]
                dp_applies_g = pick_g(dp_applies, False)  # [D]
                dp_missing = dp_vids == (v2 - 1)
                dp_ok = (~col(dp_applies_g)) | (
                    (~dp_missing) & (dp_cnt_n < col(dp_limit))
                )
                feasible = feasible & jnp.all(dp_ok, axis=0)

        # -- score terms ---------------------------------------------------
        # Two compile-time modes sharing one structure:
        #   int  (deterministic/parity): the exact integer spec of
        #        tpu/intscore.py — Q30 terms, Q27 incremental-multiplicative
        #        exponentials, score60 selection. Bit-identical on every
        #        backend, so plan parity holds ON the real TPU.
        #   float (throughput): f32 arithmetic, non-parity.
        with jax.named_scope("affinity"):
            # same specialization: no reschedule history -> penalty_idx has a
            # zero K axis and the [K, *N] compare disappears
            if penalty_idx.shape[-1] == 0:
                pmask = jnp.zeros(node_shape, bool)
            else:
                pmask = jnp.any(iota == col(penalty_idx), axis=0)

            anti_present = tg_counts_g > 0

        with jax.named_scope("spread"):
            # spread row selects (shared) — value-id lookups as one-hot sums
            vids = pick_g(spread_vids)                       # [S, *N]
            # floor-at-zero matches the host's cleared-value clamping
            s_counts = jnp.maximum(pick_g(spread_counts), 0)    # [S, V]
            s_entry = pick_g(spread_entry, False)            # [S, V]
            desired_sv = pick_g(spread_desired)              # [S, V]
            weights_s = pick_g(spread_weights)
            has_targets_s = pick_g(spread_has_targets, False)
            active_s = pick_g(spread_active, False)

            invalid_bucket = v_plus - 1
            oh_vids = vids[:, None] == col(iota_v)           # [S, V, *N]
            current = jnp.sum(jnp.where(oh_vids, col(s_counts), 0), axis=1)
            missing = vids == invalid_bucket
            has_entries = jnp.any(s_entry[:, :invalid_bucket], axis=-1)  # [S]

        if int_mode:
            from .intscore import (
                BIG_FP,
                E27_BITS,
                E27_ONE,
                RECIP_BITS,
                TERM_BITS,
                TERM_ONE,
                binpack_q30,
            )

            with jax.named_scope("binpack_score"):
                # selection-time exponentials: e_base (running product in the
                # carry) times the static per-TG ask factor — 10**(free - ask/cap)
                ea = pick_g(e_ask)                                 # [2, *N] int32
                e_sel = (e_base.astype(i64) * ea.astype(i64)) >> E27_BITS
                e_sel_i32 = e_sel.astype(jnp.int32)                # placement update
                fit = i64(20 * E27_ONE) - e_sel[0] - e_sel[1]
                fit = jnp.clip(fit, 0, 18 * E27_ONE)
                # Q30 = fit * 2**30 / (18 * 2**27) = (fit*4)//9, as one
                # multiply and one shift (an int64 // is a 30-kernel long
                # division on the TPU: 19 of a step's 84 us, PERF.md §7)
                binpack = binpack_q30(fit)

            with jax.named_scope("affinity"):
                rsh = RECIP_BITS - TERM_BITS
                # -(c+1)/desired via the Q45 reciprocal of the (small, per-step
                # scalar) desired count — error < 4 Q30-ulp
                q_d = jnp.floor_divide(
                    i64(1 << RECIP_BITS), jnp.maximum(desired_g.astype(i64), 1)
                )
                anti = jnp.where(
                    anti_present,
                    -(((tg_counts_g.astype(i64) + 1) * q_d) >> rsh),
                    0,
                )
                resched = jnp.where(pmask, i64(-TERM_ONE), i64(0))

            with jax.named_scope("spread"):
                d64 = desired_sv.astype(i64)                       # [S, V]
                u64 = s_counts.astype(i64) + 1
                w64 = weights_s.astype(i64)[:, None]
                sw64 = jnp.maximum(sum_sw_p.astype(i64), 1)
                # targeted boost: ((d - u)/d)*(w/sum_w) as ONE fused Q30
                # rational, floor-rounded (d in hundredths: d = pct*count).
                # Its operands are functions of the value id alone, so the
                # int64 division (a 30-kernel long division on the TPU)
                # runs over the [S, V] table and each node looks its
                # value's boost up: one non-zero term a sum, so exact
                t_num = (d64 - 100 * u64) * w64 * TERM_ONE
                t_den = jnp.maximum(d64, 1) * sw64
                targeted_sv = jnp.where(
                    d64 > 0,
                    jnp.floor_divide(t_num, t_den),
                    jnp.where(d64 == 0, i64(-BIG_FP), i64(-TERM_ONE)),
                )
                targeted_raw = jnp.sum(
                    jnp.where(oh_vids, col(targeted_sv), 0), axis=1
                )                                                  # [S, *N]

                # even-spread boost (same branch structure as the host);
                # divisions by min_c (a count) via its Q45 reciprocal — [S]-
                # shaped, so the division is off the hot [N] axis
                LARGE = i64(1) << 40
                sc64 = s_counts.astype(i64)[:, :invalid_bucket]
                se = s_entry[:, :invalid_bucket]
                min_c = jnp.where(
                    has_entries, jnp.min(jnp.where(se, sc64, LARGE), axis=-1), 0
                )  # [S]
                max_c = jnp.where(
                    has_entries, jnp.max(jnp.where(se, sc64, -LARGE), axis=-1), 0
                )
                r_min = jnp.floor_divide(
                    i64(1 << RECIP_BITS), jnp.maximum(min_c, 1)
                )  # [S]
                min_cn = col(min_c)
                max_cn = col(max_c)
                cur64 = current.astype(i64)
                delta_boost = jnp.where(
                    min_cn == 0,
                    i64(-TERM_ONE),
                    ((min_cn - cur64) * col(r_min)) >> rsh,
                )
                even = jnp.where(
                    cur64 != min_cn,
                    delta_boost,
                    jnp.where(
                        min_cn == max_cn,
                        i64(-TERM_ONE),
                        jnp.where(
                            min_cn == 0,
                            i64(TERM_ONE),
                            ((max_cn - min_cn) * col(r_min)) >> rsh,
                        ),
                    ),
                )
                even = jnp.where(col(has_entries), even, 0)

                per_spread = jnp.where(col(has_targets_s), targeted_raw, even)
                per_spread = jnp.where(missing, i64(-TERM_ONE), per_spread)
                per_spread = jnp.where(col(active_s), per_spread, 0)
                spread_total = jnp.sum(per_spread, axis=0)  # [*N] int64
                spread_p = spread_total != 0

            with jax.named_scope("score_mean"):
                # term-presence bits packed into ONE uint8 plane: num_terms is
                # 1 + popcount instead of four astype(int32) planes and adds —
                # the whole (presence -> factor -> final) chain is a single
                # fused elementwise expression over [N]
                presence = pack_presence_lanes(anti_present, pmask, aff_p, spread_p)
                num_terms = 1 + jlax.population_count(presence).astype(jnp.int32)
                # mean of terms via EXACT scale-by-60 (all of 1..5 divide 60)
                factor = jnp.floor_divide(60, num_terms).astype(i64)
                final = (
                    binpack + anti + resched
                    + jnp.where(aff_p, aff.astype(i64), 0) + spread_total
                ) * factor
                score_zero = i64(0)
        else:
            with jax.named_scope("binpack_score"):
                node_cpu = totals[DIM_CPU] - reserved[DIM_CPU]
                node_mem = totals[DIM_MEM] - reserved[DIM_MEM]
                free_cpu = 1.0 - util[DIM_CPU] / jnp.maximum(node_cpu, 1e-9)
                free_mem = 1.0 - util[DIM_MEM] / jnp.maximum(node_mem, 1e-9)
                fitness = 20.0 - (jnp.power(10.0, free_cpu) + jnp.power(10.0, free_mem))
                binpack = jnp.clip(fitness, 0.0, 18.0) / 18.0

            with jax.named_scope("affinity"):
                collisions = tg_counts_g.astype(fdt)
                anti = jnp.where(anti_present, -(collisions + 1.0) / desired_g.astype(fdt), 0.0)
                resched = jnp.where(pmask, -1.0, 0.0)

            with jax.named_scope("spread"):
                big = jnp.finfo(fdt).max / 16.0
                used_count = current.astype(fdt) + 1.0           # [S, *N]
                df = jnp.sum(
                    jnp.where(oh_vids, col(desired_sv), 0), axis=1
                ).astype(fdt)
                # divisor: the host SpreadIterator's weight sum accumulates
                # across visited task groups -> passed per placement (sum_sw_p)
                weight_frac = col(weights_s) / jnp.maximum(sum_sw_p, 1e-9)
                # Go float semantics: d == 0 -> -Inf boost (clamped large neg)
                targeted_raw = jnp.where(
                    df > 0.0,
                    (df - used_count) / jnp.where(df > 0.0, df, 1.0) * weight_frac,
                    jnp.where(df == 0.0, -big, -1.0),  # d<0: no target -> -1
                )

                # even-spread boost
                scf = s_counts.astype(fdt)[:, :invalid_bucket]
                entry_counts = jnp.where(s_entry[:, :invalid_bucket], scf, jnp.inf)
                min_c = jnp.where(has_entries, jnp.min(entry_counts, axis=-1), 0.0)  # [S]
                max_counts = jnp.where(s_entry[:, :invalid_bucket], scf, -jnp.inf)
                max_c = jnp.where(has_entries, jnp.max(max_counts, axis=-1), 0.0)
                currentf = current.astype(fdt)
                min_cn = col(min_c)
                max_cn = col(max_c)
                delta_boost = jnp.where(
                    min_cn == 0.0, -1.0,
                    (min_cn - currentf) / jnp.maximum(min_cn, 1e-9)
                )
                even = jnp.where(
                    currentf != min_cn,
                    delta_boost,
                    jnp.where(
                        min_cn == max_cn,
                        -1.0,
                        jnp.where(
                            min_cn == 0.0,
                            1.0,
                            (max_cn - min_cn) / jnp.maximum(min_cn, 1e-9),
                        ),
                    ),
                )
                even = jnp.where(col(has_entries), even, 0.0)

                per_spread = jnp.where(col(has_targets_s), targeted_raw, even)
                per_spread = jnp.where(missing, -1.0, per_spread)
                per_spread = jnp.where(col(active_s), per_spread, 0.0)
                spread_total = jnp.sum(per_spread, axis=0)  # [*N]
                spread_p = spread_total != 0.0

            with jax.named_scope("score_mean"):
                # same popcount fusion as int mode (small counts are exact in
                # any float dtype, so the quotient is bit-identical to the
                # astype-chain form)
                presence = pack_presence_lanes(anti_present, pmask, aff_p, spread_p)
                num_terms = (1 + jlax.population_count(presence)).astype(fdt)
                final = (binpack + anti + resched + jnp.where(aff_p, aff, 0.0) + spread_total) / num_terms
                score_zero = jnp.asarray(0.0, fdt)

        with jax.named_scope("select"):
            chosen, best_score, pulls, offset, rival = _select(
                final, feasible, iota, n_real, offset, limit_p, skip_step,
                totals, util)

        with jax.named_scope("carry_update"):
            # -- apply placement / revert eviction (one-hot adds) --------------
            success = chosen >= 0
            ch = jnp.maximum(chosen, 0)
            oh_ch = (iota == ch)
            oh_chf = oh_ch.astype(fdt)
            add_vec = jnp.where(success, ask, 0)
            used = used + col(add_vec) * oh_chf
            inc_i = jnp.where(success, 1, 0)
            tg_counts = tg_counts + (col(sel_g) & oh_ch) * inc_i
            job_counts = job_counts + oh_ch * inc_i

            ch_vid = jnp.sum(jnp.where(oh_ch, vids, 0), axis=nodes)  # [S]
            oh_ch_vid = (iota_v[None, :] == ch_vid[:, None])              # [S, V]
            inc = jnp.where(success & active_s, 1, 0).astype(fdt)
            spread_counts = spread_counts + jnp.where(
                sel_g[:, None, None], (oh_ch_vid.astype(fdt) * inc[:, None])[None, :, :], 0
            )
            entry_set = sel_g[:, None, None] & (oh_ch_vid & (inc > 0)[:, None])[None, :, :]
            spread_entry = spread_entry | entry_set

            # placement commits the chosen node's new exponential — EXACTLY the
            # already-computed selection value (running-product spec)
            if e_base.size:
                e_base = jnp.where(oh_ch & success, e_sel_i32, e_base)
            if dp_vids.shape[0]:
                ch_vid_dp = jnp.sum(jnp.where(oh_ch, dp_vids, 0), axis=nodes)  # [D]
                inc_dp = dp_applies_g & success
                dp_counts = dp_counts + (
                    (iota_v2[None, :] == ch_vid_dp[:, None]) & inc_dp[:, None]
                ).astype(jnp.int32)

        with jax.named_scope("preemption"):
            # -- commit the eviction set on the chosen node --------------------
            # Host ordering: preemption fires only when the node did NOT fit
            # outright. The greedy set is filtered by the reference's second
            # pass (distance vs the FRESH ask, descending) on the chosen
            # node's extracted [C] row — off the hot [N] axis.
            if has_pre:
                c_w = pre_res.shape[1]
                from .preempt import second_pass_jnp

                fits_ch = jnp.any(oh_ch & fits)
                use_pre = success & (~fits_ch) & (~skip_step)

                def row_c(arr):
                    # arr[ch] without gather: one-hot sum over N (exactly one
                    # non-zero term, so negative fills survive intact)
                    shape = (n_pad,) + (1,) * (arr.ndim - 1)
                    out = jnp.sum(jnp.where(oh_ch.reshape(shape), arr, 0), axis=0)
                    return out.astype(arr.dtype)

                sel_ord_ch = row_c(sel_ord)                        # [C]
                res3_ch = row_c(pre_res3)                          # [C, 3] i64
                rem_ch = row_c(pre_remaining)                      # [3] i64
                keep, p_rank = second_pass_jnp(ask3, res3_ch, sel_ord_ch, rem_ch)
                keep = keep & use_pre                              # [C]

                # freed capacity credits `used` (the alloc itself stays
                # overcommitted for SCORING, matching the host's allocs_fit
                # used — the credit lands after the score terms above)
                res4_ch = row_c(pre_res)                           # [C, 4] i32
                freed4 = jnp.sum(
                    jnp.where(keep[:, None], res4_ch.astype(fdt), 0), axis=0,
                    dtype=fdt,
                )                                                  # [4]
                d_dims = totals.shape[0]
                if d_dims > 4:
                    # batch padding may widen D past the gate's 4 dims; the
                    # extra (device) dims free nothing
                    freed_vec = jnp.concatenate(
                        [freed4, jnp.zeros(d_dims - 4, freed4.dtype)]
                    )
                else:
                    freed_vec = freed4[:d_dims]
                used = used - col(freed_vec) * oh_chf

                # running Q27 exponential: multiply the just-committed chosen
                # row by each kept candidate's eviction factor (slot-ascending
                # product order is fixed, so the result is deterministic)
                if e_base.size:
                    from .intscore import E27_BITS as _PB, E27_ONE as _PO

                    eb_ch = jnp.sum(jnp.where(oh_ch, e_base, 0), axis=nodes,
                                    dtype=i64)                     # [2]
                    evf_ch = row_c(pre_evf)                        # [C, 2] i32
                    for ci in range(c_w):
                        f = jnp.where(keep[ci], evf_ch[ci].astype(i64), i64(_PO))
                        eb_ch = (eb_ch * f) >> _PB
                    e_base = jnp.where(
                        oh_ch & use_pre, col(eb_ch.astype(jnp.int32)), e_base
                    )

                evicted = oh_ch[:, None] & keep[None, :]           # [N, C]
                pre_alive = pre_alive & ~evicted
                freed3 = jnp.sum(jnp.where(keep[:, None], res3_ch, 0), axis=0)
                pre_remaining = pre_remaining + jnp.where(
                    oh_ch[:, None], freed3[None, :], 0
                )
                gid_ch = row_c(pre_gid)                            # [C]
                pre_counts = pre_counts + jnp.sum(
                    ((gid_ch[:, None] == iota_gp[None, :]) & keep[:, None])
                    .astype(jnp.int32),
                    axis=0,
                    dtype=jnp.int32,
                )
                # output column: second-pass rank per evicted slot (ascending
                # rank = final eviction order), -1 for untouched slots
                evict_out = jnp.where(keep, p_rank, jnp.int32(-1))  # [C]
            else:
                evict_out = jnp.zeros((0,), jnp.int32)

        with jax.named_scope("carry_update"):
            # failed placement: revert eviction, mark TG failed
            if has_evict:
                revert = do_evict & (~success)
                used = used + col(jnp.where(revert, evict_res, 0)) * oh_ev_nodef
                rev_i = jnp.where(revert & (evict_tg >= 0), 1, 0)
                tg_counts = tg_counts + (col(sel_evg) & oh_ev_node) * rev_i
                job_counts = job_counts + oh_ev_node * jnp.where(revert, 1, 0)
                spread_counts = spread_counts + jnp.where(
                    sel_evg[:, None, None],
                    (oh_ev_vid * jnp.where(revert, ev_dec, 0).astype(fdt)[:, None])[None, :, :],
                    0,
                )
                if e_base.size:
                    from .intscore import E27_BITS as _E27B, E27_ONE as _E27O

                    rev_f = jnp.where(revert, rev_factor, _E27O).astype(i64)  # [2]
                    eb_rev = (e_base.astype(i64) * col(rev_f)) >> _E27B
                    e_base = jnp.where(
                        oh_ev_node, eb_rev, e_base.astype(i64)
                    ).astype(jnp.int32)
            # forced-node (system) placements are independent per-node
            # decisions: a failure must NOT poison the TG for later nodes
            unforced = (forced_node[0] < 0) if forced_node.shape[-1] else True
            failed = failed | (sel_g & ((~success) & (~skip_step) & unforced))

        new_carry = (used, tg_counts, job_counts, spread_counts, spread_entry,
                     offset, failed, e_base, dp_counts,
                     pre_alive, pre_remaining, pre_counts)
        out = (chosen, jnp.where(success, best_score, score_zero), pulls,
               skip_step, evict_out, rival)
        return new_carry, out

    return step


# Where the wire layout (EncodedEval.static / .carry, tpu/wire.py) keeps a
# leaf's node axis; the preemption tables (node axis first, [N, C, ...])
# are not listed: preempt.py's kernels read them as they are.
_STATIC_NODE_AXIS = {0: 0, 1: 0, 3: 1, 4: 1, 9: 2, 16: 1, 17: 1}
_CARRY_NODE_AXIS = {0: 0, 1: 1, 2: 0, 7: 0}
_LANES = 128


def _step_layout(static, carry, fold):
    """One eval's ``static`` and ``carry`` as ``_make_step``'s step reads
    them: every node plane with its node axis LAST, and with ``fold`` that
    axis viewed as ``(n_pad // 128, 128)``. The TPU tiles an array by its
    last two axes: ``[n_pad, 2]``, ``[n_pad, 4]`` and a ``[1, n_pad]`` row
    of a single task group or spread fill two, four and one sublane of a
    register's eight, where ``[2, 40, 128]`` fills every one. A program
    without a batch axis folds; under ``vmap`` the batch axis fills the
    sublanes. A zero-sized leaf has no node axis and stays as it is; an
    eval with preemption candidates, whose tables keep the node axis
    first, and a fleet padded to less than a lane row are not folded.
    Called once, outside the loop; ``_wire_carry`` is its inverse."""
    import jax.numpy as jnp

    n_pad = carry[2].shape[0]
    fold = fold and n_pad % _LANES == 0 and static[20].shape[1] == 0

    def lay(leaves, node_axis):
        out = list(leaves)
        for i, axis in node_axis.items():
            a = leaves[i]
            if a.shape[axis] != n_pad:
                continue
            a = jnp.moveaxis(a, axis, -1)
            if fold:
                a = a.reshape(a.shape[:-1] + (n_pad // _LANES, _LANES))
            out[i] = a
        return tuple(out)

    return lay(static, _STATIC_NODE_AXIS), lay(carry, _CARRY_NODE_AXIS)


def _wire_carry(carry):
    """A step-layout carry back in the wire's layout."""
    import jax.numpy as jnp

    out = list(carry)
    node_dims = carry[2].ndim
    for i, axis in _CARRY_NODE_AXIS.items():
        a = carry[i]
        if a.size:
            flat = a.reshape(a.shape[:a.ndim - node_dims] + (-1,))
            out[i] = jnp.moveaxis(flat, -1, axis)
    return tuple(out)


def _build_place_scan():
    import jax

    # x64 for the int64 score intermediates of the exact integer spec
    # (intscore.py). Parity mode carries int32 arrays and compares int64
    # score60s — bit-identical on every backend, including the real TPU.
    jax.config.update("jax_enable_x64", True)
    _enable_persistent_compile_cache()
    step = _make_step()

    @partial(jax.jit, static_argnames=("n_pad",))
    def place_scan(n_pad, static, init_carry, xs):
        import jax.lax as lax

        static, init_carry = _step_layout(static, init_carry, fold=True)
        carry, outs = lax.scan(
            lambda c, x: step(static, c, x), init_carry, xs)
        return _wire_carry(carry), outs

    return place_scan


def _build_forced_kernel():
    """Scan-free system-eval kernel: when every placement names a DISTINCT
    forced node (single-TG system jobs — one alloc per eligible node,
    system_sched.go:268-286) and the eval carries no evictions, spreads,
    affinities, reschedule penalties or distinct_property (the system
    encoder emits exactly this shape), the scan steps are independent
    given the initial carry: no step's placement touches another step's
    node, spread counts are inert, and the ring offset cannot change any
    output (each step has at most ONE candidate — selected whether it
    lands in the source or the backlog window). So the whole eval
    collapses to ONE vectorized pass over the placement axis — identical
    arithmetic to the scan step restricted to that shape, bit-identical
    outputs (asserted by tests/test_system_engine.py host-parity and the
    scan-equivalence fuzz), at O(1) dispatch instead of O(P) sequential
    steps."""
    import jax

    jax.config.update("jax_enable_x64", True)
    _enable_persistent_compile_cache()
    import jax.numpy as jnp

    from .intscore import FEAT_FEAS_BIT, unpack_feat_lane

    def forced_eval(static, carry, xs):
        (totals, reserved, asks, feat_packed, _aff_score,
         desired_counts, dh_job, dh_tg, _limits, _spread_vids,
         _spread_desired, _spread_weights, _spread_has_targets,
         _spread_active, _sum_spread_weights, n_real, e_ask,
         _dp_vids, _dp_limit, _dp_applies,
         _pre_res, _pre_prio, _pre_elig, _pre_mp, _pre_gid,
         _pre_evf) = static
        (used0, tg_counts0, job_counts0, _sc0, _se0, _off0, failed0,
         e_base0, _dpc0, _pre_alive0, _pre_rem0, _pre_counts0) = carry
        (tg_idx, _penalty_idx, _evict_node, _evict_res, _evict_tg,
         _limit_p, _sum_sw_p, _ev_factor, _rev_factor, forced_node) = xs

        fdt = totals.dtype
        int_mode = jnp.issubdtype(fdt, jnp.integer)
        i64 = jnp.int64
        j = forced_node[:, 0]                          # [P] node per step
        g = tg_idx                                     # [P] TG per step

        ask = asks[g]                                  # [P, D]
        used_j = used0[j]                              # [P, D]
        totals_j = totals[j]
        if reserved.shape[0]:
            util = used_j + reserved[j] + ask
        else:
            util = used_j + ask
        fits = jnp.all(util <= totals_j, axis=-1)

        jc = job_counts0[j]                            # [P]
        tgc = tg_counts0[g, j]                         # [P]
        dh_mask = jnp.where(
            dh_job[g],
            jc == 0,
            jnp.where(dh_tg[g], ~((tgc > 0) & (jc > 0)), True),
        )
        feasible = (
            unpack_feat_lane(feat_packed[g, j], FEAT_FEAS_BIT)
            & fits & dh_mask & (j >= 0) & (j < n_real)
            & ~failed0[g]
        )

        anti_present = tgc > 0
        if int_mode:
            from .intscore import (
                E27_BITS,
                E27_ONE,
                RECIP_BITS,
                TERM_BITS,
                binpack_q30,
            )

            e_sel = (e_base0[j].astype(i64) * e_ask[g, j].astype(i64)) \
                >> E27_BITS                            # [P, 2]
            fit = i64(20 * E27_ONE) - e_sel[:, 0] - e_sel[:, 1]
            fit = jnp.clip(fit, 0, 18 * E27_ONE)
            binpack = binpack_q30(fit)
            rsh = RECIP_BITS - TERM_BITS
            q_d = jnp.floor_divide(
                i64(1 << RECIP_BITS),
                jnp.maximum(desired_counts[g].astype(i64), 1),
            )
            anti = jnp.where(
                anti_present, -(((tgc.astype(i64) + 1) * q_d) >> rsh), 0
            )
            num_terms = 1 + anti_present.astype(jnp.int32)
            factor = jnp.floor_divide(60, num_terms).astype(i64)
            final = (binpack + anti) * factor
            score_zero = i64(0)
        else:
            node_cpu = totals_j[:, DIM_CPU] - reserved[j][:, DIM_CPU]
            node_mem = totals_j[:, DIM_MEM] - reserved[j][:, DIM_MEM]
            free_cpu = 1.0 - util[:, DIM_CPU] / jnp.maximum(node_cpu, 1e-9)
            free_mem = 1.0 - util[:, DIM_MEM] / jnp.maximum(node_mem, 1e-9)
            fitness = 20.0 - (jnp.power(10.0, free_cpu)
                              + jnp.power(10.0, free_mem))
            binpack = jnp.clip(fitness, 0.0, 18.0) / 18.0
            anti = jnp.where(
                anti_present,
                -(tgc.astype(fdt) + 1.0) / desired_counts[g].astype(fdt),
                0.0,
            )
            num_terms = 1.0 + anti_present.astype(fdt)
            final = (binpack + anti) / num_terms
            score_zero = jnp.asarray(0.0, fdt)

        chosen = jnp.where(feasible, j, -1).astype(jnp.int32)
        scores = jnp.where(feasible, final, score_zero)
        p = tg_idx.shape[0]
        # the forced fast path never encodes preemption -> empty column;
        # one candidate a step -> no rival
        return (chosen, scores, jnp.zeros(p, jnp.int32),
                jnp.zeros(p, bool), jnp.zeros((p, 0), jnp.int32),
                jnp.full(p, -1, jnp.int32))

    return jax.jit(forced_eval)


def _batched_scan_fn():
    """The eval-batched scan, not yet jitted (the one body both programs
    below compile): ``batched(static_b, carry_b, xs_b, p_real)`` runs ONE
    loop of the vmapped step, whose bound is the scalar ``max(p_real)``:
    the wave's longest eval and no further. ``p_real[b]`` is each eval's
    own step count; a shorter eval's steps from its count on are masked
    by index (``past_end``), so padded steps need no inert task group to
    point at. The predicate is unbatched by construction: a ``while`` with
    a batched one would put a ``select`` on every carry.

    A wave of ONE eval (a static shape: the leading axis is 1) runs the
    step itself, with no batch axis: under ``vmap`` at b = 1 the TPU tiles
    a ``[1, n_pad]`` node plane ``T(1,128)``, one sublane of eight in use
    and forty register-rows a pass over 5,120 nodes, where the bare
    ``[n_pad]`` vector is a full tile. Same step, same loop, same outputs:
    the axis comes off the inputs before the loop and goes back on after.

    The outputs are ``[b, p_pad, ...]`` buffers pre-filled with what a
    skipped step returns and written at the step's index; rows at or past
    the bound keep the fill, and no caller reads them."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    step = _make_step()
    vstep = jax.vmap(step)

    def batched(static_b, carry_b, xs_b, p_real):
        lone = p_real.shape[0] == 1
        if lone:
            static_b, carry_b, xs_t, p_real = jax.tree_util.tree_map(
                lambda a: a[0], (static_b, carry_b, tuple(xs_b), p_real))
            static_b, carry_b = _step_layout(static_b, carry_b, fold=True)
            run = step
        else:
            # step-major, as lax.scan lays its xs and ys out: one step's
            # row of every eval is one contiguous slice
            xs_t = tuple(jnp.moveaxis(a, 1, 0) for a in xs_b)
            static_b, carry_b = jax.vmap(
                partial(_step_layout, fold=False))(static_b, carry_b)
            run = vstep

        def at(i):
            return tuple(
                lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                for a in xs_t)

        p_pad = xs_t[0].shape[0]
        zero = jnp.int32(0)
        _, out_shapes = jax.eval_shape(
            run, static_b, carry_b, at(zero), zero >= p_real)
        # chosen, score, pulls, skipped, evict, rival of a skipped step
        fills = (-1, 0, 0, True, -1, -1)
        outs0 = tuple(
            jnp.full((p_pad,) + o.shape, fill, o.dtype)
            for o, fill in zip(out_shapes, fills))

        # pulls and rival, two int32 a step, are written as ONE int64 (a
        # buffer's update is a kernel of its own in every step) and parted
        # again after the loop
        def joined(outs):
            chosen, score, pulls, skipped, evict, rival = outs
            return (chosen, score, skipped, evict,
                    (rival.astype(jnp.int64) << 32) | pulls.astype(jnp.int64))

        def body(i, state):
            carry, outs = state
            carry, out = run(static_b, carry, at(i), i >= p_real)
            return carry, tuple(
                lax.dynamic_update_index_in_dim(buf, o, i, 0)
                for buf, o in zip(outs, joined(out)))

        bound = jnp.minimum(jnp.max(p_real), p_pad)
        carry, outs = lax.fori_loop(
            zero, bound, body, (carry_b, joined(outs0)))
        if lone:
            carry = jax.tree_util.tree_map(
                lambda a: a[None], _wire_carry(carry))
            outs = tuple(o[None] for o in outs)
        else:
            carry = jax.vmap(_wire_carry)(carry)
            outs = tuple(jnp.moveaxis(o, 0, 1) for o in outs)
        chosen, score, skipped, evict, both = outs
        return carry, (chosen, score, both.astype(jnp.int32), skipped, evict,
                       (both >> 32).astype(jnp.int32))

    return batched


def _build_batched_scan(in_shardings=None):
    """Eval-batched scan: vmap the per-eval scan over a leading batch axis.

    EVERYTHING is batched — node tables included — because concurrent evals
    see different snapshots, different datacenter-filtered node sets and
    different jobs. Each eval keeps the exact sequential parity semantics of
    the single scan; the batch axis is pure data parallelism over
    independent evaluations (the device analog of the reference's
    N-scheduler-workers-per-server, nomad/server.go:1307).

    ``in_shardings``: optional (static, carry, xs, p_real) NamedShardings
    (parallel.sharding.batched_scan_shardings) to shard the dispatch over
    an ("evals", "nodes") mesh. This entry takes the 48 stacked arrays and
    the evals' step counts one by one: the mesh path's. The unsharded
    batcher dispatches ``_build_wire_scan``'s program over the same body."""
    import jax

    jax.config.update("jax_enable_x64", True)
    _enable_persistent_compile_cache()
    batched = _batched_scan_fn()

    def body(static_b, carry_b, xs_b, p_real):
        return batched(static_b, carry_b, xs_b, p_real)

    if in_shardings is not None:
        return jax.jit(body, in_shardings=in_shardings)
    return jax.jit(body)


def _build_wire_scan():
    """The batched scan behind the wire layout (tpu/wire.py): the program
    takes one flat buffer per dtype, slices the 48 fields and the evals'
    step counts out of them by the layout (a static argument: one compile
    per layout), runs the same bounded loop, and returns ONE int32 array
    holding chosen, scores, pulls, skipped and evict in bit-exact lanes.
    The final carry, which no caller of the batcher reads, is not returned. Still jitted as ``body``:
    benchmark/harness/scan.py finds the scan by ``jit_body``."""
    import jax
    import jax.numpy as jnp

    from . import wire

    jax.config.update("jax_enable_x64", True)
    _enable_persistent_compile_cache()
    batched = _batched_scan_fn()

    def body(layout, *buffers):
        _carry, outs = batched(*wire.unpack(layout, buffers, jnp))
        return wire.pack_outputs(layout, *outs)

    return jax.jit(body, static_argnums=0)


class _ResourceAssigner:
    """Host-side port and device-instance assignment for scan-chosen
    placements — the discrete half of the capacity dims the device
    pre-checked. NetworkIndex/DeviceAllocator mirrors are built lazily
    per node: network- and device-free task groups (the C1M-common case)
    never pay the per-node alloc walk."""

    def __init__(self, ctx, nodes) -> None:
        self.ctx = ctx
        self.nodes = nodes
        self._net: Dict[int, NetworkIndex] = {}
        self._dev: Dict[int, object] = {}

    def net_index(self, idx: int) -> NetworkIndex:
        ni = self._net.get(idx)
        if ni is None:
            ni = NetworkIndex(deterministic=self.ctx.deterministic)
            ni.set_node(self.nodes[idx])
            ni.add_allocs(self.ctx.proposed_allocs(self.nodes[idx].id))
            self._net[idx] = ni
        return ni

    def dev_allocator(self, idx: int):
        da = self._dev.get(idx)
        if da is None:
            from ..scheduler.device import DeviceAllocator

            da = DeviceAllocator(self.ctx, self.nodes[idx])
            da.add_allocs(self.ctx.proposed_allocs(self.nodes[idx].id))
            self._dev[idx] = da
        return da

    def build(self, node_idx: int, tg):
        """(task_resources, shared_networks, ok) for placing ``tg`` on the
        node; ok=False on a port/device-instance collision the dense
        capacity model missed (rare — the plan applier would reject it)."""
        task_resources: Dict[str, AllocatedTaskResources] = {}
        shared_networks = []
        ok = True
        if tg.networks:
            ni = self.net_index(node_idx)
            offer, _err = ni.assign_network(tg.networks[0].copy())
            if offer is None:
                ok = False
            else:
                ni.add_reserved(offer)
                shared_networks = [offer]
        for task in tg.tasks:
            tr = AllocatedTaskResources(
                cpu_shares=task.resources.cpu, memory_mb=task.resources.memory_mb
            )
            if task.resources.networks:
                ni = self.net_index(node_idx)
                offer, _err = ni.assign_network(task.resources.networks[0].copy())
                if offer is None:
                    ok = False
                    break
                ni.add_reserved(offer)
                tr.networks = [offer]
            for req in task.resources.devices:
                da = self.dev_allocator(node_idx)
                offer, _aff, _err = da.assign_device(req)
                if offer is None:
                    ok = False
                    break
                da.add_reserved(offer)
                tr.devices.append(offer)
            if not ok:
                break
            task_resources[task.name] = tr
        return task_resources, shared_networks, ok


def _int_spec_gate_reason(table, tg_specs, job):
    """Magnitude gates keeping every int64 intermediate of the integer
    scoring spec exact (intscore.py module doc). None = all clear."""
    from .intscore import MAX_TOTAL_COUNT

    caps = table.totals[:, :2]
    node_c = caps - table.reserved[:, :2]
    if caps.size and (
        caps.max() > (1 << 24)
        or node_c.min() < 1
        or (table.reserved[:, :2] > 2 * node_c).any()
    ):
        return "int-spec cpu/mem magnitude gate"
    if table.totals.size and table.totals.max() > (1 << 28):
        return "int-spec capacity magnitude gate"
    if sum(g.count for g in job.task_groups) > MAX_TOTAL_COUNT:
        return "int-spec job count gate"
    if any(spec.ask.max(initial=0) > (1 << 28) for spec in tg_specs.values()):
        return "int-spec ask magnitude gate"
    return None


def _release_enc_claim(claim_cell: Dict[str, object]) -> None:
    """Release an owned single-flight encode claim: drop the claim Event
    from the enc_cache (if it is still the parked entry) and wake every
    waiter so one of them can re-claim. Idempotent — the success path
    pops "ev" when it publishes, making later calls no-ops."""
    ev = claim_cell.pop("ev", None)
    if ev is None:
        return
    cache = claim_cell.pop("cache", None)
    key = claim_cell.pop("key", None)
    if cache is not None and cache.get(key) is ev:
        cache.pop(key, None)
    ev.set()


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def _record_lone_dispatch(source: str, enc: "EncodedEval", p_pad: int,
                          t_stack: float, t_called: float) -> None:
    """The dispatch record (trace/lifecycle.on_dispatch) of a path that
    goes around the batcher — the forced kernel, the single scan — with
    the fields it has: one eval (the one whose stage is open on this
    thread), no gather, no fence between the kernel and the copy back
    (``t_ready`` stays None). Called once the outputs are numpy arrays.
    These paths keep their own padding (the device is told no ``p_real``):
    ``p_pad`` is what the device computed, the forced kernel's pow2 bucket
    in one pass or the single scan's ``enc.p`` steps, none of them padded."""
    from ..trace import lifecycle as _tlc

    t_host = _phases.now()
    eval_id = _tlc.current_eval()
    _tlc.on_dispatch(
        wave=_tlc.next_wave(), source=source,
        eval_ids=[eval_id] if eval_id is not None else [],
        b=1, b_pad=1, p_pad=p_pad, n_pad=enc.n_pad, steps=enc.p,
        n_steps=p_pad, padded_steps=p_pad, t_start=t_stack, t_stack=t_stack,
        t_called=t_called, t_host=t_host, t_handed=t_host,
    )


class TpuPlacementEngine:
    _shared: Optional["TpuPlacementEngine"] = None
    _atexit_registered = False

    def __init__(self) -> None:
        self._place_scan = None
        self._forced_kernel = None

    @classmethod
    def shared(cls) -> "TpuPlacementEngine":
        if cls._shared is None:
            cls._shared = TpuPlacementEngine()
            if not cls._atexit_registered:
                # deterministic teardown: interpreter exit with a
                # dispatcher or warm-compile thread still inside the
                # runtime segfaults (the multichip dryrun's rc 139);
                # atexit runs BEFORE daemon threads are killed
                import atexit

                atexit.register(cls.shutdown)
                cls._atexit_registered = True
        return cls._shared

    @classmethod
    def shutdown(cls) -> None:
        """Stop every live DeviceBatcher (dispatcher thread joined, warm
        compiles joined, parked workers released) and drop the shared
        engine's compiled-callable references. Idempotent; registered
        via atexit by shared() and callable explicitly by benches/tests
        that want the TPU stack quiesced inside their own lifetime."""
        from .batcher import shutdown_all

        shutdown_all()
        eng = cls._shared
        if eng is not None:
            eng._place_scan = None
            eng._forced_kernel = None

    def _scan_fn(self):
        if self._place_scan is None:
            self._place_scan = _build_place_scan()
        return self._place_scan

    def _forced_fn(self):
        if self._forced_kernel is None:
            self._forced_kernel = _build_forced_kernel()
        return self._forced_kernel

    def run_forced(self, enc: "EncodedEval"):
        """Run one all-distinct forced-node eval through the scan-free
        kernel (see _build_forced_kernel). The placement axis pads to a
        pow2 bucket so partial retries (plan-rejection re-evals with
        fewer placements) reuse the compiled executable: padded entries
        carry forced_node=-1, which the kernel maps to chosen=-1, and
        callers only read the first ``enc.p`` slots."""
        kernel = self._forced_fn()
        import jax.numpy as jnp

        p = enc.p
        p_pad = _round_up(max(p, 1))
        xs = enc.xs
        if p_pad != p:
            def padp(arr, fill):
                widths = ((0, p_pad - p),) + ((0, 0),) * (arr.ndim - 1)
                return np.pad(arr, widths, constant_values=fill)

            (tg_idx, penalty_idx, evict_node, evict_res, evict_tg,
             limit_p, sum_sw_p, ev_factor, rev_factor, forced_node) = xs
            xs = (
                padp(tg_idx, 0), padp(penalty_idx, -1),
                padp(evict_node, -1), padp(evict_res, 0),
                padp(evict_tg, -1), padp(limit_p, 0), padp(sum_sw_p, 0),
                padp(ev_factor, 0), padp(rev_factor, 0),
                padp(forced_node, -1),
            )
        static = tuple(jnp.asarray(a) for a in enc.static)
        init_carry = tuple(jnp.asarray(a) for a in enc.carry)
        xs = tuple(jnp.asarray(a) for a in xs)
        t_stack = _phases.now()
        with _phases.track("device"):
            chosen, scores, pulls, skipped, evict, rival = kernel(
                static, init_carry, xs)
            t_called = _phases.now()
            chosen = np.asarray(chosen)
        out = (
            chosen[:p], np.asarray(scores)[:p],
            np.asarray(pulls)[:p], np.asarray(skipped)[:p],
            np.asarray(evict)[:p], np.asarray(rival)[:p],
        )
        _record_lone_dispatch("forced", enc, p_pad, t_stack, t_called)
        return out

    # ------------------------------------------------------------------

    def select(self, sched, tg, select_options):
        """Single-select path: not used — batching happens at
        compute_placements; always defer to the host stack."""
        return NotImplemented

    def compute_placements(self, sched, destructive: List, place: List):
        """Batch the eval's whole placement list through one device scan.

        Returns True when handled; NotImplemented to fall back to the host
        iterator path (unsupported features). When the scheduler's planner
        carries a ``device_batcher`` (the production server does —
        server.go:1307's N-workers analog), the encoded eval is submitted
        there so concurrent evals share ONE eval-batched device dispatch;
        otherwise it runs as a single-eval scan.
        """
        from ..utils import metrics as _metrics

        from ..trace import lifecycle as _tlc
        from .referee import referee

        wave_id = sched.eval.id
        batcher = getattr(sched.planner, "device_batcher", None)
        # Demand announcement: the batcher holds a gather while announced
        # evals are en route and closes it the moment none is, so every
        # eval bound for it is announced ahead of its arrival (the r05
        # wave-fragmentation bug: 328 evals over 21 dispatches against a
        # 64 cap). The server's worker announces before its snapshot and
        # this function takes that token over HERE, ahead of every way
        # out, so the finally / run(expected=True) below release it
        # before any host-stack placement starts; a planner without one
        # (harness and test planners, a scheduler's second attempt) has
        # it announced before encode.
        take = getattr(sched.planner, "take_announcement", None)
        expected_held = batcher is not None and take is not None and take()
        try:
            # Small evals don't amortize a device dispatch: the host stack
            # places them in low milliseconds, exactly like the reference's
            # per-placement iterators (generic_sched.go:426). Threshold 0 =
            # always use the device (the parity harness's frame); the
            # production server sets it.
            n_min = getattr(sched, "device_min_placements", 0)
            total = len(destructive) + len(place)
            if n_min and total < n_min:
                # Warm-bucket retry ride-along: a partial OCC retry (the
                # tail of a plan-rejected eval) is a few placements of a
                # job shape whose compile bucket is ALREADY warm from the
                # first pass — it rides that very program, and the
                # device's loop runs its own few steps and no padded one,
                # while the host stack re-walks the ranking iterators per
                # placement (5.7 s a placement for a stanza job at 5,000
                # nodes). The host keeps only what arrives before the
                # batcher has completed a batch.
                if batcher is None or not batcher.has_warmed():
                    _metrics.incr_counter("nomad.tpu_engine.small_eval_host")
                    return NotImplemented
                _metrics.incr_counter(
                    "nomad.tpu_engine.small_eval_device_retry")
            if batcher is not None and not expected_held:
                batcher.expect()
                expected_held = True
            t0 = _metrics.now()
            with _HOST_WORK_SEM:
                t1 = _metrics.now()
                with _tlc.stage("encode", wave_id):
                    enc = self.encode_eval(sched, destructive, place)
                _metrics.measure_since("nomad.tpu_engine.encode_work", t1)
            _metrics.measure_since("nomad.tpu_engine.encode", t0)
            if enc is NotImplemented:
                return NotImplemented
            if enc is True:
                return True
            self._pipeline_remember(sched, enc)
            t0 = _metrics.now()
            try:
                with _tlc.stage("device_wait", wave_id):
                    if batcher is not None:
                        expected_held = False  # run() consumes the token
                        outs = batcher.run(enc, expected=True)
                        # a worker with a backlog is en route again from
                        # here: to its next eval's arrival
                        again = getattr(sched.planner, "announce_next", None)
                        if again is not None:
                            again()
                    else:
                        outs = self.run_scan_single(enc)
                outs = referee(
                    enc, sched.job, sched.ctx, outs,
                    batcher.run if batcher is not None
                    else self.run_scan_single, wave_id)
                chosen, scores, pulls, skipped_steps, evict = outs[:5]
            except Exception:  # noqa: BLE001 — device dispatch failed
                # A failed/poisoned device round trip must not fail the eval:
                # the host iterator stack computes the identical placements
                # (bit-parity contract), so degrade this eval to the host
                # path and let the caller's fall-through handle it.
                logger.warning("device dispatch failed for %s; host fallback",
                               wave_id[:8], exc_info=True)
                _metrics.incr_counter("nomad.tpu_engine.dispatch_fallback_host")
                self._pipeline_forget(sched)
                return NotImplemented
        finally:
            if expected_held:
                batcher.cancel_expected()
        _metrics.measure_since("nomad.tpu_engine.device_wait", t0)
        t0 = _metrics.now()
        with _HOST_WORK_SEM:
            t1 = _metrics.now()
            with _tlc.stage("apply", wave_id):
                chosen = np.asarray(chosen)
                skipped_steps = np.asarray(skipped_steps)
                evict = np.asarray(evict)
                if enc.dense_ok and (chosen >= 0).all() and not skipped_steps.any():
                    # every placement succeeded and qualifies: results stay
                    # dense (no per-alloc objects) all the way to the FSM
                    self._apply_results_dense(sched, enc, chosen, scores, pulls,
                                              evict)
                else:
                    self._apply_results(
                        sched, enc.missing_list, enc.nodes, enc.table, chosen,
                        scores, pulls, skipped_steps, enc.start_ns,
                        enc=enc, evict=evict,
                    )
            _metrics.measure_since("nomad.tpu_engine.apply_work", t1)
        _metrics.measure_since("nomad.tpu_engine.apply", t0)
        return True

    def encode_eval(self, sched, destructive: List, place: List):
        """Encode one eval's placement problem into dense numpy arrays.

        Returns an EncodedEval, True (nothing to place) or NotImplemented
        (unsupported feature — host fallback).

        try/finally wrapper: the impl may claim a single-flight encode
        slot (an Event parked in the fleet's enc_cache). Success and the
        UnsupportedByEngine fallbacks release it themselves, but an
        UNEXPECTED exception must too — an abandoned claim stalls every
        same-key eval for the full 10s waiter grace period, each holding
        a HOST_WORK_SEM slot while it waits."""
        claim_cell: Dict[str, object] = {}
        try:
            return self._encode_eval_impl(sched, destructive, place, claim_cell)
        finally:
            # no-op when the claim was already published or released
            _release_enc_claim(claim_cell)

    def _encode_eval_impl(self, sched, destructive: List, place: List,
                          claim_cell: Dict[str, object]):
        job = sched.job
        ctx = sched.ctx
        nodes = list(sched.stack.source.nodes)  # order set by stack.set_nodes
        n_real = len(nodes)

        missing_list = list(destructive) + list(place)
        if not missing_list:
            return True

        from ..utils import metrics as _metrics

        # single-flight claim state (see the enc_cache block below): any
        # exit path that abandons an owned claim must release it, or
        # same-key waiters stall out their grace period — encode_eval's
        # finally covers the unexpected-exception paths

        def fallback(reason: str):
            logger.debug("tpu engine fallback: %s", reason)
            _metrics.incr_counter("nomad.tpu_engine.fallback")
            _release_enc_claim(claim_cell)
            return NotImplemented

        # Sticky-disk preferred nodes use a different two-phase select; punt.
        # Simultaneously decide dense-path eligibility: every placement
        # fresh (no previous alloc), no canaries, and its TG free of
        # network/device asks — then results stay as arrays through plan
        # submit -> plan apply -> FSM (structs.DenseTGPlacements).
        dense_ok = not sched.eval.annotate_plan
        _dense_tg_cache: Dict[str, bool] = {}
        for missing in missing_list:
            prev = missing.get_previous_allocation()
            tg = missing.get_task_group()
            if prev is not None and tg.ephemeral_disk.sticky:
                return fallback("sticky ephemeral disk")
            if dense_ok:
                if prev is not None or missing.is_canary():
                    dense_ok = False
                    continue
                tg_ok = _dense_tg_cache.get(tg.name)
                if tg_ok is None:
                    tg_ok = not tg.networks and not any(
                        t.resources.networks or t.resources.devices
                        for t in tg.tasks
                    )
                    _dense_tg_cache[tg.name] = tg_ok
                dense_ok = tg_ok

        # Build TG specs (may refuse). The per-node NetworkIndex cache is
        # shared across this eval's TGs (port-feasibility masks); the
        # fleet-static cache (encode.fleet_static) shares totals/index/
        # class-group arrays across every eval between node writes.
        from .encode import fleet_static, job_sched_signature

        fleet = fleet_static(ctx, job, nodes)

        # Device-side preemption (tpu/preempt.py): does this eval's host
        # oracle preempt? Config-gated per job type — the SAME switch the
        # host stack consults (generic_sched.get_select_options), so the
        # two paths can never disagree on whether the eval may evict.
        from ..scheduler.preemption import preemption_enabled

        _, _sched_cfg = ctx.state.scheduler_config()
        preempt_on = preemption_enabled(_sched_cfg, job.type)

        # Whole-eval encode cache: a burst of
        # same-shaped fresh jobs (the C1M workload — hundreds of
        # identical service jobs) re-derives identical arrays per eval,
        # and that re-derivation is the dominant GIL-serialized phase.
        # When every per-eval input is provably default — all placements
        # fresh (dense_ok), empty plan, clean shared spread/limit state,
        # no existing allocs of this job — the encoded arrays depend
        # only on (job content, fleet, usage state); reuse them
        # wholesale, swapping the per-eval ring offset and host context.
        # Extends the reference's per-class eligibility memoization
        # (scheduler/context.go:191) to the whole encoding.
        enc_cache = None
        cache_key = None
        if fleet is not None and dense_ok and not destructive and not preempt_on:
            plan = ctx.plan
            spread_state = sched.stack.spread
            if (
                not plan.node_allocation and not plan.node_update
                and not plan.node_preemptions
                and not spread_state.tg_spread_info
                and float(spread_state.sum_spread_weights) == 0.0
                and not ctx.state.job_has_live_allocs(job.id)
            ):
                enc_cache = fleet.setdefault("enc_cache", {})
                # NOTE: the usage epoch is NOT part of the key — entries
                # store (epoch, enc), and a stale-epoch hit is PATCHED
                # in place of a full re-encode: for jobs satisfying the
                # preconditions above, the only epoch-dependent arrays
                # are the job-independent used0/e_base0 pair
                # (encode.epoch_usage_arrays). Without this, every
                # commit wave of a C1M ingest invalidated the whole
                # cache and the re-encode storm became the dominant
                # host phase.
                cache_key = (
                    job_sched_signature(job),
                    len(missing_list),
                    tuple(m.get_task_group().name for m in missing_list),
                )
                cur_epoch = getattr(ctx.state, "usage_epoch", -1)
                # SINGLE-FLIGHT: a same-key burst (the C1M registration
                # storm — hundreds of evals of identically-shaped jobs
                # dequeued at one snapshot) must not thundering-herd the
                # encode. The first encoder claims the key with an Event
                # and builds; the rest wait for its published arrays
                # instead of re-deriving them concurrently (which made
                # the cache 0%-hit exactly when it mattered most).
                import threading as _threading

                while True:
                    hit = enc_cache.get(cache_key)
                    if hit is None:
                        claim = _threading.Event()
                        cur = enc_cache.setdefault(cache_key, claim)
                        if cur is claim:
                            claim_cell["ev"] = claim
                            claim_cell["cache"] = enc_cache
                            claim_cell["key"] = cache_key
                            break  # we build and publish
                        hit = cur
                    if isinstance(hit, _threading.Event):
                        _metrics.incr_counter(
                            "nomad.tpu_engine.encode_cache_wait")
                        if not hit.wait(timeout=10.0):
                            # owner wedged or died mid-encode: clear the
                            # stuck claim so the key heals, build our own.
                            # Wake the REST of the waiter cohort too —
                            # they re-read the cache now (and one
                            # re-claims) instead of each burning its own
                            # full grace period on the dead Event.
                            if enc_cache.get(cache_key) is hit:
                                enc_cache.pop(cache_key, None)
                            hit.set()
                            break
                        continue  # re-read the published entry
                    hit_epoch, hit = hit
                    num_dims = hit.static[0].shape[1]
                    if hit_epoch != cur_epoch:
                        if num_dims != 4:
                            # device-dim jobs carry usage on job-shaped
                            # dims; no shared patch — full re-encode
                            break
                        from .encode import epoch_usage_arrays

                        used0, e_base0 = epoch_usage_arrays(
                            ctx, fleet, hit.n_pad,
                            hit.dtype == np.int32, hit.dtype,
                        )
                        carry = list(hit.carry)
                        carry[0] = used0
                        carry[7] = e_base0
                        hit = EncodedEval(
                            n_real=hit.n_real, n_pad=hit.n_pad, g=hit.g,
                            s=hit.s, v=hit.v, p=hit.p, dtype=hit.dtype,
                            static=hit.static, carry=tuple(carry),
                            xs=hit.xs, missing_list=hit.missing_list,
                            nodes=hit.nodes, table=hit.table,
                            start_ns=hit.start_ns, dense_ok=True,
                        )
                        # re-publish at the current epoch: the rest of
                        # this wave's evals hit the pure-clone path
                        enc_cache[cache_key] = (cur_epoch, hit)
                        _metrics.incr_counter(
                            "nomad.tpu_engine.encode_cache_patch")
                    else:
                        _metrics.incr_counter(
                            "nomad.tpu_engine.encode_cache_hit")
                    _metrics.incr_counter("nomad.tpu_engine.handled")
                    offset0 = (
                        int(getattr(sched.stack.source, "offset", 0))
                        % max(n_real, 1)
                    )
                    carry = list(hit.carry)
                    carry[5] = np.int32(offset0)
                    return EncodedEval(
                        n_real=hit.n_real, n_pad=hit.n_pad, g=hit.g,
                        s=hit.s, v=hit.v, p=hit.p, dtype=hit.dtype,
                        static=hit.static, carry=tuple(carry), xs=hit.xs,
                        missing_list=missing_list, nodes=nodes,
                        table=hit.table, start_ns=_time.monotonic_ns(),
                        dense_ok=True,
                    )

        # The capacity model tracks one aggregate bandwidth dimension; the
        # host checks per NIC. Gate multi-NIC nodes to keep parity.
        for node in nodes:
            if len({net.device for net in node.node_resources.networks if net.device}) > 1:
                return fallback("multi-NIC node")
        tg_specs: Dict[str, TGSpec] = {}
        port_cache: Dict[str, object] = {}
        try:
            for missing in missing_list:
                tg = missing.get_task_group()
                if tg.name not in tg_specs:
                    tg_specs[tg.name] = build_tg_spec(
                        ctx, job, tg, nodes, sched.batch, port_cache,
                        fleet=fleet,
                    )
            table = build_node_table(ctx, job, nodes, fleet=fleet)
        except UnsupportedByEngine as e:
            return fallback(str(e))
        device_dims = job_device_dims(job)  # validated above; never raises here
        num_dims = table.totals.shape[1]    # 4 + the job's device dims
        start = _time.monotonic_ns()

        # Deterministic (parity) mode: the exact INTEGER spec of
        # intscore.py — int32 arrays, int64 score60 selection, bit-exact
        # on every backend including the real TPU. Non-deterministic:
        # float32 throughput mode.
        int_mode = bool(ctx.deterministic)
        fdtype = np.int32 if int_mode else np.float32
        if int_mode:
            reason = _int_spec_gate_reason(table, tg_specs, job)
            if reason is not None:
                return fallback(reason)

        pre_tables = None
        if preempt_on:
            # PARITY-CRITICAL: a preemption-enabled host oracle may evict
            # on ANY node, so encoding this eval WITHOUT the candidate
            # tables would diverge from it — every gate below fails the
            # WHOLE eval back to the host stack, never a partial encode.
            if not int_mode:
                return fallback("preemption requires deterministic int mode")
            if destructive:
                return fallback("preemption with destructive updates")
            if device_dims:
                # host oracle would run preempt_for_device (float scoring,
                # instance-level assignment state) — host-only
                return fallback("preemption with device asks")
            if any(
                tg.networks or any(t.resources.networks for t in tg.tasks)
                for tg in (m.get_task_group() for m in missing_list)
            ):
                # host oracle runs preempt_for_network first (reservable
                # port / MBits walk) — host-only
                return fallback("preemption with network asks")
            from .encode import build_preempt_tables

            pre_tables, reason = build_preempt_tables(ctx, job, nodes)
            if reason is not None:
                return fallback(reason)
        _metrics.incr_counter("nomad.tpu_engine.handled")

        n_pad = _round_up(max(n_real, 1))
        g_count = len(job.task_groups)
        specs_by_gi = {spec.index: spec for spec in tg_specs.values()}
        s_max = max((spec.spread_vids.shape[0] for spec in tg_specs.values()), default=0)
        v_max = max((spec.spread_desired.shape[1] for spec in tg_specs.values()), default=1)

        def pad_n(arr, fill=0.0):
            if arr.shape[-1] == n_pad:
                return arr
            pad_width = [(0, 0)] * (arr.ndim - 1) + [(0, n_pad - arr.shape[-1])]
            return np.pad(arr, pad_width, constant_values=fill)

        totals = np.zeros((n_pad, num_dims), fdtype)
        totals[:n_real] = table.totals
        reserved = np.zeros((n_pad, num_dims), fdtype)
        reserved[:n_real] = table.reserved
        used0 = np.zeros((n_pad, num_dims), fdtype)
        used0[:n_real] = table.used

        # Q27 incremental exponentials (int mode): e_base0 per node from
        # the encode-time chain; e_ask static ask factors per TG
        if int_mode:
            from .intscore import E27_ONE, e27_np, xq_np

            node_c2 = (totals[:, :2] - reserved[:, :2]).astype(np.int64)  # [N,2]
            free0 = node_c2 - used0[:, :2] - reserved[:, :2]
            e_base0 = e27_np(xq_np(free0, node_c2)).astype(np.int32)
        else:
            e_base0 = np.zeros((0, 2), np.int32)
        tg_counts0 = np.zeros((g_count, n_pad), np.int32)
        tg_counts0[:, :n_real] = table.tg_counts
        job_counts0 = np.zeros(n_pad, np.int32)
        job_counts0[:n_real] = table.job_counts

        asks = np.zeros((g_count, num_dims), fdtype)
        feas = np.zeros((g_count, n_pad), bool)
        aff_score = np.zeros((g_count, n_pad), fdtype)
        aff_present = np.zeros((g_count, n_pad), bool)
        desired_counts = np.ones(g_count, np.int32)
        dh_job = np.zeros(g_count, bool)
        dh_tg = np.zeros(g_count, bool)
        limits = np.full(g_count, 2, np.int32)
        sv = s_max  # 0 when no TG has spreads: the step's [S,V,N]
        # spread passes become zero-sized and XLA elides them
        vv = max(v_max, 2)
        spread_vids = np.full((g_count, sv, n_pad), vv - 1, np.int32)
        spread_desired = np.full((g_count, sv, vv), -1.0, fdtype)
        spread_weights = np.zeros((g_count, sv), fdtype)
        spread_has_targets = np.zeros((g_count, sv), bool)
        spread_active = np.zeros((g_count, sv), bool)
        sum_spread_weights = np.zeros(g_count, fdtype)
        spread_counts0 = np.zeros((g_count, sv, vv), fdtype)
        spread_entry0 = np.zeros((g_count, sv, vv), bool)

        if int_mode:
            e_ask = np.full((g_count, n_pad, 2), E27_ONE, np.int32)
        else:
            e_ask = np.zeros((0, 0, 2), np.int32)

        # e_ask rows depend only on (fleet capacities, the TG's cpu/mem
        # ask): cache them on the fleet entry — recurring TG shapes (the
        # C1M case: every job identical) skip the two e27 passes per eval
        e_ask_cache = None if fleet is None else fleet.setdefault("e_ask", {})
        for gi, spec in specs_by_gi.items():
            asks[gi] = spec.ask
            if int_mode:
                key = (n_pad, int(spec.ask[0]), int(spec.ask[1]))
                row = None if e_ask_cache is None else e_ask_cache.get(key)
                if row is None:
                    row = np.empty((n_pad, 2), np.int32)
                    for d in (0, 1):
                        row[:, d] = e27_np(
                            xq_np(np.full(n_pad, -int(spec.ask[d]), np.int64),
                                  node_c2[:, d])
                        ).astype(np.int32)
                    if e_ask_cache is not None and len(e_ask_cache) < 64:
                        e_ask_cache[key] = row
                e_ask[gi] = row
            feas[gi, :n_real] = spec.feasible
            aff_score[gi, :n_real] = spec.affinity_score
            aff_present[gi, :n_real] = spec.affinity_present
            desired_counts[gi] = max(spec.desired_count, 1)
            dh_job[gi] = spec.distinct_hosts_job
            dh_tg[gi] = spec.distinct_hosts_tg
            limits[gi] = min(spec.limit, 2**31 - 1)
            s = spec.spread_vids.shape[0]
            if s:
                v_spec = spec.spread_desired.shape[1]
                # remap this spec's invalid bucket onto the shared one (vv-1)
                spread_vids[gi, :s, :n_real] = np.where(
                    spec.spread_vids >= v_spec - 1, vv - 1, spec.spread_vids
                )
                spread_desired[gi, :s, :v_spec] = spec.spread_desired[:, :v_spec]
                spread_weights[gi, :s] = spec.spread_weights
                spread_has_targets[gi, :s] = spec.spread_has_targets
                spread_active[gi, :s] = True
                sum_spread_weights[gi] = spec.sum_spread_weights
                spread_counts0[gi, :s, : spec.spread_counts0.shape[1]] = spec.spread_counts0
                spread_entry0[gi, :s] = spread_counts0[gi, :s] > 0

        # per-placement inputs
        p = len(missing_list)
        tg_idx = np.zeros(p, np.int32)
        penalty_idx = np.full((p, MAX_PENALTY_NODES), -1, np.int32)
        evict_node = np.full(p, -1, np.int32)
        evict_res = np.zeros((p, num_dims), fdtype)
        evict_tg = np.full(p, -1, np.int32)
        limit_p = np.zeros(p, np.int32)
        sum_sw_p = np.zeros(p, fdtype)
        _e27one = 1
        if int_mode:
            from .intscore import E27_ONE as _e27one  # noqa: N811
        ev_factor = np.full((p, 2), _e27one, np.int32)
        rev_factor = np.full((p, 2), _e27one, np.int32)

        # Sticky limit widening + cross-TG spread-weight accumulation,
        # replicating the shared SpreadIterator/LimitIterator state in the
        # host stack (which inplace-update selects may have pre-seeded).
        widened = False
        running_sw = float(sched.stack.spread.sum_spread_weights)
        visited_tgs = set(sched.stack.spread.tg_spread_info.keys())

        tg_name_to_gi = {g.name: i for i, g in enumerate(job.task_groups)}
        for pi, missing in enumerate(missing_list):
            tg = missing.get_task_group()
            gi = tg_name_to_gi[tg.name]
            tg_idx[pi] = gi
            spec = specs_by_gi[gi]
            if tg.name not in visited_tgs:
                visited_tgs.add(tg.name)
                running_sw += float(spec.sum_spread_weights)
            if spec.widens:
                widened = True
            limit_p[pi] = 2**31 - 1 if widened else spec.limit
            sum_sw_p[pi] = running_sw
            prev = missing.get_previous_allocation()
            if prev is not None:
                from ..structs.structs import ALLOC_CLIENT_FAILED

                pens: Dict[str, None] = {}  # ordered de-dup (host uses a set)
                if prev.client_status == ALLOC_CLIENT_FAILED:
                    pens[prev.node_id] = None
                if prev.reschedule_tracker is not None:
                    for ev in prev.reschedule_tracker.events:
                        pens[ev.prev_node_id] = None
                for k, node_id in enumerate(list(pens)[:MAX_PENALTY_NODES]):
                    idx = table.node_index.get(node_id, -1)
                    penalty_idx[pi, k] = idx
            stop_prev, _ = missing.stop_previous_alloc()
            if stop_prev and prev is not None:
                idx = table.node_index.get(prev.node_id, -1)
                if idx >= 0:
                    evict_node[pi] = idx
                    cr = prev.comparable_resources()
                    evict_res[pi, DIM_CPU] = cr.flattened.cpu_shares
                    evict_res[pi, DIM_MEM] = cr.flattened.memory_mb
                    evict_res[pi, 2] = cr.shared.disk_mb
                    mb = 0
                    if prev.allocated_resources is not None:
                        for net in prev.allocated_resources.shared.networks:
                            mb += net.mbits
                        for tr in prev.allocated_resources.tasks.values():
                            for net in tr.networks:
                                mb += net.mbits
                        # devices the eviction frees, on the job's dims
                        if device_dims:
                            for tr in prev.allocated_resources.tasks.values():
                                for dev in tr.devices:
                                    for ask_id, dim in device_dims.items():
                                        if dev.id().matches(ask_id):
                                            evict_res[pi, dim] += len(dev.device_ids)
                                            break
                    evict_res[pi, DIM_MBITS] = mb
                    if prev.job_id == job.id:
                        evict_tg[pi] = tg_name_to_gi.get(prev.task_group, -1)
                    if int_mode:
                        # eviction/revert Q27 factors (evicted node known
                        # at encode time; spec: e27(±evict_res/cap))
                        from .intscore import e27_py, xq_py

                        for d in (0, 1):
                            er = int(evict_res[pi, d])
                            nc = int(node_c2[idx, d])
                            ev_factor[pi, d] = e27_py(xq_py(er, nc))
                            rev_factor[pi, d] = e27_py(xq_py(-er, nc))

        # shape specialization: absent features collapse to zero axes so
        # the step compiles without their ops (see _make_step)
        if not aff_present.any():
            aff_score = aff_score[:0]
            aff_present = aff_present[:0]
        # pack feasibility + affinity presence into ONE uint8 plane,
        # emitted once per eval — cached-encode re-dispatches reuse it
        from .intscore import pack_feat_planes

        feat_packed = pack_feat_planes(feas, aff_present)
        if (penalty_idx == -1).all():
            penalty_idx = penalty_idx[:, :0]
        if (evict_node == -1).all():
            # no destructive updates: the step's eviction/revert machinery
            # compiles away entirely
            evict_res = evict_res[:, :0]
            ev_factor = ev_factor[:, :0]
            rev_factor = rev_factor[:, :0]
        if int_mode:
            # fold reserved into totals: the E factors above were computed
            # from the split, and the fits check is identical on the netted
            # capacities — the step saves one [N, D] add per placement
            totals = totals - reserved
            reserved = np.zeros((0, num_dims), fdtype)

        # distinct_property encoding (zero-D when absent). Pad the node
        # axis: padded nodes keep the MISSING bucket (v-1) and are
        # infeasible anyway.
        try:
            dp_vids_r, dp_limit, dp_applies, dp_counts0 = (
                _distinct_property_arrays(ctx, job, nodes)
            )
        except UnsupportedByEngine as e:
            return fallback(str(e))
        if dp_vids_r.shape[0] and (evict_node >= 0).any():
            # in-eval evictions interact with the host PropertySet's
            # cleared-value refund quirk (propertyset.py:97-105: at most
            # one refund per distinct re-used value) — the scan's exact
            # counters would diverge; host fallback keeps plan parity
            return fallback("distinct_property with in-eval evictions")
        if dp_vids_r.shape[0] and pre_tables is not None:
            # same PropertySet refund quirk, via preempted allocs
            return fallback("distinct_property with preemption")
        d_dp = dp_vids_r.shape[0]
        v_dp = dp_counts0.shape[1] if d_dp else 1
        dp_vids = np.full((d_dp, n_pad), v_dp - 1, np.int32)
        if d_dp:
            dp_vids[:, :n_real] = dp_vids_r

        (pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf,
         pre_alive0, pre_remaining0, pre_counts0) = _pad_preempt_arrays(
            pre_tables, n_pad, n_real, node_c2 if int_mode else None)

        static = (
            totals, reserved, asks, feat_packed, aff_score,
            desired_counts, dh_job, dh_tg, limits, spread_vids, spread_desired,
            spread_weights, spread_has_targets, spread_active,
            sum_spread_weights, np.int32(n_real), e_ask,
            dp_vids, dp_limit, dp_applies,
            pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf,
        )
        # Ring start mirrors the host source iterator's offset as
        # set_nodes left it — 0 in the classic deterministic frame, the
        # per-eval seed when ring decorrelation is on
        # (EvalContext.ring_seed) — so host fallback and device scan walk
        # the same ring.
        offset0 = int(getattr(sched.stack.source, "offset", 0)) % max(n_real, 1)
        init_carry = (
            used0, tg_counts0, job_counts0, spread_counts0, spread_entry0,
            np.int32(offset0), np.zeros(g_count, bool), e_base0, dp_counts0,
            pre_alive0, pre_remaining0, pre_counts0,
        )
        xs = (
            tg_idx, penalty_idx, evict_node, evict_res, evict_tg,
            limit_p, sum_sw_p, ev_factor, rev_factor,
            # forced_node rides a WIDTH axis so unrestricted (generic)
            # evals compile the restriction away entirely
            np.zeros((p, 0), np.int32),
        )

        enc = EncodedEval(
            n_real=n_real, n_pad=n_pad, g=g_count, s=sv, v=vv, p=p,
            dtype=fdtype, static=static, carry=init_carry, xs=xs,
            missing_list=missing_list, nodes=nodes, table=table,
            start_ns=start, dense_ok=dense_ok,
            pre_allocs=(pre_tables.allocs if pre_tables is not None else None),
        )
        if enc_cache is not None and cache_key is not None:
            # arrays are read-only downstream (the batcher pads into
            # fresh buffers; apply only reads); a later hit swaps the
            # ring offset and host context (and usage arrays on an
            # epoch roll)
            if len(enc_cache) >= 32:
                # concurrent encoders (HOST_WORK_SEM admits several) may
                # race to evict the same oldest key — default-pop (an
                # evicted in-flight claim is re-published right below or
                # released by its owner's fallback path)
                enc_cache.pop(next(iter(enc_cache)), None)
            enc_cache[cache_key] = (cur_epoch, enc)
        ev = claim_cell.pop("ev", None)
        if ev is not None:
            ev.set()
        return enc

    def run_scan_single(self, enc: "EncodedEval"):
        """Run one encoded eval through the single-eval jit'd scan."""
        # Build the scan (enables x64) BEFORE converting arrays, or the
        # float64 inputs silently truncate to float32.
        place_scan = self._scan_fn()
        import jax.numpy as jnp

        static = tuple(jnp.asarray(a) for a in enc.static)
        init_carry = tuple(jnp.asarray(a) for a in enc.carry)
        xs = tuple(jnp.asarray(a) for a in enc.xs)

        t_stack = _phases.now()
        _carry, outs = place_scan(enc.n_pad, static, init_carry, xs)
        t_called = _phases.now()
        out = tuple(np.asarray(o) for o in outs)
        _record_lone_dispatch("single", enc, enc.p, t_stack, t_called)
        return out

    @staticmethod
    def _pipeline_remember(sched, enc: "EncodedEval") -> None:
        """Hand this wave's encode to the pipeline's re-dispatch registry
        (pipeline/redispatch.py) before the device dispatch: on a partial
        OCC commit, the async applier re-enters the device stage from the
        remembered encode (row-subset + usage-epoch patch) instead of
        re-running snapshot/encode. No-op outside the pipelined server."""
        pipe = getattr(sched.planner, "pipeline", None)
        if pipe is None:
            return
        try:
            pipe.remember_wave(
                sched.eval.id, enc, sched.job,
                getattr(sched.ctx.state, "node_epoch", -1),
            )
        except Exception:  # noqa: BLE001 — observability hook, never fatal
            logger.debug("pipeline remember_wave failed", exc_info=True)

    @staticmethod
    def _pipeline_forget(sched) -> None:
        """Drop a remembered encode when the wave degrades to the host
        path (failed device dispatch) — the registry entry would
        otherwise strand until the eval acks."""
        pipe = getattr(sched.planner, "pipeline", None)
        if pipe is None:
            return
        try:
            pipe.registry.forget(sched.eval.id)
        except Exception:  # noqa: BLE001
            logger.debug("pipeline forget failed", exc_info=True)

    # ------------------------------------------------------------------
    # System scheduler path: one alloc per ELIGIBLE node — each placement
    # names its node up front (system_sched.go:268-286), so the dense pass
    # is the same scan with a per-placement forced_node restriction and no
    # spread/affinity/limit machinery (SystemStack has none, stack.go:166).
    # ------------------------------------------------------------------

    def compute_system_placements(self, sched, place: List, sched_config=None,
                                  _preempt_pass: bool = False):
        """Batch a SystemScheduler eval's placements through one device
        scan. Returns True when fully handled, a non-empty list of
        leftover placement tuples when the device handled everything
        except nodes that need preemption (the caller runs its host
        per-node loop over just that subset), or NotImplemented to fall
        back to the host stack wholesale (which is semantically
        complete). ``sched_config`` is the SchedulerConfiguration the
        caller already read when choosing this path.
        """
        if not place:
            return True

        job = sched.job
        ctx = sched.ctx
        nodes = list(sched.nodes)
        n_real = len(nodes)

        from ..utils import metrics as _metrics

        def fallback(reason: str):
            logger.debug("tpu system engine fallback: %s", reason)
            _metrics.incr_counter("nomad.tpu_engine.fallback")
            return NotImplemented

        for node in nodes:
            if len({net.device for net in node.node_resources.networks if net.device}) > 1:
                return fallback("multi-NIC node")

        from ..trace import lifecycle as _tlc

        tg_specs: Dict[str, TGSpec] = {}
        port_cache: Dict[str, object] = {}
        try:
            with _tlc.stage("encode", sched.eval.id):
                for tup in place:
                    tg = tup.task_group
                    if tg.name not in tg_specs:
                        tg_specs[tg.name] = build_tg_spec(
                            ctx, job, tg, nodes, False, port_cache)
                table = build_node_table(ctx, job, nodes)
        except UnsupportedByEngine as e:
            return fallback(str(e))
        int_mode = bool(ctx.deterministic)
        if int_mode:
            reason = _int_spec_gate_reason(table, tg_specs, job)
            if reason is not None:
                return fallback(reason)
        num_dims = table.totals.shape[1]
        start = _time.monotonic_ns()
        fdtype = np.int32 if int_mode else np.float32

        pre_tables = None
        if _preempt_pass:
            # Second device pass over capacity-failed forced nodes: encode
            # WITH the preemption candidate tables. Any gate failure hands
            # the SUBSET to the host per-node loop (list return), never
            # the whole eval — pass-1 results are already applied.
            def subset_to_host(reason: str):
                fallback(f"system preempt pass: {reason}")
                return list(place)

            if not int_mode:
                return subset_to_host("preemption requires deterministic int mode")
            if num_dims != 4:
                # preempt_for_device is host-only
                return subset_to_host("preemption with device asks")
            if any(
                tup.task_group.networks
                or any(t.resources.networks for t in tup.task_group.tasks)
                for tup in place
            ):
                # preempt_for_network is host-only
                return subset_to_host("preemption with network asks")
            from .encode import build_preempt_tables

            pre_tables, _pre_reason = build_preempt_tables(ctx, job, nodes)
            if _pre_reason is not None:
                return subset_to_host(_pre_reason)

        n_pad = _round_up(max(n_real, 1))
        g_count = len(job.task_groups)
        specs_by_gi = {spec.index: spec for spec in tg_specs.values()}

        totals = np.zeros((n_pad, num_dims), fdtype)
        totals[:n_real] = table.totals
        reserved = np.zeros((n_pad, num_dims), fdtype)
        reserved[:n_real] = table.reserved
        used0 = np.zeros((n_pad, num_dims), fdtype)
        used0[:n_real] = table.used
        tg_counts0 = np.zeros((g_count, n_pad), np.int32)
        tg_counts0[:, :n_real] = table.tg_counts
        job_counts0 = np.zeros(n_pad, np.int32)
        job_counts0[:n_real] = table.job_counts

        if int_mode:
            from .intscore import E27_ONE, e27_np, xq_np

            node_c2 = (totals[:, :2] - reserved[:, :2]).astype(np.int64)
            free0 = node_c2 - used0[:, :2] - reserved[:, :2]
            e_base0 = e27_np(xq_np(free0, node_c2)).astype(np.int32)
            e_ask = np.full((g_count, n_pad, 2), E27_ONE, np.int32)
        else:
            e_base0 = np.zeros((0, 2), np.int32)
            e_ask = np.zeros((0, 0, 2), np.int32)

        asks = np.zeros((g_count, num_dims), fdtype)
        feas = np.zeros((g_count, n_pad), bool)
        for gi, spec in specs_by_gi.items():
            asks[gi] = spec.ask
            feas[gi, :n_real] = spec.feasible
            if int_mode:
                for d in (0, 1):
                    e_ask[gi, :, d] = e27_np(
                        xq_np(np.full(n_pad, -int(spec.ask[d]), np.int64),
                              node_c2[:, d])
                    ).astype(np.int32)

        # SystemStack has no spread/affinity/limit/anti-affinity iterators:
        # encode them inert (zero/absent) so those score terms vanish.
        # (the packed feature plane's affinity lane stays zero)
        from .intscore import pack_feat_planes

        feat_packed = pack_feat_planes(feas)
        aff_score = np.zeros((0, n_pad), np.int64 if int_mode else fdtype)
        desired_counts = np.ones(g_count, np.int32)
        dh_job = np.zeros(g_count, bool)
        dh_tg = np.zeros(g_count, bool)
        limits = np.ones(g_count, np.int32)
        spread_vids = np.full((g_count, 1, n_pad), 1, np.int32)
        spread_desired = np.full((g_count, 1, 2), -1, fdtype)
        spread_weights = np.zeros((g_count, 1), fdtype)
        spread_has_targets = np.zeros((g_count, 1), bool)
        spread_active = np.zeros((g_count, 1), bool)
        sum_spread_weights = np.zeros(g_count, fdtype)
        spread_counts0 = np.zeros((g_count, 1, 2), fdtype)
        spread_entry0 = np.zeros((g_count, 1, 2), bool)

        p = len(place)
        tg_name_to_gi = {g.name: i for i, g in enumerate(job.task_groups)}
        tg_idx = np.zeros(p, np.int32)
        forced = np.zeros(p, np.int32)
        for pi, tup in enumerate(place):
            tg_idx[pi] = tg_name_to_gi[tup.task_group.name]
            forced[pi] = table.node_index.get(tup.alloc.node_id, -1)
        if (forced < 0).any():
            return fallback("system placement on unknown node")

        from ..structs.structs import CONSTRAINT_DISTINCT_PROPERTY

        has_dp = any(
            c.operand == CONSTRAINT_DISTINCT_PROPERTY
            for c in list(job.constraints)
            + [c for tg in job.task_groups for c in tg.constraints]
        )
        if has_dp:
            # host DistinctPropertyIterator counts DP blocks as FILTERED
            # (not exhausted); the dense pass can't split that per forced
            # node without replaying counts — host fallback keeps the
            # bookkeeping identical. (The generic path vectorizes DP.)
            return fallback("system distinct_property")
        dp_vids = np.zeros((0, n_pad), np.int32)
        dp_limit = np.zeros(0, np.int32)
        dp_applies = np.zeros((g_count, 0), bool)
        dp_counts0 = np.zeros((0, 1), np.int32)

        if int_mode:
            # fold reserved into totals (see encode_eval): e factors were
            # computed from the split above
            totals = totals - reserved
            reserved = np.zeros((0, num_dims), fdtype)

        (pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf,
         pre_alive0, pre_remaining0, pre_counts0) = _pad_preempt_arrays(
            pre_tables, n_pad, n_real, node_c2 if int_mode else None)

        static = (
            totals, reserved, asks, feat_packed, aff_score,
            desired_counts, dh_job, dh_tg, limits, spread_vids, spread_desired,
            spread_weights, spread_has_targets, spread_active,
            sum_spread_weights, np.int32(n_real), e_ask,
            dp_vids, dp_limit, dp_applies,
            pre_res, pre_prio, pre_elig, pre_mp, pre_gid, pre_evf,
        )
        init_carry = (
            used0, tg_counts0, job_counts0, spread_counts0, spread_entry0,
            np.int32(0), np.zeros(g_count, bool), e_base0, dp_counts0,
            pre_alive0, pre_remaining0, pre_counts0,
        )
        xs = (
            tg_idx,
            np.full((p, 0), -1, np.int32),       # no reschedule penalties
            np.full(p, -1, np.int32),            # no evictions
            np.zeros((p, 0), fdtype),
            np.full(p, -1, np.int32),
            np.ones(p, np.int32),                # limit: the single node
            np.zeros(p, fdtype),
            np.zeros((p, 0), np.int32),
            np.zeros((p, 0), np.int32),
            forced.reshape(p, 1),
        )
        enc = EncodedEval(
            n_real=n_real, n_pad=n_pad, g=g_count, s=1, v=2, p=p,
            dtype=fdtype, static=static, carry=init_carry, xs=xs,
            missing_list=list(place), nodes=nodes, table=table,
            start_ns=start,
            pre_allocs=(pre_tables.allocs if pre_tables is not None else None),
        )

        # All-distinct forced nodes (single-TG system jobs): the scan-free
        # vectorized kernel — O(1) dispatch instead of O(P) scan steps.
        # Duplicated forced nodes (multi-TG system jobs placing several
        # allocs on one node) interact through used/tg_counts and keep
        # the sequential scan.
        batcher = getattr(sched.planner, "device_batcher", None)
        with _tlc.stage("device_wait", sched.eval.id):
            if len(set(forced.tolist())) == p and pre_tables is None:
                # (the forced fast path never encodes preemption — a preempt
                # pass always takes the sequential scan below)
                chosen, scores, pulls, skipped, evict = self.run_forced(enc)[:5]
                if batcher is not None:
                    # the forced kernel bypasses the gather queue; count it in
                    # the batcher's stats so dispatch accounting stays whole.
                    # This read-modify-write runs on scheduler worker threads
                    # concurrently with the dispatcher thread's own updates —
                    # both sides take the batcher's lock (guarded-by _lock).
                    with batcher._lock:
                        batcher.stats["dispatches"] = batcher.stats.get("dispatches", 0) + 1
                        batcher.stats["evals"] = batcher.stats.get("evals", 0) + 1
            elif batcher is not None:
                chosen, scores, pulls, skipped, evict = batcher.run(enc)[:5]
            else:
                chosen, scores, pulls, skipped, evict = self.run_scan_single(enc)[:5]

        # Preemption is a host-side greedy search per node. When enabled
        # and a forced node failed on CAPACITY (feasible by constraints
        # but no fit — port occupancy included: the host preempts port
        # holders), the device results are KEPT for every other placement
        # and only the capacity-failed subset is handed back to the host
        # per-node stack (rank.py BinPackIterator with evict=True), which
        # runs the Preemptor with vectorized distance scoring
        # (scheduler/preemption.py). Constraint-filtered nodes never
        # preempt, so they stay on the device path. The host processes
        # the leftover subset in placement order — the same order the
        # pure-host loop would visit those nodes — so preemption-count
        # penalties (max_parallel) accumulate identically.
        preemption_on = True
        if sched_config is not None:
            preemption_on = sched_config.preemption_config.system_scheduler_enabled
        leftover: List = []
        if preemption_on and not _preempt_pass:
            chosen = np.asarray(chosen)
            keep: List[int] = []
            for pi, tup in enumerate(place):
                if int(chosen[pi]) < 0:
                    spec = tg_specs[tup.task_group.name]
                    idx = int(forced[pi])
                    if idx < n_real and spec.constraint_feasible[idx]:
                        leftover.append(tup)
                        continue
                keep.append(pi)
            if leftover:
                place = [place[k] for k in keep]
                kp = np.asarray(keep, np.int64)
                chosen = np.asarray(chosen)[kp]
                scores = np.asarray(scores)[kp]
                evict = np.asarray(evict)[kp]

        if not _preempt_pass:
            _metrics.incr_counter("nomad.tpu_engine.handled")
        self._apply_system_results(
            sched, place, nodes, table, tg_specs, chosen, scores, start,
            enc=enc, evict=np.asarray(evict),
        )
        if not leftover:
            return True
        # Second device pass: re-encode JUST the capacity-failed forced
        # nodes with the preemption candidate tables (tpu/preempt.py), so
        # preempting system evals never leave the TPU path. Pass-1
        # results are already applied above, so the re-encode sees the
        # same proposed plan state the host per-node loop would. A pass-2
        # gate failure returns the subset for the host loop instead —
        # never NotImplemented (pass 1 is committed).
        _metrics.incr_counter("nomad.tpu_engine.system_preempt_pass")
        res = self.compute_system_placements(
            sched, leftover, sched_config, _preempt_pass=True)
        if res is NotImplemented:
            return leftover
        return res

    def _apply_system_results(self, sched, place, nodes, table, tg_specs,
                              chosen, scores, start_ns, enc=None,
                              evict=None) -> None:
        """Materialize system-scan results: allocs for fits, queued-alloc
        bookkeeping for constraint-filtered nodes, failed metrics +
        per-node blocked evals for capacity failures (system_sched.py host
        path semantics). The all-clean case (every node placed, fresh,
        no network/device asks) takes the dense block path — one-per-node
        system jobs are exactly the shape that benefits."""
        from ..structs.structs import AllocMetric

        job = sched.job
        ctx = sched.ctx

        chosen = np.asarray(chosen)
        if (
            not getattr(sched.eval, "annotate_plan", False)
            and len(place)
            and (chosen[: len(place)] >= 0).all()
            and all(
                (tup.alloc is None or not tup.alloc.id)
                and not tup.task_group.networks
                and not any(
                    t.resources.networks or t.resources.devices
                    for t in tup.task_group.tasks
                )
                for tup in place
            )
        ):
            self._apply_system_results_dense(
                sched, place, nodes, chosen, scores, start_ns,
                enc=enc, evict=evict,
            )
            return

        assigner = _ResourceAssigner(ctx, nodes)

        for pi, tup in enumerate(place):
            tg = tup.task_group
            node_idx = int(chosen[pi])

            if node_idx < 0:
                idx = table.node_index.get(tup.alloc.node_id, -1)
                spec = tg_specs[tg.name]
                if idx < 0 or not spec.constraint_feasible[idx]:
                    # constraint mismatch: the node just isn't in the
                    # job's domain — not a failure. (Port-OCCUPIED nodes
                    # are NOT this case: they're exhausted below, like
                    # the host's rank-phase port exhaustion.)
                    sched.queued_allocs[tg.name] -= 1
                    if (
                        sched.eval.annotate_plan
                        and sched.plan.annotations is not None
                        and tg.name in sched.plan.annotations.desired_tg_updates
                    ):
                        sched.plan.annotations.desired_tg_updates[tg.name].place -= 1
                    continue
                if sched.failed_tg_allocs and tg.name in sched.failed_tg_allocs:
                    sched.failed_tg_allocs[tg.name].coalesced_failures += 1
                    continue
                metrics = AllocMetric()
                metrics.nodes_evaluated = 1
                metrics.nodes_exhausted = 1
                metrics.nodes_available = sched.nodes_by_dc
                if sched.failed_tg_allocs is None:
                    sched.failed_tg_allocs = {}
                sched.failed_tg_allocs[tg.name] = metrics
                sched._add_blocked(nodes[idx])
                continue

            node = nodes[node_idx]
            task_resources, shared_networks, ok = assigner.build(node_idx, tg)
            if not ok:
                if sched.failed_tg_allocs and tg.name in sched.failed_tg_allocs:
                    sched.failed_tg_allocs[tg.name].coalesced_failures += 1
                    continue
                if sched.failed_tg_allocs is None:
                    sched.failed_tg_allocs = {}
                metrics = AllocMetric()
                metrics.nodes_evaluated = 1
                metrics.nodes_exhausted = 1
                metrics.nodes_available = sched.nodes_by_dc
                sched.failed_tg_allocs[tg.name] = metrics
                sched._add_blocked(node)
                continue

            metrics = AllocMetric()
            metrics.nodes_evaluated = 1
            metrics.nodes_available = sched.nodes_by_dc
            if scores.dtype.kind == "i":
                from .intscore import score60_to_float

                score_f = score60_to_float(scores[pi])
            else:
                score_f = float(scores[pi])
            metrics.score_node(node, "binpack", score_f)
            metrics.score_node(node, "normalized-score", score_f)
            metrics.populate_score_meta_data()

            resources = AllocatedResources(
                tasks=task_resources,
                shared=AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb, networks=shared_networks
                ),
            )
            alloc = Allocation(
                namespace=job.namespace,
                eval_id=sched.eval.id,
                name=tup.name,
                job_id=job.id,
                task_group=tg.name,
                metrics=metrics,
                node_id=node.id,
                node_name=node.name,
                allocated_resources=resources,
                desired_status=ALLOC_DESIRED_RUN,
                client_status=ALLOC_CLIENT_PENDING,
            )
            if tup.alloc is not None and tup.alloc.id:
                alloc.previous_allocation = tup.alloc.id
            if (
                evict is not None and evict.ndim == 2 and evict.shape[1]
                and enc is not None and enc.pre_allocs is not None
            ):
                row = evict[pi]
                ks = sorted(
                    (c for c in range(row.shape[0]) if int(row[c]) >= 0),
                    key=lambda c: int(row[c]),
                )
                if ks:
                    cand = enc.pre_allocs[node_idx]
                    stops = [cand[c] for c in ks]
                    for stop in stops:
                        sched.plan.append_preempted_alloc(stop, alloc.id)
                    alloc.preempted_allocations = [s.id for s in stops]
            sched.plan.append_alloc(alloc)

        ctx.metrics.allocation_time_ns = _time.monotonic_ns() - start_ns

    @staticmethod
    def _scores_to_float(scores) -> np.ndarray:
        """Display-float conversion (int mode carries score60s)."""
        if scores.dtype.kind == "i":
            from .intscore import TERM_ONE

            return np.asarray(scores, np.float64) / (60.0 * TERM_ONE)
        return np.asarray(scores, np.float64)

    @staticmethod
    def _dense_block(job, tg, eval_id, node_idxs, nodes, names, scores_f,
                     nodes_evaluated, nodes_available, deployment_id=""):
        """One DenseTGPlacements block for a task group's placements —
        shared by the generic and system dense paths. The dense gate
        guarantees no network/device asks, so one AllocatedResources
        prototype covers every slot and ask_vec's mbits is 0."""
        from ..structs.structs import DenseTGPlacements, generate_uuids

        proto = AllocatedResources(
            tasks={
                t.name: AllocatedTaskResources(
                    cpu_shares=t.resources.cpu,
                    memory_mb=t.resources.memory_mb,
                )
                for t in tg.tasks
            },
            shared=AllocatedSharedResources(disk_mb=tg.ephemeral_disk.size_mb),
        )
        return DenseTGPlacements(
            namespace=job.namespace,
            job_id=job.id,
            task_group=tg.name,
            eval_id=eval_id,
            deployment_id=deployment_id,
            job=job,
            resources_proto=proto,
            ask_vec=(
                float(sum(t.resources.cpu for t in tg.tasks)),
                float(sum(t.resources.memory_mb for t in tg.tasks)),
                float(tg.ephemeral_disk.size_mb),
                0.0,
            ),
            ids=generate_uuids(len(node_idxs)),
            names=names,
            node_ids=[nodes[int(j)].id for j in node_idxs],
            node_names=[nodes[int(j)].name for j in node_idxs],
            scores=[float(s) for s in scores_f],
            nodes_evaluated=list(nodes_evaluated),
            nodes_available=nodes_available,
        )

    def _apply_system_results_dense(self, sched, place, nodes, chosen,
                                    scores, start_ns, enc=None,
                                    evict=None) -> None:
        """System-path dense blocks: same DenseTGPlacements flow as the
        generic path, grouped by task group. Preconditions checked by the
        caller: every placement chose its node, all fresh, no
        network/device asks."""
        job = sched.job
        scores_f = self._scores_to_float(scores)
        by_tg: Dict[str, List[int]] = {}
        for pi, tup in enumerate(place):
            by_tg.setdefault(tup.task_group.name, []).append(pi)
        tg_by_name = {tg.name: tg for tg in job.task_groups}
        has_pre = (
            evict is not None and evict.ndim == 2 and evict.shape[1] > 0
            and enc is not None and enc.pre_allocs is not None
        )
        for tg_name, idxs in by_tg.items():
            block = self._dense_block(
                job, tg_by_name[tg_name], sched.eval.id,
                [chosen[k] for k in idxs], nodes,
                names=[place[k].name for k in idxs],
                scores_f=[scores_f[k] for k in idxs],
                nodes_evaluated=[1] * len(idxs),
                nodes_available=getattr(sched, "nodes_by_dc", {}),
            )
            if has_pre:
                pre_ids: List[List[str]] = []
                any_pre = False
                for bi, k in enumerate(idxs):
                    row = evict[int(k)]
                    ks = sorted(
                        (c for c in range(row.shape[0]) if int(row[c]) >= 0),
                        key=lambda c: int(row[c]),
                    )
                    if not ks:
                        pre_ids.append([])
                        continue
                    cand = enc.pre_allocs[int(chosen[int(k)])]
                    stops = [cand[c] for c in ks]
                    for stop in stops:
                        sched.plan.append_preempted_alloc(stop, block.ids[bi])
                    pre_ids.append([s.id for s in stops])
                    any_pre = True
                if any_pre:
                    block.preempted = pre_ids
            sched.plan.dense_placements.append(block)
        sched.ctx.metrics.allocation_time_ns = _time.monotonic_ns() - start_ns

    # ------------------------------------------------------------------

    def _apply_results_dense(self, sched, enc, chosen, scores, pulls,
                             evict=None) -> None:
        """Record scan results as DenseTGPlacements blocks — one per task
        group, parallel arrays only. The per-placement work here is a few
        list appends; AllocMetric/Allocation objects materialize lazily
        on read (structs.DenseTGPlacements.materialize). Preconditions
        (checked by the caller): enc.dense_ok, every placement chosen."""
        job = sched.job
        deployment_id = ""
        if sched.deployment is not None and sched.deployment.active():
            deployment_id = sched.deployment.id

        scores_f = self._scores_to_float(scores)
        pulls = np.asarray(pulls)
        tg_idx = enc.xs[0]  # [p] task-group index per placement
        missing_list = enc.missing_list
        has_pre = (
            evict is not None and evict.ndim == 2 and evict.shape[1] > 0
            and enc.pre_allocs is not None
        )

        for gi in np.unique(tg_idx):
            sel = np.nonzero(tg_idx == gi)[0]
            block = self._dense_block(
                job, job.task_groups[int(gi)], sched.eval.id,
                chosen[sel], enc.nodes,
                names=[missing_list[k].get_name() for k in sel],
                scores_f=scores_f[sel],
                nodes_evaluated=pulls[sel].tolist(),
                nodes_available=getattr(sched, "_nodes_by_dc", {}),
                deployment_id=deployment_id,
            )
            if has_pre:
                # eviction sets ride the block as parallel id lists AND go
                # into plan.node_preemptions (plan_apply re-checks them and
                # the FSM commits the evictions)
                pre_ids: List[List[str]] = []
                any_pre = False
                for bi, k in enumerate(sel):
                    row = evict[int(k)]
                    ks = sorted(
                        (c for c in range(row.shape[0]) if int(row[c]) >= 0),
                        key=lambda c: int(row[c]),
                    )
                    if not ks:
                        pre_ids.append([])
                        continue
                    cand = enc.pre_allocs[int(chosen[int(k)])]
                    stops = [cand[c] for c in ks]
                    for stop in stops:
                        sched.plan.append_preempted_alloc(stop, block.ids[bi])
                    pre_ids.append([s.id for s in stops])
                    any_pre = True
                if any_pre:
                    block.preempted = pre_ids
            sched.plan.dense_placements.append(block)

        sched.ctx.metrics.allocation_time_ns = _time.monotonic_ns() - enc.start_ns

    def _apply_results(self, sched, missing_list, nodes, table, chosen, scores,
                       pulls, skipped_steps, start_ns, enc=None,
                       evict=None) -> None:
        """Materialize scan results into the plan (allocs, stops, metrics)."""
        from ..structs.structs import AllocMetric

        job = sched.job
        ctx = sched.ctx
        deployment_id = ""
        if sched.deployment is not None and sched.deployment.active():
            deployment_id = sched.deployment.id
        now = _time.time_ns()

        # Lazy per-node NetworkIndex / DeviceAllocator mirrors for port and
        # device-instance assignment (the discrete half the capacity dims
        # pre-checked on device).
        assigner = _ResourceAssigner(ctx, nodes)

        for pi, missing in enumerate(missing_list):
            tg = missing.get_task_group()
            node_idx = int(chosen[pi])

            if skipped_steps[pi]:
                # coalesced failure (TG already failed earlier in this eval)
                if sched.failed_tg_allocs and tg.name in sched.failed_tg_allocs:
                    sched.failed_tg_allocs[tg.name].coalesced_failures += 1
                continue

            prev_allocation = missing.get_previous_allocation()
            stop_prev, stop_desc = missing.stop_previous_alloc()

            metrics = AllocMetric()
            metrics.nodes_evaluated = int(pulls[pi])
            metrics.nodes_available = getattr(sched, "_nodes_by_dc", {})

            if node_idx < 0:
                if sched.failed_tg_allocs is None:
                    sched.failed_tg_allocs = {}
                sched.failed_tg_allocs[tg.name] = metrics
                continue

            if stop_prev and prev_allocation is not None:
                sched.plan.append_stopped_alloc(prev_allocation, stop_desc, "")

            node = nodes[node_idx]

            task_resources, shared_networks, ok = assigner.build(node_idx, tg)
            if not ok:
                # Port/device-instance collision the capacity model missed:
                # extremely rare; record as failed placement (plan applier
                # would have rejected it anyway).
                if sched.failed_tg_allocs is None:
                    sched.failed_tg_allocs = {}
                sched.failed_tg_allocs[tg.name] = metrics
                if stop_prev and prev_allocation is not None:
                    sched.plan.pop_update(prev_allocation)
                continue

            if scores.dtype.kind == "i":
                # int-spec score60 -> display float (metrics only; never
                # used in selection comparisons)
                from .intscore import score60_to_float

                score_f = score60_to_float(scores[pi])
            else:
                score_f = float(scores[pi])
            metrics.score_node(node, "binpack", score_f)
            metrics.score_node(node, "normalized-score", score_f)
            metrics.populate_score_meta_data()

            resources = AllocatedResources(
                tasks=task_resources,
                shared=AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb, networks=shared_networks
                ),
            )

            alloc = Allocation(
                namespace=job.namespace,
                eval_id=sched.eval.id,
                name=missing.get_name(),
                job_id=job.id,
                task_group=tg.name,
                metrics=metrics,
                node_id=node.id,
                node_name=node.name,
                deployment_id=deployment_id,
                allocated_resources=resources,
                desired_status=ALLOC_DESIRED_RUN,
                client_status=ALLOC_CLIENT_PENDING,
            )

            if prev_allocation is not None:
                alloc.previous_allocation = prev_allocation.id
                if missing.is_rescheduling():
                    from ..scheduler.generic_sched import update_reschedule_tracker

                    update_reschedule_tracker(alloc, prev_allocation, now)

            if missing.is_canary() and sched.deployment is not None:
                state = sched.deployment.task_groups.get(tg.name)
                if state is not None:
                    state.placed_canaries.append(alloc.id)
                from ..structs.structs import AllocDeploymentStatus

                alloc.deployment_status = AllocDeploymentStatus(canary=True)

            if (
                evict is not None and evict.ndim == 2 and evict.shape[1]
                and enc is not None and enc.pre_allocs is not None
            ):
                # device eviction set: column c holds the second-pass rank
                # (>=0 kept, -1 dropped); materialize in rank order — the
                # order the host oracle reports preempted allocs in
                row = evict[pi]
                ks = sorted(
                    (c for c in range(row.shape[0]) if int(row[c]) >= 0),
                    key=lambda c: int(row[c]),
                )
                if ks:
                    cand = enc.pre_allocs[node_idx]
                    stops = [cand[c] for c in ks]
                    for stop in stops:
                        sched.plan.append_preempted_alloc(stop, alloc.id)
                    alloc.preempted_allocations = [s.id for s in stops]

            sched.plan.append_alloc(alloc)

        ctx.metrics.allocation_time_ns = _time.monotonic_ns() - start_ns


# ---------------------------------------------------------------------------
# Synthetic inputs (graft entry / dryrun / microbench)
# ---------------------------------------------------------------------------


def example_scan_inputs(n_nodes: int = 64, n_tgs: int = 2, n_placements: int = 16,
                        n_spreads: int = 1, vocab: int = 4,
                        dtype=np.float32, seed: int = 0, num_dims: int = 4):
    """Build plausible dense scan inputs directly (no scheduler objects).

    Returns (n_pad, static, init_carry, xs) as numpy arrays, shaped exactly
    like compute_placements builds them. ``dtype=np.int32`` builds the
    exact-integer parity encoding (spread targets in hundredths, Q30
    affinity ints — the intscore.py spec); float dtypes build the
    throughput encoding.
    """
    dtype = np.dtype(dtype)
    int_mode = dtype.kind == "i"
    rng = np.random.default_rng(seed)
    n_pad = _round_up(n_nodes)
    # zero n_spreads = a true ZERO S axis: the spread machinery
    # (one-hot [S,V,N] lookups, boosts, count carries) compiles away
    # entirely, matching production encode for spread-free jobs
    g, s, v = n_tgs, n_spreads, vocab + 1

    totals = np.zeros((n_pad, num_dims), dtype)
    totals[:n_nodes, DIM_CPU] = rng.choice([2000, 4000, 8000], n_nodes)
    totals[:n_nodes, DIM_MEM] = rng.choice([4096, 8192, 16384], n_nodes)
    totals[:n_nodes, 2] = 100 * 1024
    totals[:n_nodes, DIM_MBITS] = 1000
    reserved = np.zeros((n_pad, num_dims), dtype)
    reserved[:n_nodes, DIM_CPU] = 100
    reserved[:n_nodes, DIM_MEM] = 256
    used0 = np.zeros((n_pad, num_dims), dtype)

    asks = np.zeros((g, num_dims), dtype)
    asks[:, DIM_CPU] = rng.choice([100, 250, 500], g)
    asks[:, DIM_MEM] = rng.choice([128, 256, 512], g)
    asks[:, 2] = 150
    asks[:, DIM_MBITS] = 10

    feas = np.zeros((g, n_pad), bool)
    feas[:, :n_nodes] = rng.random((g, n_nodes)) < 0.9
    from .intscore import pack_feat_planes

    feat_packed = pack_feat_planes(feas)
    # no affinities in the synthetic workload: zero G axis (the step
    # compiles the affinity term away — matching production encode)
    aff_score = np.zeros((0, n_pad), dtype)
    desired_counts = np.full(g, max(n_placements // g, 1), np.int32)
    dh_job = np.zeros(g, bool)
    dh_tg = np.zeros(g, bool)
    limits = np.full(g, max(2, int(np.ceil(np.log2(max(n_nodes, 2))))), np.int32)

    spread_vids = np.full((g, s, n_pad), v - 1, np.int32)
    spread_vids[:, :, :n_nodes] = rng.integers(0, vocab, (g, s, n_nodes))
    spread_desired = np.full((g, s, v), -1, dtype) if int_mode else \
        np.full((g, s, v), -1.0, dtype)
    if int_mode:
        # hundredths (d = percent * count), evenly targeted
        spread_desired[:, :, :vocab] = (100 * n_placements) // vocab
    else:
        spread_desired[:, :, :vocab] = float(n_placements) / vocab
    spread_weights = np.full((g, s), 50, dtype)
    spread_has_targets = np.ones((g, s), bool)
    spread_active = np.zeros((g, s), bool)
    spread_active[:, :n_spreads] = True
    sum_spread_weights = np.full(g, 50 * max(n_spreads, 1), dtype)
    spread_counts0 = np.zeros((g, s, v), dtype)
    spread_entry0 = np.zeros((g, s, v), bool)

    if int_mode:
        from .intscore import E27_ONE, e27_np, xq_np

        node_c2 = (totals[:, :2] - reserved[:, :2]).astype(np.int64)
        e_base0 = e27_np(xq_np(node_c2 - used0[:, :2] - reserved[:, :2],
                               node_c2)).astype(np.int32)
        e_ask = np.full((g, n_pad, 2), E27_ONE, np.int32)
        for gi in range(g):
            for d in (0, 1):
                e_ask[gi, :, d] = e27_np(
                    xq_np(np.full(n_pad, -int(asks[gi, d]), np.int64),
                          node_c2[:, d])
                ).astype(np.int32)
        # reserved folds into totals (see encode_eval)
        totals = totals - reserved
        reserved = np.zeros((0, num_dims), dtype)
    else:
        e_base0 = np.zeros((0, 2), np.int32)
        e_ask = np.zeros((0, 0, 2), np.int32)

    static = (totals, reserved, asks, feat_packed, aff_score,
              desired_counts, dh_job, dh_tg, limits, spread_vids,
              spread_desired, spread_weights, spread_has_targets,
              spread_active, sum_spread_weights, np.int32(n_nodes), e_ask,
              np.zeros((0, n_pad), np.int32),   # dp_vids: no distinct_property
              np.zeros(0, np.int32),
              np.zeros((g, 0), bool),
              # no preemption: zero-width candidate axis compiles the
              # eviction path away
              np.zeros((n_pad, 0, 4), np.int32), np.zeros((n_pad, 0), np.int32),
              np.zeros((n_pad, 0), bool), np.zeros((n_pad, 0), np.int32),
              np.zeros((n_pad, 0), np.int32), np.zeros((n_pad, 0, 2), np.int32))
    init_carry = (used0, np.zeros((g, n_pad), np.int32), np.zeros(n_pad, np.int32),
                  spread_counts0, spread_entry0, np.int32(0), np.zeros(g, bool),
                  e_base0, np.zeros((0, 1), np.int32),
                  np.zeros((n_pad, 0), bool), np.zeros((0, 3), np.int64),
                  np.zeros(0, np.int32))
    limit_val = max(2, int(np.ceil(np.log2(max(n_nodes, 2)))))
    xs = (rng.integers(0, g, n_placements).astype(np.int32),
          np.full((n_placements, 0), -1, np.int32),  # no reschedule history
          np.full(n_placements, -1, np.int32),
          # no evictions: zero-width axes compile the evict path away
          np.zeros((n_placements, 0), dtype),
          np.full(n_placements, -1, np.int32),
          np.full(n_placements, 2**31 - 1 if n_spreads else limit_val, np.int32),
          np.full(n_placements, 50 * max(n_spreads, 1), dtype),
          np.zeros((n_placements, 0), np.int32),
          np.zeros((n_placements, 0), np.int32),
          np.zeros((n_placements, 0), np.int32))  # forced_node: unrestricted
    return n_pad, static, init_carry, xs
