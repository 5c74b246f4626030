"""The step's ``select`` (``engine._select``): the winner found by ONE
lexicographic reduction (highest score, then lowest rank, the node index
and the pulled count carried along) is, bit for bit, what the serial
selection it replaced found: ``max(score)``, then ``min(rank)`` among the
winners, ``any(cand)``, the first winner's index and ``sum(pulled)``, each
a reduction of its own. That selection is kept here as the plain
reference, ``_select_by_six_reductions``."""
import functools
import math

import numpy as np
import pytest

from nomad_tpu.tpu import intscore
from nomad_tpu.tpu.engine import MAX_SKIP, _select


@pytest.fixture(autouse=True)
def _x64():
    import jax

    jax.config.update("jax_enable_x64", True)


def _select_by_six_reductions(final, feasible, iota, n_real, offset, limit,
                              skip_step, totals, util):
    """``select`` as it was: six reductions to a scalar, in series."""
    import jax.numpy as jnp
    from jax import lax as jlax

    from nomad_tpu.tpu.intscore import (
        NEAR_TIE_BAND60, PACK_COUNT_MAX, RIVAL_BITS, pack_count_lanes,
        unpack_count_hi, unpack_count_lo)

    i32 = jnp.int32
    i32_max = (1 << 31) - 1
    mix_by = (-1640531527, 506961463, 668265263, 374761393)
    node_shape = final.shape
    nodes = tuple(range(len(node_shape)))
    n_pad = math.prod(node_shape)
    int_mode = jnp.issubdtype(final.dtype, jnp.integer)
    neg_inf = jnp.iinfo(jnp.int64).min // 4 if int_mode else -jnp.inf

    valid = iota < n_real
    nr = jnp.maximum(n_real, 1)
    feas_v = feasible & valid
    low = feas_v & (final <= 0)

    def ring_cumsum(a_int):
        s_flat = jnp.cumsum(a_int.reshape(n_pad))
        s_nat = s_flat.reshape(node_shape)
        total = s_flat[-1]
        before = jnp.sum(jnp.where(iota < offset, a_int, 0), dtype=i32)
        ring = jnp.where(
            iota >= offset, s_nat - before, s_nat + (total - before))
        return ring, total

    if n_pad < PACK_COUNT_MAX:
        packed_cum, packed_total = ring_cumsum(pack_count_lanes(low, feas_v))
        low_cum = unpack_count_lo(packed_cum)
        feas_cum = unpack_count_hi(packed_cum)
        low_total = unpack_count_lo(packed_total)
        feas_total = unpack_count_hi(packed_total)
    else:
        low_cum, low_total = ring_cumsum(low.astype(i32))
        feas_cum, feas_total = ring_cumsum(feas_v.astype(i32))

    skipped = low & (low_cum <= MAX_SKIP)
    skip_cum = jnp.minimum(low_cum, MAX_SKIP)
    ret = feas_v & ~skipped
    ret_excl = (feas_cum - skip_cum) - ret.astype(i32)
    pulled = valid & (ret_excl < limit)
    src_cand = ret & pulled
    ret_total = feas_total - jnp.minimum(low_total, MAX_SKIP)
    backlog_n = jnp.maximum(limit - ret_total, 0)
    skip_excl = skip_cum - skipped.astype(i32)
    backlog_cand = skipped & (skip_excl < backlog_n)
    cand = src_cand | backlog_cand
    rank = jnp.where(src_cand, ret_excl, ret_total + skip_excl)

    cand_scores = jnp.where(cand, final, neg_inf)
    best_score = jnp.max(cand_scores)
    winners = cand & (cand_scores == best_score)
    best_rank = jnp.min(jnp.where(winners, rank, i32(i32_max)))
    any_cand = jnp.any(cand)
    first = winners & (rank == best_rank)

    refereed = int_mode and n_pad <= (1 << RIVAL_BITS)
    operands = [(jnp.where(first, iota, i32_max), i32_max, jnp.minimum)]
    if refereed:
        mix = (totals[0] * i32(mix_by[0]) + totals[1] * i32(mix_by[1])
               + util[0] * i32(mix_by[2]) + util[1] * i32(mix_by[3]))
        delta = best_score - cand_scores
        in_band = (delta > 0) & (delta <= NEAR_TIE_BAND60)
        key = ((delta >> 1).astype(i32) << RIVAL_BITS) | iota
        operands += [
            (jnp.where(winners, mix, i32_max), i32_max, jnp.minimum),
            (jnp.where(winners, mix, -i32_max - 1), -i32_max - 1,
             jnp.maximum),
            (jnp.where(in_band, key, i32_max), i32_max, jnp.minimum),
            (jnp.where(in_band, key, 0), 0, jnp.maximum),
            (in_band.astype(i32), 0, jnp.add)]
    arrays, inits, joins = zip(*operands)
    found = jlax.reduce(
        arrays, tuple(i32(v) for v in inits),
        lambda a, b: tuple(j(x, y) for j, x, y in zip(joins, a, b)), nodes)
    chosen = jnp.where(any_cand & (~skip_step), found[0], -1)
    pulls = jnp.where(skip_step, 0, jnp.sum(pulled.astype(i32))).astype(i32)
    offset = jnp.where(skip_step, offset, (offset + pulls) % nr).astype(i32)
    if refereed:
        _first, mix_lo, mix_hi, nearest, farthest, members = found
        uneven = mix_lo != mix_hi
        index = i32((1 << RIVAL_BITS) - 1)
        rival = jnp.where(
            ((members > 0) | uneven) & any_cand & (~skip_step),
            (nearest & index) | ((farthest & index) << RIVAL_BITS)
            | (((members > 2) | uneven).astype(i32) << (2 * RIVAL_BITS)),
            i32(-1))
    else:
        rival = i32(-1)
    return chosen, best_score, pulls, offset, rival


# ---------------------------------------------------------------------------
# the cases: one node plane each, drawn from a seed; a 16-wide case is
# sixteen of them, seeds 0..15
# ---------------------------------------------------------------------------

_BAND = intscore.NEAR_TIE_BAND60
_WIDE_PAD = (1 << 15) + 128          # beyond the referee and the count lanes
_BOTH = ("random", "no-candidate", "all-twins", "negative", "ring-wraps",
         "limit-cuts", "backlog", "skip-step", "wide-fleet")
_INT_ONLY = ("halves", "in-band-rival", "crowded-band", "uneven-twins")


def _instance(case, mode, seed):
    """The arguments of ``_select`` for one plane, flat, as numpy."""
    rng = np.random.default_rng([seed, (_BOTH + _INT_ONLY).index(case)])
    int_mode = mode == "int32"
    n_pad = _WIDE_PAD if case == "wide-fleet" else 256
    n_real = n_pad - int(rng.integers(1, 40))
    iota = np.arange(n_pad)
    valid = iota < n_real
    # pads drawn feasible too: select masks them itself
    feasible = rng.random(n_pad) < 0.7
    totals = rng.integers(1000, 4000, (4, n_pad))
    util = rng.integers(0, 1000, (4, n_pad))
    offset = int(rng.integers(0, n_real))
    limit = int(rng.integers(1, 12))
    skip = False
    if int_mode:
        pool = rng.integers(1, 1 << 42, 6)
    else:
        pool = rng.random(6) * 2.0 - 0.5
    final = rng.choice(pool, n_pad)

    def ring(k):
        return (offset + k) % n_real

    if case == "no-candidate":
        feasible[:] = False
    elif case == "all-twins":
        feasible[:] = True
        final[:] = abs(pool[0]) + 1
        totals[:] = totals[:, :1]
        util[:] = util[:, :1]
        limit = n_real
    elif case == "negative":
        final = -rng.choice(np.abs(pool) + 1, n_pad)
    elif case == "ring-wraps":
        offset, limit = n_real - 2, 8
    elif case == "limit-cuts":
        feasible[:] = True
        final = np.abs(final) + 1
        final[ring(10)] = final.max() + 1
        limit = 3
    elif case == "backlog":
        # six feasible nodes, all scoring <= 0: the first three in ring
        # order are skipped, the rest returned, and a limit of 10 takes
        # the skipped ones back as the backlog
        feasible[:] = False
        for k, score in enumerate((0, -5, 0, -100, -200, -300)):
            feasible[ring(k)] = True
            final[ring(k)] = score
        limit = 10
    elif case == "skip-step":
        skip = True
    elif case == "halves":
        # one high int32 half, low halves that differ in their top bit
        feasible[:] = True
        final = (np.int64(3) << 32) + rng.choice(
            np.array([5, 0x7FFFFFFF, 0x80000000, 0x80000001], np.int64),
            n_pad)
        final[ring(7)] = (np.int64(3) << 32) + 0xFFFFFFF0
        final[ring(2)] = (np.int64(2) << 32) + 0xFFFFFFFF
        limit = n_real
    elif case in ("in-band-rival", "crowded-band", "uneven-twins"):
        feasible[:] = True
        top = int(pool.max()) + 100 * _BAND
        final[:] = top - 10 * _BAND
        final[ring(5)] = top
        under = {"in-band-rival": (_BAND // 2,),
                 "crowded-band": (_BAND // 4, _BAND // 2, _BAND),
                 "uneven-twins": (0,)}[case]
        for k, d in enumerate(under):
            final[ring(20 + 3 * k)] = top - d
        limit = n_real
    dt = np.int64 if int_mode else np.float32
    pdt = np.int32 if int_mode else np.float32
    return (final.astype(dt), feasible, iota.astype(np.int32),
            np.int32(n_real), np.int32(offset), np.int32(limit),
            np.bool_(skip), totals.astype(pdt), util.astype(pdt))


def _laid_out(args, layout):
    """The node planes flat ``(n_pad,)`` or folded ``(n_pad // 128, 128)``."""
    final = args[0]
    if layout == "flat":
        return args
    shape = (final.shape[-1] // 128, 128)
    planes = {0, 1, 2, 7, 8}
    return tuple(a.reshape(a.shape[:-1] + shape) if i in planes else a
                 for i, a in enumerate(args))


@functools.cache
def _jitted(fn, width):
    """``fn`` jitted, vmapped over all but ``iota`` where 16 wide."""
    import jax

    return jax.jit(fn if width == 1 else jax.vmap(
        fn, in_axes=(0, 0, None, 0, 0, 0, 0, 0, 0)))


def _run(fn, args, width):
    return [np.asarray(o) for o in _jitted(fn, width)(*args)]


def _premise(case, args, out):
    """What the case is there to reach, read off the reference's outputs
    (seed 0's plane)."""
    final, _feasible, _iota, n_real, offset, _limit, _skip = args[:7]
    chosen, best, pulls, new_offset, rival = (o.reshape(-1)[0].item()
                                              for o in out)
    crowded = rival >= 0 and bool(rival & intscore.RIVAL_CROWDED)
    ok = {
        "random": chosen >= 0,
        "no-candidate": chosen == -1 and rival == -1,
        "all-twins": chosen == int(offset) and rival == -1,
        "negative": chosen >= 0 and best < 0,
        "ring-wraps": new_offset < int(offset),
        "limit-cuts": chosen >= 0 and chosen != int(np.argmax(
            final[:int(n_real)])),
        "backlog": chosen == int(offset) and best == 0,
        "skip-step": chosen == -1 and pulls == 0 and new_offset == int(
            offset),
        "wide-fleet": chosen >= 0 and rival == -1,
        "halves": best == (3 << 32) + 0xFFFFFFF0,
        "in-band-rival": rival >= 0 and not crowded,
        "crowded-band": crowded,
        "uneven-twins": crowded,
    }[case]
    assert ok, (case, chosen, best, pulls, new_offset, rival)


_SELECT_CASES = [
    (case, mode, layout, width)
    for mode in ("int32", "float32")
    for case in _BOTH + (_INT_ONLY if mode == "int32" else ())
    for layout in ("flat", "folded")
    for width in (1, 16)
]


@pytest.mark.parametrize(
    "case,mode,layout,width", _SELECT_CASES,
    ids=[f"{c}-{m}-{lay}-{w}wide" for c, m, lay, w in _SELECT_CASES])
def test_one_lexicographic_reduction_selects_as_six_did(case, mode, layout,
                                                        width):
    """``chosen``, the score, ``pulls``, the new ring offset and ``rival``
    of ``engine._select``, bit for bit the six-reduction selection's."""
    import jax

    planes = [_laid_out(_instance(case, mode, seed), layout)
              for seed in range(width)]
    if width == 1:
        args = planes[0]
    else:
        args = jax.tree_util.tree_map(lambda *a: np.stack(a), *planes)
        args = args[:2] + (planes[0][2],) + args[3:]
    want = _run(_select_by_six_reductions, args, width)
    have = _run(_select, args, width)
    _premise(case, planes[0], [w[0] if width > 1 else w for w in want])
    for name, w, h in zip(("chosen", "score", "pulls", "offset", "rival"),
                          want, have):
        assert (h.dtype, h.shape) == (w.dtype, w.shape), name
        assert h.tobytes() == w.tobytes(), (name, w, h)


# ---------------------------------------------------------------------------
# the structure: reductions over the node plane inside the loop's select
# ---------------------------------------------------------------------------

_REDUCTIONS = {"reduce", "reduce_sum", "reduce_max", "reduce_min",
               "reduce_or", "reduce_and", "argmax", "argmin"}


def _scopes(eqn):
    """The scope names an equation was traced under, transforms taken off:
    ``vmap(select)`` reads ``select``."""
    return {part.split("(")[-1].rstrip(")")
            for part in str(eqn.source_info.name_stack).split("/")}


def _select_reductions(fn, layout, *buffers):
    """The reductions of a node plane to a scalar inside the ``while`` of
    ``fn``'s jaxpr, in the ``select`` scope; the ring cumsum is a scan,
    not one of them."""
    import jax

    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            here = inside or eqn.primitive.name == "while"
            if (inside and eqn.primitive.name in _REDUCTIONS
                    and "select" in _scopes(eqn)):
                shape = eqn.invars[0].aval.shape
                axes = eqn.params.get("axes", eqn.params.get("dimensions"))
                found.append((eqn.primitive.name,
                              math.prod(shape[a] for a in axes)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jax.make_jaxpr(fn, static_argnums=0)(layout, *buffers).jaxpr, False)
    return found


@pytest.mark.parametrize("mode,b_pad,count", [
    ("int32", 1, 3), ("int32", 16, 3), ("float32", 1, 2), ("float32", 16, 2)],
    ids=["refereed-lone", "refereed-16wide", "float-lone", "float-16wide"])
def test_select_reduces_the_node_plane_three_times(mode, b_pad, count):
    """On the refereed path (int mode): ``before``, the lexicographic
    reduction and the near-tie one; off it (float mode): the first two.
    The serial selection had six on both."""
    from nomad_tpu.tpu import wire
    from nomad_tpu.tpu.batcher import DeviceBatcher
    from nomad_tpu.tpu.engine import (
        EncodedEval, _build_wire_scan, example_scan_inputs)

    scan = _build_wire_scan()
    dtype = np.dtype(mode).type
    n_pad, static, carry, xs = example_scan_inputs(
        n_nodes=300, n_tgs=2, n_placements=20, n_spreads=1, seed=3,
        dtype=dtype)
    enc = EncodedEval(
        n_real=300, n_pad=n_pad, g=2, s=static[9].shape[1],
        v=static[10].shape[2], p=20, dtype=dtype, static=static,
        carry=carry, xs=xs, missing_list=[], nodes=[], table=None,
        start_ns=0)
    dims = DeviceBatcher._batch_dims([enc])
    layout = wire.WireLayout(wire.shape_key(enc, dims, enc.dtype), b_pad,
                             dims)
    bufs = wire.WireBuffers(layout)
    wire.pack(bufs, [enc])
    found = _select_reductions(scan, layout, *bufs.arrays)
    assert [size for _name, size in found] == [enc.n_pad] * count, found
