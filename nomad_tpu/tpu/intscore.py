"""Exact integer scoring spec for the ``tpu_binpack`` parity engine.

The round-2 engine scored in float64 — emulated (double-double) on TPU
v5e and STILL not bit-identical to the host (XLA's f64 ``pow`` rounds
differently from libm, flipping exact-tie orderings; that forced the
parity suite onto the CPU backend). This module replaces float scoring
with a deterministic integer program: every runtime operation is an
int32/int64 add, multiply, shift, compare or floor division — exact on
every backend — so the device scan's selection decisions are
bit-identical to a pure-Python evaluation of the same spec ON THE REAL
CHIP, with no floating point in the comparison path.

Cost model that shaped the design: scan-body cost is per-HLO-pass over
the [batch, nodes] arrays; a 26-multiply exponential chain or an int64
division per step is ruinous, while small ([batch]- or [S,V]-shaped)
ops are free.
Hence the exponential is INCREMENTAL-MULTIPLICATIVE:

  e_base[n]  Q27 10**x_base, x_base = (cap - used - reserved)/cap,
             carried per node; initialized by the encode-time chain and
             updated by MULTIPLYING precomputed Q27 factors when a
             placement/eviction changes the node (a running product —
             each update floor-rounds at Q27, drift <= k*2**-27 for k
             touch events, mirrored exactly by the oracle)
  e_ask[g,n] Q27 10**(-ask_g/cap_n): static per eval (encode-time)
  ev/rev     Q27 eviction/revert factors: per-placement scalars
             (the evicted node is known at encode time)
  score      E_sel = (e_base * e_ask) >> 27 per dim; BestFit-v3 =
             clip(20*2**27 - Ec - Em, 0, 18*2**27); Q30 term =
             (fit * 4) // 9, a constant divisor that the device computes
             as one multiply and one shift (``binpack_q30``: the TPU's
             compiler lowers an int64 ``//`` to a 30-kernel long division)

Numeric layout
  x (free fraction)   Q24, x_q = floor(x * 2**24), clamped to [-2, 1]
  10**x               Q28 bit-product chain (ENCODE TIME ONLY):
                      prod over set bits i of round(2**28 * 10**(2**(i-24)));
                      negative x via 2**56 // E(|x|); Q27 = (Q28+1)>>1
  score terms         Q30: binpack as above; anti-affinity and the
                      even-spread boost via Q45 reciprocals of SMALL
                      denominators (counts <= 2**17, so the reciprocal
                      error is < 4 Q30-ulp); the targeted spread boost
                      via ONE exact int64 floor division
  final selection     score60 = terms_sum * (60 // num_terms) —
                      num_terms in 1..5 all divide 60, so the mean
                      normalization (rank.go:688) is an EXACT multiply

Precision vs the reference's float64 (funcs.go:154 ScoreFit): the spec
tracks the real-valued score within ~5e-7, so orderings agree with the
host float64 pipeline whenever true score gaps exceed that — which the
parity fuzz corpus (and any realistic cluster: the smallest binpack gap
is ~ask/capacity ~ 1e-2) clears by orders of magnitude. Exact rational
ties (identical node tuples) tie in BOTH systems and fall to the same
deterministic rank tie-break.

Magnitude gates (enforced by encode; host fallback otherwise):
  cpu/mem capacities       <= 2**24
  reserved                 <= 2 * (totals - reserved)
  any capacity/ask         <= 2**28
  job total count          <= 100_000
  spread weight            in [0, 256]; spread percent in [0, 100]
  sum of spread weights    > 0 when spreads exist
With these every int64 intermediate stays below 2**63.
"""
from __future__ import annotations

from typing import List

import numpy as np

# Fixed-point scales
XQ_BITS = 24          # Q24 free-fraction quantization
E_BITS = 28           # Q28 encode-time exponential chain
E27_BITS = 27         # Q27 runtime e_base / factor arrays (fit int32)
TERM_BITS = 30        # Q30 score terms
RECIP_BITS = 45       # Q45 reciprocals of small denominators
TERM_ONE = 1 << TERM_BITS
XQ_ONE = 1 << XQ_BITS
E_ONE = 1 << E_BITS
E27_ONE = 1 << E27_BITS

# d == 0 spread-target sentinel: the host uses -finfo.max/16; any value
# far beyond the legitimate term range works.
BIG_FP = 1 << 44

# The near-tie band. The spec tracks the float64 score to within ~5e-7
# (module doc; the widest distance a run of the benchmark has read is
# 1.06e-7), so two candidates whose Q30 scores lie no farther apart than
# two such roundings may stand the other way round in float64, and the
# guarantee is upstream's float64 order. 2**10 Q30 units of the
# mean-normalised score are 9.5e-7: twice the stated bound rounded to a
# power of two. The scan's step names the candidates inside the band
# (engine._make_step, ``rival``) and the host decides among them in
# float64 (tpu/referee.py). score60 carries the mean times 60.
NEAR_TIE_BAND_Q30 = 1 << 10
NEAR_TIE_BAND60 = 60 * NEAR_TIE_BAND_Q30
# ``rival``, one int32 a step: the nearest band member's node index, the
# farthest's above it, then the bit "more than these two"; -1 for none
RIVAL_BITS = 15
RIVAL_CROWDED = 1 << (2 * RIVAL_BITS)

# Max job total count for the int path (overflow gate, see module doc)
MAX_TOTAL_COUNT = 100_000

# E-chain constants: c[i] = round(2**28 * 10**(2**(i-24))) for i = 0..25.
# Bits 0..23 are fractional (10**(2**-24) .. 10**(1/2)); bit 24 is 10**1,
# bit 25 is 10**2 (|x| <= 2 needs two integer bits).
_CHAIN_LEN = XQ_BITS + 2


def _chain_constants() -> List[int]:
    from decimal import Decimal, getcontext

    getcontext().prec = 50
    out = []
    ten = Decimal(10)
    for i in range(_CHAIN_LEN):
        exp = Decimal(2) ** (i - XQ_BITS)
        val = ten ** exp
        out.append(int((val * (1 << E_BITS)).to_integral_value(rounding="ROUND_HALF_EVEN")))
    return out


CHAIN = _chain_constants()


# ---------------------------------------------------------------------------
# Pure-Python / numpy reference (the spec oracle — exact integer math).
# These run at ENCODE time and in tests; nothing here touches the device.
# ---------------------------------------------------------------------------


def xq_py(free_num: int, cap: int) -> int:
    """x_q = floor(free_num * 2**24 / cap), clamped to [-2, 1] in Q24.

    The +1 upper clamp keeps every Q27 exponential <= 10*2**27 (int32);
    free fractions above 1 cannot occur for real state (used,res >= 0),
    and an eviction factor above 10 would mean evicting more than 100%
    of effective capacity in one alloc."""
    q = (int(free_num) << XQ_BITS) // max(int(cap), 1)
    return max(-2 * XQ_ONE, min(XQ_ONE, q))


def exp10_fp_py(x_q: int) -> int:
    """Q28 10**x for x_q in Q24, |x_q| <= 2*2**24. Exact per the spec."""
    neg = x_q < 0
    xa = -x_q if neg else x_q
    acc = E_ONE
    for i in range(_CHAIN_LEN):
        if (xa >> i) & 1:
            acc = (acc * CHAIN[i]) >> E_BITS
    if neg:
        acc = (1 << (2 * E_BITS)) // max(acc, 1)
    return acc


def e27_py(x_q: int) -> int:
    """Q27 10**x: the Q28 chain rounded-half-up to Q27 (fits int32)."""
    return (exp10_fp_py(x_q) + 1) >> 1


def xq_np(free_num, cap):
    """Vectorized x_q (numpy int64; floor division, clamped to [-2, 1])."""
    free_num = np.asarray(free_num, np.int64)
    cap = np.maximum(np.asarray(cap, np.int64), 1)
    q = np.floor_divide(free_num << XQ_BITS, cap)
    return np.clip(q, -2 * XQ_ONE, XQ_ONE)


def exp10_fp_np(x_q):
    """Vectorized Q28 chain — bit-identical to exp10_fp_py (int64 exact)."""
    x_q = np.asarray(x_q, np.int64)
    neg = x_q < 0
    xa = np.abs(x_q)
    acc = np.full(x_q.shape, E_ONE, np.int64)
    for i in range(_CHAIN_LEN):
        bit = (xa >> i) & 1
        f = np.where(bit == 1, np.int64(CHAIN[i]), np.int64(E_ONE))
        acc = (acc * f) >> E_BITS
    recip = np.int64(1 << (2 * E_BITS)) // np.maximum(acc, 1)
    return np.where(neg, recip, acc)


def e27_np(x_q):
    return (exp10_fp_np(x_q) + 1) >> 1


def binpack_fp_from_e(ec: int, em: int) -> int:
    """Q30 BestFit-v3 from the two Q27 exponentials (runtime formula):
    clip(20 - 10**free_cpu - 10**free_mem, 0, 18)/18, as (fit*4)//9."""
    fit = 20 * E27_ONE - int(ec) - int(em)
    fit = max(0, min(18 * E27_ONE, fit))
    return (fit * 4) // 9


# 9 * _NINTH == 2**33 + 1: for 0 <= fit < 2**33, (fit * _NINTH) >> 33 is
# floor(fit / 9) exactly. With fit = 9a + r the product is a * 2**33 +
# (a + r * _NINTH), and the bracket stays under 2**33 while fit does.
_NINTH = ((1 << 33) + 1) // 9


def binpack_q30(fit):
    """``(fit * 4) // 9`` for ``0 <= fit <= 18 * E27_ONE`` (under 2**32,
    so the product stays under 2**62) without a division: the quotient
    and remainder of ``fit`` by 9 from one multiply and one shift, then
    ``4a + (4r) // 9`` with ``(4r) // 9 == (116 r) >> 8`` for r in 0..8.
    Takes an int64 array of numpy or jax, or a Python int; bit for bit
    ``binpack_fp_from_e``'s value (tests/test_intscore.py)."""
    a = (fit * _NINTH) >> 33
    r = fit - 9 * a
    return 4 * a + ((r * 116) >> 8)


def e_sel_py(e_base: int, e_ask: int) -> int:
    """Selection-time Q27 exponential: running-product multiply."""
    return (int(e_base) * int(e_ask)) >> E27_BITS


def anti_fp_py(collisions: int, desired: int) -> int:
    """Q30 job anti-affinity penalty: -(collisions+1)/desired
    (rank.go:509) via the Q45-reciprocal of the (small) desired count."""
    if collisions <= 0:
        return 0
    q = (1 << RECIP_BITS) // max(int(desired), 1)
    return -(((collisions + 1) * q) >> (RECIP_BITS - TERM_BITS))


def spread_targeted_fp_py(d_hund: int, used_count: int, weight: int, sum_w: int) -> int:
    """Q30 targeted spread boost: ((d-u)/d) * (w/sum_w), d in hundredths,
    as ONE exact floor division (the only big division in the spec).

    d_hund < 0 means no target for this value (-1), d_hund == 0 is the
    zero-percent sentinel (-BIG_FP, the host's -inf boost)."""
    if d_hund == 0:
        return -BIG_FP
    if d_hund < 0:
        return -TERM_ONE
    num = (d_hund - 100 * used_count) * weight * TERM_ONE
    den = d_hund * max(sum_w, 1)
    return num // den  # Python floor division (spec: floor semantics)


def even_fp_py(current: int, min_c: int, max_c: int, has_entries: bool) -> int:
    """Q30 even-spread boost (spread.go:178 semantics) via the
    Q45-reciprocal of min_c (a count, <= 2**17)."""
    if not has_entries:
        return 0
    r = (1 << RECIP_BITS) // max(min_c, 1)
    sh = RECIP_BITS - TERM_BITS
    if current != min_c:
        if min_c == 0:
            return -TERM_ONE
        return ((min_c - current) * r) >> sh
    if min_c == max_c:
        return -TERM_ONE
    if min_c == 0:
        return TERM_ONE
    return ((max_c - min_c) * r) >> sh


def aff_fp_py(total_weight: int, sum_abs_weight: int) -> int:
    """Q30 normalized affinity score (rank.go:640): total/sum_abs, exact."""
    if sum_abs_weight == 0:
        return 0
    return (total_weight * TERM_ONE) // sum_abs_weight


def score60_py(terms_sum: int, num_terms: int) -> int:
    """Final comparable score: mean of terms scaled by 60 (exact)."""
    return terms_sum * (60 // max(1, min(5, num_terms)))


def score60_to_float(score60) -> float:
    """Display conversion (metrics only — never used in comparisons)."""
    return float(score60) / (60.0 * TERM_ONE)


# ---------------------------------------------------------------------------
# Packed-mask lanes (the roofline pass-reduction layout).
#
# The scan step's per-node boolean planes ride PACKED layouts so the step
# touches fewer [B, N] arrays per placement:
#
#   feature plane  uint8 [G, N], emitted once per eval by encode:
#                  bit FEAT_FEAS_BIT = class/constraint feasibility,
#                  bit FEAT_AFF_BIT  = affinity presence. One static plane
#                  (and one pick_g pass) instead of two.
#   presence plane uint8 [N], built per step: one bit per optional score
#                  term; num_terms = 1 + population_count(plane) replaces
#                  the chain of four astype(int32) adds.
#   count lanes    int32 [N]: two boolean count planes packed into 16-bit
#                  fields so ONE ring cumsum serves both. int64 packing
#                  would fit wider counts, but int64 prefix sums are
#                  pathologically slow on this backend — int32 lanes are
#                  free and exact while each lane's total stays below
#                  2**15 (n_pad < PACK_COUNT_MAX, asserted by callers).
#
# These helpers are the ONLY sanctioned way to cross a packed boundary
# (nomad-lint's dtype-discipline rule flags raw shift/mask unpacking and
# float promotion of packed planes). They are backend-agnostic: numpy
# arrays at encode time, jax arrays inside the jit'd step.
# ---------------------------------------------------------------------------

PACK_LANE_BITS = 16
PACK_LANE_MASK = (1 << PACK_LANE_BITS) - 1
# counts packed per lane must stay strictly below this (the high lane's
# shifted total must fit int32, and the low lane must never carry)
PACK_COUNT_MAX = 1 << (PACK_LANE_BITS - 1)

FEAT_FEAS_BIT = 0   # class/constraint feasibility
FEAT_AFF_BIT = 1    # affinity presence


def pack_feat_planes(feas, aff_present=None):
    """Pack the per-TG feasibility plane (and, when the eval carries
    affinities, the affinity-presence plane) into ONE uint8 [G, N] bit
    plane. Emitted once per eval at encode time; the cached-encode
    re-dispatch path reuses the packed plane as-is."""
    packed = feas.astype("uint8")
    if aff_present is not None and aff_present.shape[0]:
        packed = packed | (aff_present.astype("uint8") << FEAT_AFF_BIT)
    return packed


def unpack_feat_lane(packed, bit):
    """Boolean lane ``bit`` of a packed feature plane."""
    return ((packed >> bit) & 1).astype(bool)


def pack_presence_lanes(m0, m1, m2, m3):
    """Pack four boolean term-presence planes into one uint8 bit plane;
    ``1 + population_count(plane)`` is the score's num_terms."""
    return (
        m0.astype("uint8")
        | (m1.astype("uint8") << 1)
        | (m2.astype("uint8") << 2)
        | (m3.astype("uint8") << 3)
    )


def pack_count_lanes(lo_mask, hi_mask):
    """Pack two boolean count planes into one int32 plane: ``lo`` in bits
    0..15, ``hi`` in bits 16..30. Prefix sums over the packed plane are
    exact per lane while both totals stay below PACK_COUNT_MAX: neither
    lane can carry into the other, and every ring-cumsum branch is
    lane-wise non-negative."""
    return lo_mask.astype("int32") | (hi_mask.astype("int32") << PACK_LANE_BITS)


def unpack_count_lo(packed):
    """Low 16-bit count lane of a packed (cumsummed) count plane."""
    return packed & PACK_LANE_MASK


def unpack_count_hi(packed):
    """High count lane of a packed (cumsummed) count plane."""
    return packed >> PACK_LANE_BITS
