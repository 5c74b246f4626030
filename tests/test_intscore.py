"""Integer scoring spec (tpu/intscore.py) — platform-independence tests.

The parity claim of the int spec: the device scan's selection decisions
are produced by an exact integer program, so they are BIT-IDENTICAL on
every backend — CPU, TPU, anywhere. These tests assert (a) the numpy
implementation matches the pure-Python oracle value-for-value, (b) the
spec tracks the real-valued math within its documented error budget,
and (c) the full scan produces identical outputs when run on two
different backends in one process (cpu vs the default platform — on a
TPU machine that is the real device-vs-host parity check, with no
float in the comparison path).
"""
import numpy as np
import pytest

from nomad_tpu.tpu import intscore
from nomad_tpu.tpu.engine import _build_place_scan, example_scan_inputs


@pytest.fixture(autouse=True)
def _x64():
    # int64 spec arithmetic needs x64 (the engine builders enable it;
    # standalone helper calls here must too)
    import jax

    jax.config.update("jax_enable_x64", True)


def test_exp10_fp_np_matches_python_oracle():
    rng = np.random.default_rng(7)
    xs = np.concatenate([
        rng.integers(-2 * intscore.XQ_ONE, 2 * intscore.XQ_ONE + 1, 500),
        np.array([0, 1, -1, intscore.XQ_ONE, -2 * intscore.XQ_ONE,
                  2 * intscore.XQ_ONE, intscore.XQ_ONE - 1, -intscore.XQ_ONE]),
    ]).astype(np.int64)
    got = intscore.exp10_fp_np(xs)
    want = np.array([intscore.exp10_fp_py(int(x)) for x in xs], np.int64)
    assert (got == want).all()
    got27 = intscore.e27_np(xs)
    want27 = np.array([intscore.e27_py(int(x)) for x in xs], np.int64)
    assert (got27 == want27).all()
    # Q27 values of CLAMPED x_q fit int32 (the e_base/e_ask arrays are
    # int32; xq_* clamps to [-2, 1])
    xq = intscore.xq_np(xs, np.full_like(xs, intscore.XQ_ONE))
    assert intscore.e27_np(xq).max() < 2**31


def test_exp10_fp_accuracy_and_monotonicity():
    # value check vs true 10**x within the spec's error budget, and
    # monotone in x_q (ordering never inverts from rounding)
    xs = np.linspace(-2 * intscore.XQ_ONE, 2 * intscore.XQ_ONE, 4001).astype(np.int64)
    vals = intscore.exp10_fp_np(xs).astype(np.float64)
    true = 10.0 ** (xs / float(intscore.XQ_ONE)) * intscore.E_ONE
    rel = np.abs(vals - true) / true
    assert rel.max() < 1e-6
    assert (np.diff(vals) >= 0).all()


def test_binpack_from_e_tracks_float_reference():
    # the Q30 binpack term (via Q27 exponentials) stays near float math
    rng = np.random.default_rng(13)
    for _ in range(200):
        cc = int(rng.integers(500, 20000))
        cm = int(rng.integers(500, 40000))
        uc = int(rng.integers(0, cc))
        um = int(rng.integers(0, cm))
        ec = intscore.e27_py(intscore.xq_py(cc - uc, cc))
        em = intscore.e27_py(intscore.xq_py(cm - um, cm))
        fp = intscore.binpack_fp_from_e(ec, em) / intscore.TERM_ONE
        fit = 20.0 - (10.0 ** (1 - uc / cc) + 10.0 ** (1 - um / cm))
        ref = min(max(fit, 0.0), 18.0) / 18.0
        assert abs(fp - ref) < 2.5e-6, (uc, um, cc, cm, fp, ref)


@pytest.mark.parametrize("fits", [
    "every value up to 2**17", "the top of the range", "a million drawn",
    "just past every multiple of 9 * 2**k",
])
def test_binpack_q30_is_the_division_it_replaces(fits):
    """The device's multiply-and-shift form of the Q30 binpack term is,
    over the whole clipped range 0..18*2**27, the spec's (fit*4)//9."""
    top = 18 * intscore.E27_ONE
    fit = {
        "every value up to 2**17": np.arange(1 << 17, dtype=np.int64),
        "the top of the range": np.arange(top - (1 << 17), top + 1,
                                          dtype=np.int64),
        "a million drawn": np.random.default_rng(9).integers(
            0, top + 1, 1_000_000, dtype=np.int64),
        "just past every multiple of 9 * 2**k": np.concatenate([
            np.clip(9 * (np.int64(1) << k) * m + np.arange(-9, 10), 0, top)
            for k in range(28) for m in (1, 2)]).astype(np.int64),
    }[fits]
    np.testing.assert_array_equal(intscore.binpack_q30(fit), (fit * 4) // 9)
    assert intscore.binpack_q30(top) == intscore.TERM_ONE
    for ec, em in ((0, 0), (intscore.E27_ONE, 3 * intscore.E27_ONE),
                   (123456789, 987654321)):
        want = intscore.binpack_fp_from_e(ec, em)
        f = max(0, min(top, 20 * intscore.E27_ONE - ec - em))
        assert intscore.binpack_q30(f) == want


def test_running_product_drift_is_bounded():
    # place/evict the same amounts repeatedly: the Q27 running product
    # must stay within k*2**-26 of the directly-computed exponential
    cap = 8000
    ask = 250
    e = intscore.e27_py(intscore.xq_py(cap, cap))  # empty node
    f_place = intscore.e27_py(intscore.xq_py(-ask, cap))
    f_evict = intscore.e27_py(intscore.xq_py(ask, cap))
    k = 0
    for _ in range(50):
        e = intscore.e_sel_py(e, f_place)
        e = intscore.e_sel_py(e, f_evict)
        k += 2
    direct = intscore.e27_py(intscore.xq_py(cap, cap))
    rel = abs(e - direct) / direct
    assert rel < (k + 4) * 2.0**-24


def test_anti_and_even_recip_precision():
    # Q45-reciprocal terms stay within a few Q30-ulp of the exact ratio
    for c, d in [(0, 5), (1, 5), (7, 3), (1000, 999), (2**17 - 1, 2**17)]:
        got = intscore.anti_fp_py(c, d)
        if c <= 0:
            assert got == 0
            continue
        exact = -((c + 1) * intscore.TERM_ONE) // d
        assert abs(got - exact) <= 8
    for cur, mn, mx in [(3, 1, 5), (1, 1, 5), (0, 0, 4), (10, 2, 10)]:
        got = intscore.even_fp_py(cur, mn, mx, True)
        assert isinstance(got, int)
        if cur != mn and mn > 0:
            exact = ((mn - cur) * intscore.TERM_ONE) // mn
            assert abs(got - exact) <= 8


def _scan_outputs(backend=None):
    import jax

    n_pad, static, carry, xs = example_scan_inputs(
        n_nodes=96, n_tgs=3, n_placements=40, n_spreads=1, dtype=np.int32,
        seed=3,
    )
    scan = _build_place_scan()
    if backend is not None:
        dev = jax.devices(backend)[0]
        static = jax.device_put(static, dev)
        carry = jax.device_put(carry, dev)
        xs = jax.device_put(xs, dev)
    _c, outs = scan(n_pad, static, carry, xs)
    return tuple(np.asarray(o) for o in outs)


def test_scan_cross_backend_bit_identical():
    """cpu vs default platform: identical chosen/scores bit-for-bit.

    Under the test suite both are CPU (trivially equal); on a TPU machine
    (run with JAX_PLATFORMS unset) this is the on-chip parity assertion:
    the device executes the same integer program as the host."""
    import jax

    default = jax.default_backend()
    base = _scan_outputs(backend=None)
    cpu = _scan_outputs(backend="cpu")
    for b, c in zip(base, cpu):
        assert b.dtype == c.dtype
        assert (b == c).all(), f"backend {default} diverged from cpu"


def test_scan_scores_are_exact_spec_values():
    """Every emitted score60 is on the 60-scaled mean grid: divisible by
    60//num_terms for some num_terms in 1..5 (necessary structural
    property of the exact integer normalization)."""
    chosen, scores, pulls, skipped, _evict, _rival = _scan_outputs()
    assert scores.dtype == np.int64
    placed = chosen >= 0
    assert placed.any()
    facs = np.array([12, 15, 20, 30, 60], np.int64)
    for s in scores[placed]:
        assert any(int(s) % int(f) == 0 for f in facs)


def test_chain_constants_are_exact():
    # spot-check the Q28 chain against high-precision references
    from decimal import Decimal, getcontext

    getcontext().prec = 60
    for i in (0, 1, 12, 23, 24, 25):
        exact = Decimal(10) ** (Decimal(2) ** (i - intscore.XQ_BITS))
        want = int((exact * (1 << intscore.E_BITS)).to_integral_value(
            rounding="ROUND_HALF_EVEN"))
        assert intscore.CHAIN[i] == want
    assert intscore.CHAIN[24] == 10 * intscore.E_ONE
    assert intscore.CHAIN[25] == 100 * intscore.E_ONE
