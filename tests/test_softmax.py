"""ITA integer softmax: unit + property tests (paper §IV claims)."""

import jax.numpy as jnp
import numpy as np
import pytest
from compat_hypothesis import given, settings, st

from repro.core import softmax as S
from repro.core.quant import EPS_MAX


def _quantize(x):
    return np.clip(np.round(x / EPS_MAX), -128, 127).astype(np.int8)


def test_oneshot_matches_formula():
    """p = (2^16 // sigma) >> k, sigma = sum 256 >> k (paper eq. 4/5)."""
    x = np.array([[10, -20, 100, 127, -128]], np.int8)
    p, sigma, mx = S.ita_softmax_int(jnp.asarray(x))
    k = (int(x.max()) - x.astype(np.int64)) >> 5
    sig = int((256 >> k).sum())
    assert int(sigma[0, 0]) == sig
    inv = (1 << 16) // sig
    np.testing.assert_array_equal(np.asarray(p)[0], inv >> k[0])


def test_rowsums_bounded():
    rng = np.random.default_rng(1)
    x = _quantize(rng.normal(0, 1.2, (64, 128)))
    p = np.asarray(S.ita_softmax(jnp.asarray(x)))
    sums = p.sum(-1)
    assert np.all(sums <= 1.0 + 1e-6)        # floor-only arithmetic
    assert np.all(sums > 0.05)


def test_shift_invariance():
    """ITA softmax is exactly invariant to a common shift of all inputs
    (k_i depends only on max - x_i)."""
    rng = np.random.default_rng(2)
    x = _quantize(rng.normal(0, 1.0, (8, 64)) - 1.0)
    x = np.clip(x, -100, 90)
    p1 = np.asarray(S.ita_softmax(jnp.asarray(x)))
    p2 = np.asarray(S.ita_softmax(jnp.asarray((x + 30).astype(np.int8))))
    np.testing.assert_array_equal(p1, p2)


def test_monotonicity():
    x = np.arange(-128, 127, 2, np.int8)[None]
    p = np.asarray(S.ita_softmax(jnp.asarray(x)))[0]
    assert np.all(np.diff(p) >= 0)


def test_streaming_equals_oneshot_when_sorted_desc():
    """If the global max arrives in the first part, no correction is ever
    needed and streaming == one-shot exactly."""
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1.0, (16, 256))
    xq = np.sort(_quantize(x), axis=-1)[:, ::-1].copy()
    a = np.asarray(S.ita_softmax(jnp.asarray(xq)))
    b = np.asarray(S.ita_softmax_streaming(jnp.asarray(xq), num_parts=8))
    np.testing.assert_array_equal(a, b)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 4, 8]))
def test_streaming_bounded_error(seed, parts):
    """The paper's multi-part Σ correction can only *overestimate* the
    one-shot Σ, by at most 2^(#max-updates); probabilities stay in [0,1]
    and the MAE to float stays small."""
    rng = np.random.default_rng(seed)
    xq = _quantize(rng.normal(0, 1.0, (8, 128)))
    ps = np.asarray(S.ita_softmax_streaming(jnp.asarray(xq), parts))
    pf = np.asarray(S.softmax_float(jnp.asarray(xq)))
    assert ps.min() >= 0 and ps.max() <= 1.0 + 1e-6
    assert np.abs(ps - pf).mean() < 0.02


def test_mask_zeroes_probabilities():
    rng = np.random.default_rng(4)
    xq = _quantize(rng.normal(0, 1, (8, 64)))
    mask = rng.random((8, 64)) > 0.3
    for fn in (S.ita_softmax, S.ita_softmax_adaptive,
               lambda x, mask: S.ita_softmax_streaming(x, 4, mask=mask)):
        p = np.asarray(fn(jnp.asarray(xq), mask=jnp.asarray(mask)))
        assert np.all(p[~mask] == 0)


def test_fully_masked_row_is_zero():
    xq = jnp.asarray(np.ones((2, 32), np.int8))
    mask = jnp.zeros((2, 32), bool)
    p = np.asarray(S.ita_softmax(xq, mask=mask))
    assert np.all(p == 0)


def test_adaptive_beats_paper_mode_on_long_rows():
    """Beyond-paper: per-row power-of-two scaling fixes the Σ>=2^16
    underflow and improves MAE on long rows."""
    rng = np.random.default_rng(5)
    xq = _quantize(rng.normal(0, 0.6, (16, 2048)))
    pf = np.asarray(S.softmax_float(jnp.asarray(xq)))
    mae_paper = np.abs(np.asarray(S.ita_softmax(jnp.asarray(xq))) - pf).mean()
    mae_adapt = np.abs(
        np.asarray(S.ita_softmax_adaptive(jnp.asarray(xq))) - pf).mean()
    assert mae_adapt < mae_paper


def test_mae_vs_float_in_paper_ballpark():
    """Paper §V-C: ITA MAE 0.46%, I-BERT 0.35% (on CCT activations).
    On a matched synthetic logit distribution both must land < 1% and
    I-BERT must not be wildly different from ITA."""
    rng = np.random.default_rng(6)
    xq = _quantize(rng.normal(0, 1.0, (256, 256)))
    pf = np.asarray(S.softmax_float(jnp.asarray(xq)))
    mae_ita = np.abs(np.asarray(S.ita_softmax(jnp.asarray(xq))) - pf).mean()
    mae_ib = np.abs(S.ibert_softmax_np(xq) - pf).mean()
    assert mae_ita < 0.01
    assert mae_ib < 0.01


def test_ibert_jnp_matches_np():
    rng = np.random.default_rng(7)
    xq = _quantize(rng.normal(0, 1.0, (32, 128)))
    a = np.asarray(S.ibert_softmax(jnp.asarray(xq)))
    b = S.ibert_softmax_np(xq)
    np.testing.assert_allclose(a, b, atol=1e-7)


def test_bitexact_saturation():
    """15-bit Σ saturation: long rows of identical values saturate Σ at
    2^15-1 — probabilities then overestimate (HW-accepted behaviour)."""
    xq = jnp.asarray(np.zeros((1, 512), np.int8))
    p = np.asarray(S.ita_softmax_bitexact(xq, num_parts=4))
    # one-shot wide mode: sigma = 512*256 = 2^17 -> p = (2^16//2^17)=0
    p_wide = np.asarray(S.ita_softmax(xq))
    assert p.sum() > p_wide.sum()


def test_ste_grads_flow():
    import jax
    x = jnp.linspace(-2, 2, 64).reshape(2, 32)
    g = jax.grad(lambda l: S.ita_softmax_ste(l)[0, 0])(x)
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.abs(g).sum()) > 0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_softermax_close_to_float(seed):
    rng = np.random.default_rng(seed)
    xq = _quantize(rng.normal(0, 1.0, (4, 64)))
    pf = np.asarray(S.softmax_float(jnp.asarray(xq)))
    ps = np.asarray(S.softermax(jnp.asarray(xq)))
    assert np.abs(ps - pf).mean() < 5e-3


# ---------------------------------------------------------------------------
# Grid-aligned DA: the fused kernels' arithmetic
# ---------------------------------------------------------------------------

def _ordered(xq, order):
    if order == "ascending":            # the max grows in every part
        return np.sort(xq, axis=-1)
    if order == "descending":           # the max comes first
        return np.sort(xq, axis=-1)[:, ::-1].copy()
    return xq


@pytest.mark.parametrize("order", ["ascending", "descending", "random"])
def test_aligned_streaming_equals_oneshot_any_order(order):
    """Streaming DA on the 2^5 grid composes exactly: probabilities and
    sigma equal the one-shot pass bit for bit, whichever part holds the
    max; masked keys and a fully masked row included."""
    rng = np.random.default_rng(9)
    xq = _ordered(_quantize(rng.normal(0, 1.5, (16, 512))), order)
    mask = rng.random(xq.shape) > 0.1
    mask[3] = False
    x, m = jnp.asarray(xq), jnp.asarray(mask)
    np.testing.assert_array_equal(
        np.asarray(S.ita_softmax_aligned_streaming(x, 16, mask=m)),
        np.asarray(S.ita_softmax_aligned(x, mask=m)))
    carry_b = jnp.full((16, 1), -8, jnp.int32)
    carry_s = jnp.zeros((16, 1), jnp.int32)
    for i in range(16):
        sl = slice(32 * i, 32 * (i + 1))
        carry_b, carry_s = S.ita_da_update_aligned(carry_b, carry_s,
                                                   x[:, sl], m[:, sl])
    _, sigma, row_b = S.ita_softmax_aligned_int(x, mask=m)
    np.testing.assert_array_equal(np.asarray(carry_s), np.asarray(sigma))
    np.testing.assert_array_equal(np.asarray(carry_b), np.asarray(row_b))


def test_paper_streaming_leaves_oneshot_when_max_grows():
    """The paper's multi-part update, kept as the paper defines it: when
    the max grows part by part its floors do not compose and streaming
    leaves the one-shot result; the aligned update does not."""
    rng = np.random.default_rng(10)
    xq = _ordered(_quantize(rng.normal(0, 1.5, (16, 512))), "ascending")
    x = jnp.asarray(xq)
    paper = np.asarray(S.ita_softmax_streaming(x, 16))
    assert (paper != np.asarray(S.ita_softmax(x))).any()
    np.testing.assert_array_equal(
        np.asarray(S.ita_softmax_aligned_streaming(x, 16)),
        np.asarray(S.ita_softmax_aligned(x)))


def test_aligned_oneshot_matches_formula():
    """k = (max >> 5) - (x >> 5), sigma = sum 256 >> k, p = (2^16 //
    sigma) >> k; a row whose max is 127 (bucket edge) equals the paper's
    arithmetic exactly."""
    x = np.array([[10, -20, 100, 126, -128], [10, -20, 100, 127, -128]],
                 np.int8)
    p, sigma, rb = S.ita_softmax_aligned_int(jnp.asarray(x))
    xi = x.astype(np.int64)
    k = (xi.max(-1, keepdims=True) >> 5) - (xi >> 5)
    sig = (256 >> k).sum(-1)
    np.testing.assert_array_equal(np.asarray(sigma)[:, 0], sig)
    np.testing.assert_array_equal(np.asarray(rb)[:, 0], [3, 3])
    np.testing.assert_array_equal(np.asarray(p),
                                  ((1 << 16) // sig)[:, None] >> k)
    np.testing.assert_array_equal(
        np.asarray(p)[1], np.asarray(S.ita_softmax_int(jnp.asarray(x))[0])[1])


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 4, 8, 16]))
def test_aligned_streaming_mae_matches_paper_oneshot(seed, parts):
    """The aligned arithmetic is no coarser than the paper's: one factor
    of two per bucket. Over rows of logits at a realistic spread its MAE
    to float stays within 1.5x of the one-shot paper softmax's, and it
    equals its own one-shot result at any part count."""
    rng = np.random.default_rng(seed)
    xq = jnp.asarray(_quantize(rng.normal(0, 1.0, (16, 256))))
    pa = np.asarray(S.ita_softmax_aligned_streaming(xq, parts))
    np.testing.assert_array_equal(pa, np.asarray(S.ita_softmax_aligned(xq)))
    pf = np.asarray(S.softmax_float(xq))
    mae_paper = np.abs(np.asarray(S.ita_softmax(xq)) - pf).mean()
    assert np.abs(pa - pf).mean() < 1.5 * mae_paper
