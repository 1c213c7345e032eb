"""ITA integer streaming softmax + baselines (I-BERT, Softermax, float).

The paper's key algorithm (§IV): with the *maximum meaningful* quantization
scale ``eps = B/(2**B * log2 e)`` (B = 8), the softmax exponent in base 2
becomes a pure right shift::

    e^(eps * x_q) = 2^(eps' * x_q),  eps' = B/2**B = 2**-5
    2^(eps' (x_q - max)) = 2^(-((max - x_q) >> 5))

so each denominator term is ``256 >> k`` with ``k = (max - x_q) >> 5`` (the
top 3 bits of the 8-bit difference), and normalization is a shift of the
inverted denominator: ``p_i = sigma_inv >> k_i`` (paper eq. 5).

Three phases map onto the attention dataflow:

- **DA** (denominator accumulation): running row max + running sum while the
  ``Q K^T`` tiles stream by; a late max update corrects the accumulated sum
  with ``sigma >>= (delta_max >> 5)`` — the paper's multi-part row update.
- **DI** (denominator inversion): once per row, ``sigma_inv = 2^16 // sigma``
  (two serial dividers in silicon; one integer divide per row here).
- **EN** (element normalization): fused into the ``A V`` pass, pure shifts.

Modes implemented here (pure jnp references; Pallas kernels in
``repro/kernels`` are validated against these):

- ``ita_softmax``            one-shot, paper semantics, int32 accumulators
                             ("wide mode" — the 15-bit HW accumulator is a
                             gate-count constraint, not algorithmic).
- ``ita_softmax_streaming``  tiled DA/DI/EN with the paper's max-correction.
- ``ita_softmax_aligned``    beyond-paper: the exponent taken on the 2^5
                             grid (``(max >> 5) - (x >> 5)``), so streaming
                             DA (``ita_softmax_aligned_streaming``) equals
                             one-shot exactly — the fused attention
                             kernels' arithmetic.
- ``ita_softmax_bitexact``   15-bit sigma / 16-bit sigma_inv silicon
                             semantics (validates the paper's MAE claim).
- ``ita_softmax_adaptive``   beyond-paper: per-row power-of-two output scale
                             (still shift-only) so rows of length >> 256
                             don't underflow the fixed 2^-8 output grid.
- ``ibert_softmax``          I-BERT 32-bit integer softmax (accuracy
                             baseline the paper compares against).
- ``softermax``              base-2 fixed-point softmax (Softermax/Keller).
- ``softmax_float``          float oracle.
- ``ita_softmax_ste``        differentiable QAT forward with straight-
                             through floors (the paper trains the clipping
                             range with QAT incorporating this softmax).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import B_BITS, EPS_MAX, SOFTMAX_SHIFT

# 2**8 — the unit in which denominator terms are accumulated.
_UNIT = 1 << B_BITS
# Paper's denominator-inversion width: sigma_inv = 2**16 // sigma.
_W_INV = 2 * B_BITS
# Shift amount for masked-out elements: forces the term/probability to 0.
_MASK_K = 31


def _k_of(x_q: jax.Array, row_max: jax.Array) -> jax.Array:
    """Exponent shift k = (max - x) >> 5 (top-3-bits of the 8-bit diff)."""
    diff = row_max.astype(jnp.int32) - x_q.astype(jnp.int32)
    return jax.lax.shift_right_logical(diff, SOFTMAX_SHIFT)


def _apply_mask_k(k: jax.Array, mask: jax.Array | None) -> jax.Array:
    if mask is None:
        return k
    return jnp.where(mask, k, _MASK_K)


# Sentinel below any int8 value; small enough that (max - sentinel) cannot
# overflow int32 (unlike INT32_MIN).
_NEG_SENTINEL = -(2 ** B_BITS)


def _masked_max(x_q: jax.Array, mask: jax.Array | None, axis: int) -> jax.Array:
    x = x_q.astype(jnp.int32)
    if mask is not None:
        x = jnp.where(mask, x, jnp.int32(_NEG_SENTINEL))
    return jnp.max(x, axis=axis, keepdims=True)


def ita_softmax_int(x_q: jax.Array, mask: jax.Array | None = None,
                    axis: int = -1):
    """One-shot ITA softmax. Returns ``(p, sigma, row_max)`` where ``p`` is
    the int32 probability in units of 2^-8 (i.e. ``p/256 ~= softmax``).

    ``p`` fits in 9 bits (max 256 when one element dominates); the uint8 HW
    representation clips 256 -> 255 which callers apply when packing.
    """
    row_max = _masked_max(x_q, mask, axis)
    k = _apply_mask_k(_k_of(x_q, row_max), mask)
    terms = jax.lax.shift_right_logical(jnp.int32(_UNIT), jnp.minimum(k, 31))
    sigma = jnp.sum(terms, axis=axis, keepdims=True)           # DA
    sigma = jnp.maximum(sigma, 1)
    sigma_inv = (jnp.int32(1) << _W_INV) // sigma              # DI
    p = jax.lax.shift_right_logical(sigma_inv, jnp.minimum(k, 31))  # EN
    # Identity on every reachable value (a live row has sigma >= 256 >> k_i
    # for each of its elements, so sigma_inv >> k_i <= 256; a fully masked
    # row shifts by _MASK_K and gets 0) — stated structurally so the range
    # verifier can bound the downstream p*V accumulator non-relationally.
    p = jnp.minimum(p, _UNIT)
    return p, sigma, row_max


def ita_softmax(x_q: jax.Array, mask: jax.Array | None = None,
                axis: int = -1) -> jax.Array:
    """ITA softmax as float probabilities (p * 2^-8)."""
    p, _, _ = ita_softmax_int(x_q, mask=mask, axis=axis)
    return p.astype(jnp.float32) * (2.0 ** -B_BITS)


# ---------------------------------------------------------------------------
# Streaming (DA across row parts) — the paper's multi-part update
# ---------------------------------------------------------------------------

def ita_da_update(carry_max: jax.Array, carry_sigma: jax.Array,
                  part_q: jax.Array, part_mask: jax.Array | None = None,
                  axis: int = -1):
    """One DA step: fold a new row part into (running max, running sigma).

    Matches the silicon behaviour exactly: when the max grows, the *already
    accumulated* sigma is corrected with a single shift ``(delta_max >> 5)``
    — the floor interacts with previously floored terms, so streaming sigma
    can overestimate the one-shot sigma by at most ``2**(number of max
    updates)`` (typically it is equal; bounded-error property is tested).
    """
    part_max = _masked_max(part_q, part_mask, axis)
    new_max = jnp.maximum(carry_max, part_max)
    delta = jax.lax.shift_right_logical(
        (new_max - carry_max).astype(jnp.int32), SOFTMAX_SHIFT)
    corrected = jax.lax.shift_right_logical(carry_sigma, jnp.minimum(delta, 31))
    k = _apply_mask_k(_k_of(part_q, new_max), part_mask)
    terms = jax.lax.shift_right_logical(jnp.int32(_UNIT), jnp.minimum(k, 31))
    part_sigma = jnp.sum(terms, axis=axis, keepdims=True)
    return new_max, corrected + part_sigma


def ita_softmax_streaming(x_q: jax.Array, num_parts: int,
                          mask: jax.Array | None = None) -> jax.Array:
    """Full DA -> DI -> EN over ``num_parts`` chunks of the last axis."""
    *lead, n = x_q.shape
    assert n % num_parts == 0, (n, num_parts)
    part = n // num_parts
    run_max = jnp.full((*lead, 1), _NEG_SENTINEL, jnp.int32)
    run_sigma = jnp.zeros((*lead, 1), jnp.int32)
    for i in range(num_parts):                                   # DA
        sl = slice(i * part, (i + 1) * part)
        m = None if mask is None else mask[..., sl]
        run_max, run_sigma = ita_da_update(run_max, run_sigma, x_q[..., sl], m)
    sigma = jnp.maximum(run_sigma, 1)
    sigma_inv = (jnp.int32(1) << _W_INV) // sigma                # DI
    k = _apply_mask_k(_k_of(x_q, run_max), mask)                 # EN
    p = jax.lax.shift_right_logical(sigma_inv, jnp.minimum(k, 31))
    return p.astype(jnp.float32) * (2.0 ** -B_BITS)


# ---------------------------------------------------------------------------
# Grid-aligned DA — the fused attention kernels' arithmetic (beyond paper)
# ---------------------------------------------------------------------------
#
# The paper's streaming correction does not compose: a key shifted by
# ``(m_old - x) >> 5`` and later by ``(m_new - m_old) >> 5`` can end up one
# short of ``(m_new - x) >> 5`` per max update (floor(a) + floor(b) <=
# floor(a + b)), and Σ's shift drops bits. Over a long context whose max
# keeps growing that biases every earlier key upwards, by up to 2x per
# update. Taking the exponent on the 2^5 grid — ``k = (max >> 5) - (x >> 5)``,
# the difference of the two logits' 3-bit buckets — makes every update a
# whole number of buckets, so a late correction composes exactly with the
# earlier shifts: streaming Σ (and the kernels' A·V accumulator) equal the
# one-shot values bit for bit, whatever order the maxima arrive in. The
# quantisation is the paper's (one factor of two per bucket), with the
# bucket edges fixed on the logit grid instead of hung from the row max.

# The masked-logit sentinel's bucket: below every live bucket [-4, 3].
_NEG_BUCKET = _NEG_SENTINEL >> SOFTMAX_SHIFT


def ita_bucket(x_q: jax.Array) -> jax.Array:
    """``x >> 5`` (arithmetic): a logit's exponent bucket, in [-4, 3] for
    int8 logits."""
    return jax.lax.shift_right_arithmetic(x_q.astype(jnp.int32),
                                          jnp.int32(SOFTMAX_SHIFT))


def _aligned_k(xb: jax.Array, row_bucket: jax.Array,
               mask: jax.Array | None) -> jax.Array:
    """k = row bucket - key bucket, in [0, 7] on live keys. The clip is an
    identity on every reachable value (the row's bucket is the largest
    live one), stated so the range verifier proves the shift legal."""
    return _apply_mask_k(jnp.clip(row_bucket - xb, 0, _MASK_K), mask)


def _masked_bucket_max(xb, mask, axis):
    if mask is not None:
        xb = jnp.where(mask, xb, jnp.int32(_NEG_BUCKET))
    return jnp.max(xb, axis=axis, keepdims=True)


def ita_softmax_aligned_int(x_q: jax.Array, mask: jax.Array | None = None,
                            axis: int = -1):
    """One-shot grid-aligned ITA softmax. Returns ``(p, sigma, row_bucket)``
    with ``p`` in units of 2^-8, as ``ita_softmax_int``; ``sigma`` sums the
    ``256 >> k`` terms with ``k = row_bucket - (x >> 5)``."""
    xb = ita_bucket(x_q)
    row_bucket = _masked_bucket_max(xb, mask, axis)
    k = _aligned_k(xb, row_bucket, mask)
    terms = jax.lax.shift_right_logical(jnp.int32(_UNIT), k)
    sigma = jnp.sum(terms, axis=axis, keepdims=True)            # DA
    sigma_inv = (jnp.int32(1) << _W_INV) // jnp.maximum(sigma, 1)  # DI
    p = jnp.minimum(jax.lax.shift_right_logical(sigma_inv, k), _UNIT)  # EN
    return p, sigma, row_bucket


def ita_softmax_aligned(x_q: jax.Array, mask: jax.Array | None = None,
                        axis: int = -1) -> jax.Array:
    """Grid-aligned ITA softmax as float probabilities (p * 2^-8)."""
    p, _, _ = ita_softmax_aligned_int(x_q, mask=mask, axis=axis)
    return p.astype(jnp.float32) * (2.0 ** -B_BITS)


def ita_da_update_aligned(carry_bucket: jax.Array, carry_sigma: jax.Array,
                          part_q: jax.Array,
                          part_mask: jax.Array | None = None,
                          axis: int = -1):
    """One exact streaming DA step on the 2^5 grid: fold a row part into
    (running max bucket, running sigma). Every term already in ``sigma``
    is ``2^(8 - k)`` with its final ``k <= 7``, so ``sigma >> delta`` drops
    no bit and the result equals the one-shot sigma of the parts so far.
    Start from ``carry_bucket = -8`` (the sentinel's bucket), sigma 0."""
    xb = ita_bucket(part_q)
    new_bucket = jnp.maximum(carry_bucket,
                             _masked_bucket_max(xb, part_mask, axis))
    delta = jnp.clip(new_bucket - carry_bucket, 0, _MASK_K)
    k = _aligned_k(xb, new_bucket, part_mask)
    terms = jax.lax.shift_right_logical(jnp.int32(_UNIT), k)
    sigma = jax.lax.shift_right_logical(carry_sigma, delta) \
        + jnp.sum(terms, axis=axis, keepdims=True)
    return new_bucket, sigma


def ita_softmax_aligned_streaming(x_q: jax.Array, num_parts: int,
                                  mask: jax.Array | None = None
                                  ) -> jax.Array:
    """DA over ``num_parts`` chunks of the last axis with the grid-aligned
    update, then DI and EN: equal to ``ita_softmax_aligned`` exactly."""
    *lead, n = x_q.shape
    assert n % num_parts == 0, (n, num_parts)
    part = n // num_parts
    run_bucket = jnp.full((*lead, 1), _NEG_BUCKET, jnp.int32)
    run_sigma = jnp.zeros((*lead, 1), jnp.int32)
    for i in range(num_parts):                                   # DA
        sl = slice(i * part, (i + 1) * part)
        m = None if mask is None else mask[..., sl]
        run_bucket, run_sigma = ita_da_update_aligned(
            run_bucket, run_sigma, x_q[..., sl], m)
    sigma_inv = (jnp.int32(1) << _W_INV) // jnp.maximum(run_sigma, 1)
    k = _aligned_k(ita_bucket(x_q), run_bucket, mask)            # EN
    p = jax.lax.shift_right_logical(sigma_inv, k)
    return p.astype(jnp.float32) * (2.0 ** -B_BITS)


# ---------------------------------------------------------------------------
# Bit-exact silicon mode (15-bit sigma, 16-bit sigma_inv)
# ---------------------------------------------------------------------------

def ita_softmax_bitexact(x_q: jax.Array, num_parts: int = 1,
                         mask: jax.Array | None = None) -> jax.Array:
    """Paper-silicon semantics: sigma saturates at 2^15-1, sigma_inv at
    2^16-1. Valid for rows up to ~128 max-valued elements (the compact-
    transformer regime the paper targets); used to validate the MAE claim."""
    *lead, n = x_q.shape
    part = n // num_parts
    run_max = jnp.full((*lead, 1), _NEG_SENTINEL, jnp.int32)
    run_sigma = jnp.zeros((*lead, 1), jnp.int32)
    for i in range(num_parts):
        sl = slice(i * part, (i + 1) * part)
        m = None if mask is None else mask[..., sl]
        run_max, run_sigma = ita_da_update(run_max, run_sigma, x_q[..., sl], m)
        run_sigma = jnp.minimum(run_sigma, (1 << 15) - 1)        # 15-bit sat
    sigma = jnp.maximum(run_sigma, 1)
    sigma_inv = jnp.minimum((jnp.int32(1) << _W_INV) // sigma, (1 << 16) - 1)
    k = _apply_mask_k(_k_of(x_q, run_max), mask)
    p = jax.lax.shift_right_logical(sigma_inv, jnp.minimum(k, 31))
    return p.astype(jnp.float32) * (2.0 ** -B_BITS)


# ---------------------------------------------------------------------------
# Beyond-paper: adaptive per-row power-of-two scale (still shift-only)
# ---------------------------------------------------------------------------

def ita_softmax_adaptive_int(x_q: jax.Array, mask: jax.Array | None = None,
                             axis: int = -1, aligned: bool = False):
    """ITA softmax with a per-row power-of-two output scale.

    The paper's fixed ``sigma_inv = 2^16/sigma`` underflows to 0 when
    ``sigma >= 2^16`` (rows longer than ~256 with flat scores) — an inherent
    8-bit-probability limitation. We pick the row exponent
    ``e_r = floor(log2 sigma)`` and compute ``sigma_inv = 2^(e_r+8)/sigma``
    in (128, 256], so ``softmax ~= p * 2^-e_r``. All operations remain
    shifts + one divide; the per-row 2^-e_r folds into the A.V output
    requant as a row shift. Returns ``(p, e_r, row_max)``. ``aligned``
    takes the exponent on the 2^5 grid (``ita_softmax_aligned_int``; then
    ``row_max`` is the row's max bucket).
    """
    if aligned:
        xb = ita_bucket(x_q)
        row_max = _masked_bucket_max(xb, mask, axis)
        k = _aligned_k(xb, row_max, mask)
    else:
        row_max = _masked_max(x_q, mask, axis)
        k = _apply_mask_k(_k_of(x_q, row_max), mask)
    terms = jax.lax.shift_right_logical(jnp.int32(_UNIT), jnp.minimum(k, 31))
    sigma = jnp.maximum(jnp.sum(terms, axis=axis, keepdims=True), 1)
    e_r = 31 - jax.lax.clz(sigma)                         # floor(log2 sigma)
    # 2^(e_r+8)/sigma without 64-bit: pre-shift sigma so the dividend fits.
    pre = jnp.maximum(e_r + B_BITS - 30, 0)
    sigma_inv = (jnp.int32(1) << jnp.minimum(e_r + B_BITS - pre, 30)) \
        // jax.lax.shift_right_logical(sigma, pre)
    # Identity clamp: 2^e_r <= sigma forces the quotient into (128, 256],
    # but that bound is relational — state it structurally for the range
    # verifier (mirrors kernels/common.py::adaptive_inverse).
    sigma_inv = jnp.minimum(sigma_inv, _UNIT)
    p = jax.lax.shift_right_logical(sigma_inv, jnp.minimum(k, 31))
    return p, e_r, row_max


def ita_softmax_adaptive(x_q: jax.Array, mask: jax.Array | None = None,
                         axis: int = -1) -> jax.Array:
    p, e_r, _ = ita_softmax_adaptive_int(x_q, mask=mask, axis=axis)
    return p.astype(jnp.float32) * jnp.exp2(-e_r.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------

def softmax_float(x_q: jax.Array, eps: float = EPS_MAX,
                  mask: jax.Array | None = None, axis: int = -1) -> jax.Array:
    """Float oracle: softmax of the dequantized inputs."""
    x = x_q.astype(jnp.float32) * eps
    if mask is not None:
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


# I-BERT (Kim et al., ICML'21) integer softmax — the paper's accuracy
# baseline (MAE 0.35% vs ITA's 0.46%). Faithful port of the reference
# implementation, which stores integer values in float tensors.
_IBERT_COEF = (0.35815147, 0.96963238, 1.0)   # a(x+b)^2 + c, normalized
_IBERT_N = 30
_IBERT_X0 = -0.6931471805599453               # -ln 2


def _ibert_int_polynomial(x_int, scale):
    b_int = np.floor(_IBERT_COEF[1] / _IBERT_COEF[0] / scale)
    c_int = np.floor(_IBERT_COEF[2] / _IBERT_COEF[0] / scale ** 2)
    z = (x_int + b_int) * x_int + c_int
    return z, _IBERT_COEF[0] * scale ** 2


def _ibert_int_exp(x_int, scale):
    x0_int = np.floor(_IBERT_X0 / scale)
    x_int = jnp.maximum(x_int, _IBERT_N * x0_int)
    q = jnp.floor_divide(x_int, x0_int)
    r = x_int - x0_int * q
    exp_int, exp_scale = _ibert_int_polynomial(r, scale)
    exp_int = jnp.clip(jnp.floor(exp_int * jnp.exp2(_IBERT_N - q)), 0, None)
    return exp_int, exp_scale / 2 ** _IBERT_N


def ibert_softmax(x_q: jax.Array, eps: float = EPS_MAX,
                  mask: jax.Array | None = None, axis: int = -1,
                  output_bit: int = 8) -> jax.Array:
    """I-BERT IntSoftmax. Inputs int8 (cast up); internals 32-bit integers
    held in f32 (as in the reference implementation).

    Includes the reference code's 16-bit ``QuantAct`` requantization of the
    exponent before summation (``self.act``) — without it the 2^32/sum
    inversion underflows. Since ``exp(x - max) <= 1`` the 16-bit scale is
    the constant ``1/(2^15 - 1)``.
    """
    x_int = x_q.astype(jnp.float32)
    if mask is not None:
        x_int = jnp.where(mask, x_int, jnp.min(x_int) - 1e4)
    x_int = x_int - jnp.max(x_int, axis=axis, keepdims=True)
    exp_int, exp_scale = _ibert_int_exp(x_int, eps)
    # QuantAct(16): requantize exp to 16-bit symmetric (max real value is 1).
    exp16 = jnp.floor(exp_int * exp_scale * (2.0 ** 15 - 1))
    if mask is not None:
        exp16 = jnp.where(mask, exp16, 0.0)
    exp_sum = jnp.sum(exp16, axis=axis, keepdims=True)
    factor = jnp.floor(2.0 ** 32 / jnp.maximum(exp_sum, 1.0))
    out = jnp.floor(exp16 * factor / 2.0 ** (32 - output_bit))
    return out / 2.0 ** output_bit


def ibert_softmax_np(x_q: np.ndarray, eps: float = EPS_MAX,
                     output_bit: int = 8) -> np.ndarray:
    """Exact int64 version (numpy) of I-BERT softmax for MAE tables."""
    x_int = x_q.astype(np.int64)
    x_int = x_int - x_int.max(axis=-1, keepdims=True)
    x0_int = np.int64(np.floor(_IBERT_X0 / eps))
    x_int = np.maximum(x_int, _IBERT_N * x0_int)
    q = np.floor_divide(x_int, x0_int)
    r = x_int - x0_int * q
    b_int = np.int64(np.floor(_IBERT_COEF[1] / _IBERT_COEF[0] / eps))
    c_int = np.int64(np.floor(_IBERT_COEF[2] / _IBERT_COEF[0] / eps ** 2))
    poly = (r + b_int) * r + c_int
    exp_int = np.clip(poly * (np.int64(1) << (_IBERT_N - q).astype(np.int64)
                              ).astype(np.int64), 0, None)
    # QuantAct(16) requant (see jnp version); exact integer arithmetic here.
    exp_scale = _IBERT_COEF[0] * eps ** 2 / 2 ** _IBERT_N
    exp16 = np.floor(exp_int.astype(np.float64) * exp_scale * (2.0 ** 15 - 1)
                     ).astype(np.int64)
    exp_sum = exp16.sum(axis=-1, keepdims=True)
    factor = (np.int64(1) << 32) // np.maximum(exp_sum, 1)
    out = (exp16 * factor) >> np.int64(32 - output_bit)
    return out.astype(np.float64) / 2.0 ** output_bit


def softermax(x_q: jax.Array, eps: float = EPS_MAX, frac_bits: int = 8,
              mask: jax.Array | None = None, axis: int = -1) -> jax.Array:
    """Softermax (Stevens et al., DAC'21): base-2 softmax with running max
    in fixed point. Re-implemented here as a related-work baseline: exponent
    ``2^(eps' * (x - max))`` evaluated in Q(frac_bits) fixed point."""
    eps_p = eps * np.log2(np.e)
    x = x_q.astype(jnp.float32)
    if mask is not None:
        x = jnp.where(mask, x, -jnp.inf)
    z = (x - jnp.max(x, axis=axis, keepdims=True)) * eps_p
    pow2 = jnp.floor(jnp.exp2(z) * 2 ** frac_bits)        # fixed-point 2^z
    denom = jnp.maximum(jnp.sum(pow2, axis=axis, keepdims=True), 1.0)
    return pow2 / denom


# ---------------------------------------------------------------------------
# Differentiable QAT forward (straight-through floors)
# ---------------------------------------------------------------------------

def _ste_round(x):
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def _ste_floor(x):
    return x + jax.lax.stop_gradient(jnp.floor(x) - x)


def ita_softmax_ste(logits: jax.Array, eps: float = EPS_MAX,
                    mask: jax.Array | None = None, axis: int = -1) -> jax.Array:
    """QAT forward matching the deployed integer pipeline.

    Quantizes logits to the int8 grid (STE round + clip), floors the
    exponent shift (STE), and normalizes in float. Training through this
    forward learns the clipping range the paper obtains via QAT.
    """
    q = jnp.clip(_ste_round(logits / eps), -128, 127)
    if mask is not None:
        # keep everything finite for clean STE gradients; masked elements
        # are zeroed multiplicatively below
        qm = jnp.where(mask, q, jax.lax.stop_gradient(
            jnp.min(q, axis=axis, keepdims=True)))
    else:
        qm = q
    kf = _ste_floor((jnp.max(qm, axis=axis, keepdims=True) - qm)
                    / 2.0 ** SOFTMAX_SHIFT)
    w = jnp.exp2(-jnp.clip(kf, 0.0, 30.0))
    if mask is not None:
        w = w * mask.astype(w.dtype)
    return w / jnp.maximum(jnp.sum(w, axis=axis, keepdims=True), 1e-9)
