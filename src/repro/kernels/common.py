"""Shared helpers for the ITA Pallas kernels (mask/index math, DA update,
interpret-mode resolution)."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.quant import SOFTMAX_SHIFT

# --- Declared integer bounds of the ITA softmax pipeline -------------------
# These are the named facts the jaxpr range verifier (``repro.analysis``)
# consumes; every bound below is re-proven per kernel on every CI run, so
# changing one without updating the kernels fails the analysis gate.
#
# NEG_SENTINEL: the masked-logit fill. One below INT8_MIN*2, so it is
#   (a) strictly below any real requantized logit (int8 grid), and
#   (b) small enough that ``new_max - x <= 127 - (-256) = 383`` keeps the
#   DA shift argument ``k = 383 >> SOFTMAX_SHIFT = 11`` well inside
#   [0, 31] *before* the explicit min(k, 31) clamp — the subtraction can
#   never approach int32 overflow.
NEG_SENTINEL = -256
# MASK_K: shift applied to masked elements; 128 >> 31 == 0, so a masked
#   element contributes exactly nothing to sigma. Also the largest legal
#   int32 shift, which is why every DA shift amount is clamped to it.
MASK_K = 31
# U_MAX: the DA numerator ``u = 128 >> k`` is at most 128 (k == 0, the
#   row max itself). A (bq, bkv) tile therefore adds at most
#   ``2 * bkv * U_MAX`` to sigma per DA step.
U_MAX = 128
# SIGMA_INV_MAX: both DI variants produce a reciprocal in [0, 256]:
#   paper:    2^16 // sigma with sigma >= 2*U_MAX = 256 once any element
#             is live (the row max contributes u = 128, doubled), so
#             2^16 // 256 = 256 = SIGMA_INV_MAX; an all-masked row has
#             sigma == 0 -> max(sigma, 1) -> 65536, which the EN pass
#             never uses (its p is multiplied by an all-zero mask) but
#             *is* the true paper_inverse range — see PAPER_INV_MAX.
#   adaptive: 2^(e_r+8) // sigma with 2^e_r <= sigma (e_r = floor(log2
#             sigma)) gives a quotient in (128, 256]. The bound is
#             *relational* (it needs 2^e_r <= sigma), which a
#             non-relational interval analyzer cannot derive, so
#             ``adaptive_inverse`` carries an identity ``clip(.., 0,
#             SIGMA_INV_MAX)`` to make it structural.
SIGMA_INV_MAX = 256
# PAPER_INV_MAX: the raw paper DI range before the EN shift, reached only
#   on all-masked rows (sigma clamped to 1): 2^16. The EN pass bound
#   ``p = sigma_inv >> k <= PAPER_INV_MAX`` is what sizes the p*V int8
#   accumulator: bkv * PAPER_INV_MAX * 127 < 2^31 holds for bkv <= 256.
PAPER_INV_MAX = 1 << 16

# Per-backend block-size defaults, chosen by the
# ``benchmarks/bench_kernels.py --sweep`` grid (VMEM working set stays
# within one core's budget at d<=128 while the kv tile amortizes the DA
# bookkeeping; the decode kernel has no q tiling — block_q is None).
# Attention backends record (block_q, block_kv); ``int8_matmul`` records
# (block_m, block_n, block_k) — its sweep column of the same grid run.
# These replace the hardcoded defaults that used to live in
# ``attention/backends.py`` / ``int8_matmul/ops.py``; explicit
# ``block_*=`` call arguments still override per call.
BLOCK_DEFAULTS = {
    "ita_onepass_pallas": (128, 128),
    "ita_twopass_pallas": (128, 128),
    "ita_decode_pallas": (None, 128),
    "int8_matmul": (256, 128, 128),
}

# Rings/pools allocated at a multiple of this never hit the `_pad_seq`
# per-step pad-copy in the fused-attention plumbing (any block_kv that
# divides it stays pad-free). ``KVCacheState.init`` block-aligns
# capacities above one block to it.
MIN_BLOCK_KV = 128

# Vector lanes of a TPU tile: the paged KV pool's minor (head) dim is
# padded to a multiple of it (``PagedKVState.init``). A row-major int8
# ``(page, hd)`` page occupies whole 128-lane tiles in HBM whatever its
# ``hd``, so the padding costs no memory over the row-major layout the
# paged kernels read; without it, XLA's default layout for ``hd < 128``
# puts ``hd`` major (more compact, and unreadable by the kernels), and
# every program that hands the pool to a kernel copies it whole.
LANES = 128


def default_blocks(backend: str) -> tuple:
    """(block_q, block_kv) defaults for a fused *attention* backend name
    (the matmul entry records three sizes — use ``default_matmul_blocks``)."""
    blocks = BLOCK_DEFAULTS.get(backend, (128, 128))
    assert len(blocks) == 2, \
        f"{backend!r} records {len(blocks)} block sizes, not (bq, bkv); " \
        f"use default_matmul_blocks() for the matmul kernel"
    return blocks


def default_matmul_blocks() -> tuple:
    """(block_m, block_n, block_k) defaults for the int8 matmul kernel."""
    return BLOCK_DEFAULTS["int8_matmul"]

# Platforms with a compiled Pallas lowering; everything else (CPU CI
# containers) runs the kernels in interpret mode.
_COMPILED_PALLAS_PLATFORMS = ("tpu", "gpu")


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve the Pallas ``interpret`` flag.

    ``None`` (the default everywhere) means *auto*: interpret only when
    the detected JAX backend has no compiled Pallas lowering — so the
    fused kernels never silently run in slow interpret mode on capable
    hardware. The ``ITA_PALLAS_INTERPRET`` env var (``1``/``0``,
    ``true``/``false``) overrides auto-detection; an explicit bool
    argument wins over both.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("ITA_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "")
    return jax.default_backend() not in _COMPILED_PALLAS_PLATFORMS


def tile_mask(q_tile: jax.Array, kv_tile: jax.Array, bq: int, bkv: int,
              causal: bool, window: int, kv_len: jax.Array | None,
              q_offset: jax.Array | int = 0,
              q_len: jax.Array | int | None = None):
    """Validity mask (bq, bkv) for a (q_tile, kv_tile) grid cell, computed
    from indices so the EN pass never relies on sentinel logit values.

    ``window > 0`` selects sliding-window attention (Mixtral/Gemma-local):
    key j is visible from query i iff ``i - window < j <= i``.
    ``q_offset`` shifts the queries' logical positions (decode: the new
    token lives at position ``kv_len - 1``, not 0).
    ``q_len`` masks *query rows* beyond a row's valid count (ragged
    q_len: a mixed chunked-prefill/decode batch where one kernel call
    carries rows with different real query widths — pad rows come out
    all-masked, sigma 0, output 0).
    """
    qli = q_tile * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    qi = q_offset + qli
    kj = kv_tile * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    valid = jnp.ones((bq, bkv), jnp.bool_)
    if causal or window > 0:
        valid &= qi >= kj
    if window > 0:
        valid &= (qi - kj) < window
    if kv_len is not None:
        valid &= kj < kv_len
    if q_len is not None:
        valid &= qli < q_len
    return valid


def da_update(m_ref, sigma_ref, logits_i32: jax.Array, valid: jax.Array):
    """One streaming DA step over a (bq, bkv) logits tile.

    Updates the per-row running max and running denominator stored in the
    (bq, 1) scratch refs and returns ``(u8 numerator tile, delta_shift)``
    where ``u = 128 >> k`` (int32, fits int8 for the MXU) and
    ``delta_shift`` is the correction shift the caller must apply to any
    value accumulated under the previous max (paper's multi-part update).
    """
    x = jnp.where(valid, logits_i32, NEG_SENTINEL)
    part_max = jnp.max(x, axis=1, keepdims=True)
    new_max = jnp.maximum(m_ref[...], part_max)
    delta = jnp.minimum(
        jax.lax.shift_right_logical(new_max - m_ref[...], SOFTMAX_SHIFT), 31)
    k = jax.lax.shift_right_logical(new_max - logits_i32, SOFTMAX_SHIFT)
    k = jnp.where(valid, jnp.minimum(k, 31), MASK_K)
    u = jax.lax.shift_right_logical(jnp.int32(128), k)       # 128 >> k
    # sigma accumulates the paper's 2^(8-k) = 2*u terms.
    sigma_ref[...] = jax.lax.shift_right_logical(sigma_ref[...], delta) \
        + 2 * jnp.sum(u, axis=1, keepdims=True)
    m_ref[...] = new_max
    return u, delta


def pow2_neg(n: jax.Array) -> jax.Array:
    """``2^-n`` in float32, exactly, for integer ``n`` in [0, 40] (the DA
    correction shift ``delta <= 31`` and the DI scale ``e_r + 8 <= 38``).
    Built from integer shifts because ``jnp.exp2`` rounds: XLA lowers it
    to ``exp(n * ln 2)``, which is off by an ulp from ``n = 13`` on the
    CPU, and a compiled kernel need not round like the interpreter.
    ``2^(20-a) * 2^(20-b) * 2^-40`` with ``a + b = n`` is a product of
    powers of two — exact in f32."""
    n = n.astype(jnp.int32)
    a = jnp.clip(n, 0, 20)
    b = jnp.clip(n - a, 0, 20)
    one = jnp.int32(1)
    return (jax.lax.shift_left(one, 20 - a).astype(jnp.float32)
            * jax.lax.shift_left(one, 20 - b).astype(jnp.float32)
            * jnp.float32(2.0 ** -40))


def adaptive_inverse(sigma: jax.Array):
    """DI with per-row power-of-two scaling: returns (sigma_inv, e_r) with
    ``sigma_inv ~= 2^(e_r+8)/sigma`` in (128, 256] and ``e_r = floor(log2
    sigma)``. With e_r pinned to 8 this reduces to the paper's 2^16/sigma.

    The final clip is an identity on every reachable value — ``2^e_r <=
    sigma`` forces the quotient into (128, 256] — but the bound is
    relational, so the clip is what lets the non-relational range
    verifier prove ``sigma_inv <= SIGMA_INV_MAX`` structurally.
    """
    sigma = jnp.maximum(sigma, 1)
    e_r = 31 - jax.lax.clz(sigma)
    pre = jnp.maximum(e_r + 8 - 30, 0)
    sigma_inv = (jnp.int32(1) << jnp.minimum(e_r + 8 - pre, 30)) \
        // jax.lax.shift_right_logical(sigma, pre)
    return jnp.clip(sigma_inv, 0, SIGMA_INV_MAX), e_r


def paper_inverse(sigma: jax.Array):
    """DI exactly as in silicon: sigma_inv = 2^16 // sigma (16-bit),
    i.e. ``PAPER_INV_MAX // sigma`` — at most PAPER_INV_MAX (all-masked
    row, sigma clamped to 1), at most SIGMA_INV_MAX on any live row."""
    return jnp.int32(PAPER_INV_MAX) // jnp.maximum(sigma, 1)
