"""Pure-jnp oracles for the fused ITA attention kernels.

Three references:

- ``ita_attention_ref``        one-shot, paper EN semantics (p = Σ_inv >> k
                               then p·V). The twopass kernel must match this
                               exactly when given a single kv tile, and match
                               ``ita_attention_stream_ref`` exactly always.
- ``ita_attention_fused_ref``  one-shot, fused semantics (u = 128>>k, u·V,
                               Σ_inv folded into the output requant) — the
                               onepass kernel's single-tile oracle.
- ``ita_attention_stream_ref`` tile-by-tile mirror of the kernels' streaming
                               DA (and accumulator corrections), for exact
                               equality at any tiling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import INT8_MAX, INT8_MIN, SOFTMAX_SHIFT
from repro.kernels.common import MASK_K, NEG_SENTINEL, pow2_neg


def _full_mask(sq, skv, causal, window, kv_len, q_offset=0):
    qi = q_offset + jnp.arange(sq, dtype=jnp.int32)[:, None]
    kj = jnp.arange(skv, dtype=jnp.int32)[None, :]
    valid = jnp.ones((sq, skv), jnp.bool_)
    if causal or window > 0:
        valid &= qi >= kj
    if window > 0:
        valid &= (qi - kj) < window
    valid &= kj < kv_len
    return valid


def _logits(q_q, k_q, lmult):
    acc = jnp.einsum("bqd,bkd->bqk", q_q.astype(jnp.int32),
                     k_q.astype(jnp.int32))
    y = jnp.round(acc.astype(jnp.float32) * lmult)
    return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int32)


def _k_and_sigma(logits, valid):
    x = jnp.where(valid, logits, NEG_SENTINEL)
    row_max = jnp.max(x, axis=-1, keepdims=True)
    k = jax.lax.shift_right_logical(row_max - logits, SOFTMAX_SHIFT)
    k = jnp.where(valid, jnp.minimum(k, 31), MASK_K)
    sigma = jnp.sum(2 * jax.lax.shift_right_logical(jnp.int32(128), k),
                    axis=-1, keepdims=True)
    return k, sigma, row_max


def _inverse(sigma, adaptive):
    sigma = jnp.maximum(sigma, 1)
    if adaptive:
        e_r = 31 - jax.lax.clz(sigma)
        pre = jnp.maximum(e_r + 8 - 30, 0)
        inv = (jnp.int32(1) << jnp.minimum(e_r + 8 - pre, 30)) \
            // jax.lax.shift_right_logical(sigma, pre)
    else:
        inv = (jnp.int32(1) << 16) // sigma
        e_r = jnp.full_like(inv, 8)
    return inv, e_r


def ita_attention_ref(q_q, k_q, v_q, lmult, omult, kv_len, *, causal,
                      window=0, adaptive=False, q_offset=0):
    """One-shot paper-EN reference. Returns (out int8, a int8)."""
    sq, skv = q_q.shape[1], k_q.shape[1]
    valid = _full_mask(sq, skv, causal, window, kv_len, q_offset)[None]
    logits = _logits(q_q, k_q, lmult)
    k, sigma, _ = _k_and_sigma(logits, valid)
    inv, e_r = _inverse(sigma, adaptive)
    p = jax.lax.shift_right_logical(inv, k)                       # EN
    acc = jnp.einsum("bqk,bkd->bqd", p, v_q.astype(jnp.int32))
    y = jnp.round(acc.astype(jnp.float32)
                  * pow2_neg(e_r) * omult)
    out = jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)
    return out, logits.astype(jnp.int8)


def ita_attention_fused_ref(q_q, k_q, v_q, lmult, omult, kv_len, *, causal,
                            window=0, adaptive=True, q_offset=0):
    """One-shot fused-EN reference (u = 128>>k numerators)."""
    sq, skv = q_q.shape[1], k_q.shape[1]
    valid = _full_mask(sq, skv, causal, window, kv_len, q_offset)[None]
    logits = _logits(q_q, k_q, lmult)
    k, sigma, _ = _k_and_sigma(logits, valid)
    inv, e_r = _inverse(sigma, adaptive)
    u = jax.lax.shift_right_logical(jnp.int32(128), k)
    acc = jnp.einsum("bqk,bkd->bqd", u, v_q.astype(jnp.int32)
                     ).astype(jnp.float32)
    scale = 2.0 * inv.astype(jnp.float32) * pow2_neg(e_r + 8) * omult
    y = jnp.round(acc * scale)
    return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)


def ita_attention_stream_ref(q_q, k_q, v_q, lmult, omult, kv_len, *, causal,
                             window=0, adaptive=True, block_kv=128,
                             kind="onepass", q_offset=0):
    """Tile-by-tile mirror of the kernels (exact-match oracle)."""
    bh, sq, d = q_q.shape
    skv = k_q.shape[1]
    n_kv = -(-skv // block_kv)
    valid_full = _full_mask(sq, skv, causal, window, kv_len, q_offset)[None]
    logits = _logits(q_q, k_q, lmult)

    run_max = jnp.full((bh, sq, 1), NEG_SENTINEL, jnp.int32)
    run_sigma = jnp.zeros((bh, sq, 1), jnp.int32)
    acc = jnp.zeros((bh, sq, d), jnp.float32)
    for j in range(n_kv):
        sl = slice(j * block_kv, min((j + 1) * block_kv, skv))
        lg, vd = logits[..., sl], valid_full[..., sl]
        x = jnp.where(vd, lg, NEG_SENTINEL)
        part_max = jnp.max(x, axis=-1, keepdims=True)
        new_max = jnp.maximum(run_max, part_max)
        delta = jnp.minimum(jax.lax.shift_right_logical(
            new_max - run_max, SOFTMAX_SHIFT), 31)
        k = jax.lax.shift_right_logical(new_max - lg, SOFTMAX_SHIFT)
        k = jnp.where(vd, jnp.minimum(k, 31), MASK_K)
        u = jax.lax.shift_right_logical(jnp.int32(128), k)
        run_sigma = jax.lax.shift_right_logical(run_sigma, delta) \
            + 2 * jnp.sum(u, axis=-1, keepdims=True)
        run_max = new_max
        if kind == "onepass":
            pv = jnp.einsum("bqk,bkd->bqd", u, v_q[:, sl].astype(jnp.int32))
            acc = acc * pow2_neg(delta) \
                + pv.astype(jnp.float32)

    inv, e_r = _inverse(run_sigma, adaptive)
    if kind == "onepass":
        scale = 2.0 * inv.astype(jnp.float32) * pow2_neg(e_r + 8) * omult
        y = jnp.round(acc * scale)
        return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)

    # twopass: EN with the final streamed stats (numerators exact).
    k = jax.lax.shift_right_logical(run_max - logits, SOFTMAX_SHIFT)
    k = jnp.where(valid_full, jnp.minimum(k, 31), MASK_K)
    p = jax.lax.shift_right_logical(inv, k)
    acc2 = jnp.einsum("bqk,bkd->bqd", p, v_q.astype(jnp.int32))
    y = jnp.round(acc2.astype(jnp.float32)
                  * pow2_neg(e_r) * omult)
    return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)


def float_attention_ref(q, k, v, *, causal, window=0, kv_len=None,
                        q_offset=0):
    """f32 attention oracle for end-to-end accuracy comparisons."""
    d = q.shape[-1]
    kv_len = k.shape[1] if kv_len is None else kv_len
    valid = _full_mask(q.shape[1], k.shape[1], causal, window, kv_len,
                       q_offset)[None]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v)
