"""Direct (one-shot) XLA attention paths in model layout (B,S,H,hd).

These materialize the (Sq, Skv) logit matrix, so they serve the *short-q*
cases: decode steps over a KV cache and the integer serve specs the fused
Pallas kernels decline (logit softcap, custom query scale, long decode
bursts). GQA is native — KV heads are never broadcast.

Registered behind ``float_xla`` / ``ita_direct_xla`` / ``ibert_xla`` in
``repro.attention.backends``; call ``repro.attention.dispatch`` rather
than this module directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import softmax as S
from repro.core.quant import EPS_MAX, INT8_MAX, INT8_MIN


def softcap(x, cap):
    return jnp.tanh(x / cap) * cap if cap else x


def quantize_to_int8(x, scale):
    """Quantize onto a fixed (per-tensor or broadcastable) scale."""
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, INT8_MIN, INT8_MAX).astype(jnp.int8)


def mask(sq, skv, q_offset, causal, window, kv_len):
    """Validity mask. ``q_offset``/``kv_len`` may be scalars (dense) or
    (B,) per-sequence vectors (ragged batch); the result is (sq, skv) or
    (B, sq, skv) accordingly."""
    q_off = jnp.asarray(q_offset, jnp.int32)
    kvl = None if kv_len is None else jnp.asarray(kv_len, jnp.int32)
    if q_off.ndim or (kvl is not None and kvl.ndim):
        b = q_off.shape[0] if q_off.ndim else kvl.shape[0]
        q_off = jnp.broadcast_to(q_off.reshape(-1), (b,))[:, None, None]
        if kvl is not None:
            kvl = jnp.broadcast_to(kvl.reshape(-1), (b,))[:, None, None]
    qi = q_off + jnp.arange(sq, dtype=jnp.int32)[:, None]
    kj = jnp.arange(skv, dtype=jnp.int32)[None, :]
    m = jnp.ones(qi.shape[:-1] + (skv,), jnp.bool_)
    if causal or window > 0:
        m &= qi >= kj
    if window > 0:
        m &= (qi - kj) < window
    if kv_len is not None:
        m = m & (kj < kvl)
    return m


def _lift(m):
    """mask -> broadcastable against (B, G, M, Sq, Skv) logits."""
    return m[:, None, None] if m.ndim == 3 else m[None, None, None]


def gqa_logits(q, k):
    """q (B,Sq,H,hd), k (B,Skv,G,hd) -> logits (B,G,H/G,Sq,Skv) without
    materializing broadcast KV heads."""
    b, sq, h, hd = q.shape
    g = k.shape[2]
    qg = q.reshape(b, sq, g, h // g, hd)
    return jnp.einsum("bqgmd,bkgd->bgmqk", qg, k)


def gqa_out(p, v):
    """p (B,G,M,Sq,Skv), v (B,Skv,G,hd) -> (B,Sq,H,hd)."""
    out = jnp.einsum("bgmqk,bkgd->bqgmd", p, v)
    b, sq, g, m, hd = out.shape
    return out.reshape(b, sq, g * m, hd)


def direct_float(q, k, v, *, scale, cap=0.0, causal=True, window=0,
                 q_offset=0, kv_len=None):
    """Float softmax attention; q (B,Sq,H,hd), k/v (B,Skv,G,hd) float.
    Returns (B,Sq,H,hd) in v.dtype-ish precision."""
    m = _lift(mask(q.shape[1], k.shape[1], q_offset, causal, window,
                   kv_len))
    logits = gqa_logits(q, k) * scale
    logits = softcap(logits, cap)
    logits = jnp.where(m, logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    p = jnp.where(m, p, 0.0).astype(v.dtype)
    return gqa_out(p, v)


def direct_int(q8, k8, v8, *, s_q, s_k, s_v, scale, impl="ita",
               softmax="adaptive", cap=0.0, causal=True, window=0,
               q_offset=0, kv_len=None):
    """Integer serve path: int8 Q·Kᵀ (int32 accum), requant onto the ITA
    logit grid (with optional float-side softcap), shift-only or I-BERT
    softmax, int A·V. q8 (B,Sq,H,hd), k8/v8 (B,Skv,G,hd) int8.
    Returns (B,Sq,H,hd) float32 (dequantized through s_v)."""
    sq_, skv = q8.shape[1], k8.shape[1]
    m = _lift(mask(sq_, skv, q_offset, causal, window, kv_len))

    acc = gqa_logits(q8.astype(jnp.int32), k8.astype(jnp.int32))     # int32
    logits_f = acc.astype(jnp.float32) * (s_q * s_k * scale)
    logits_f = softcap(logits_f, cap)
    lq = jnp.clip(jnp.round(logits_f / EPS_MAX), INT8_MIN, INT8_MAX
                  ).astype(jnp.int32)
    bmask = jnp.broadcast_to(m, lq.shape)

    if impl == "ibert":
        p = S.ibert_softmax(lq, mask=bmask)                 # f32 probs
        out = jnp.einsum("bgmqk,bkgd->bqgmd", p, v8.astype(jnp.float32))
        out = out * s_v
    else:                                                   # ITA
        # the fused kernels' grid-aligned exponent (core.softmax
        # ita_softmax_aligned_int), so every serve path shares one softmax
        if softmax == "paper":
            p_int, sigma, _ = S.ita_softmax_aligned_int(lq, mask=bmask)
            e_r = jnp.full_like(sigma, 8)
        else:                                               # adaptive
            p_int, e_r, _ = S.ita_softmax_adaptive_int(lq, mask=bmask,
                                                       aligned=True)
        acc_o = jnp.einsum("bgmqk,bkgd->bqgmd", p_int,
                           v8.astype(jnp.int32))            # Σp·v, int32-safe
        out = acc_o.astype(jnp.float32) \
            * jnp.exp2(-e_r.astype(jnp.float32)).transpose(0, 3, 1, 2, 4) \
            * s_v
    b, sq2, g, mm, hd = out.shape
    return out.reshape(b, sq2, g * mm, hd)
