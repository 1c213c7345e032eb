"""Int8 KV-cache decode engine: quantization helpers + the kernel-level
prefill/decode loop over a ``repro.attention.KVCacheState`` ring buffer.

K/V projections are stored quantized (int8 + quantization scales), so the
cache is 4x smaller than f32 and feeds the integer attention path
directly — no dequantize pass, the int8 MXU consumes the cache bytes as
stored (paper §III's weight-stationary philosophy applied to the KV
stream). The ring/pool semantics (slot ``t % C``, logical ``pos``,
``valid_len``/``q_offset`` derivation, page tables + free stack) live on
the typed states in ``repro.attention.state``; this module adds the
*engine*: per-head symmetric quantization of the KV stream and the
prefill/decode attend steps, dispatched through the attention backend
registry (layout capabilities select the fused Pallas kernels — the
decode step reads ring buffers via ``bhsd_bsgd``, relaid out head-major
per call, and paged pools via ``bhsd_paged`` page-table index maps with
no relayout, broadcast or gather copies).

Per-head scales are finer than the per-tensor QAT grid; the model path
(``repro.models.attention``) passes the QAT per-tensor scales instead, so
train/serve semantics stay aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.attention import (AttentionSpec, KVCacheState, PagedKVState,
                             QuantScales, dispatch)
from repro.core.quant import INT8_MAX, INT8_MIN

__all__ = ["KVCacheState", "PagedKVState", "init_cache", "init_paged_cache",
           "quantize_per_head", "quantize_with_scale", "prefill_attend",
           "decode_attend"]


def quantize_per_head(x: jax.Array, head_axis: int = 2):
    """Symmetric per-head int8 quantization.

    ``x`` (..., G, hd) float with heads on ``head_axis``. Returns
    ``(x_q int8, scale (G,) f32)``.
    """
    red = tuple(i for i in range(x.ndim) if i != head_axis)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=red)
    scale = jnp.maximum(amax, 1e-8) / INT8_MAX
    sh = [1] * x.ndim
    sh[head_axis] = x.shape[head_axis]
    q = jnp.round(x.astype(jnp.float32) / scale.reshape(sh))
    return jnp.clip(q, INT8_MIN, INT8_MAX).astype(jnp.int8), scale


def quantize_with_scale(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize onto a fixed (per-tensor or broadcastable) scale."""
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, INT8_MIN, INT8_MAX).astype(jnp.int8)


def init_cache(batch: int, capacity: int, n_kv_heads: int, head_dim: int,
               dtype=jnp.int8, per_head_scales: bool = False) -> KVCacheState:
    """Fresh (zeroed) ring-buffer cache."""
    return KVCacheState.init(batch, capacity, n_kv_heads, head_dim,
                             dtype=dtype, per_head_scales=per_head_scales)


def init_paged_cache(batch: int, capacity: int, n_kv_heads: int,
                     head_dim: int, dtype=jnp.int8,
                     per_head_scales: bool = False, *, page_size: int = 128,
                     num_pages: int | None = None) -> PagedKVState:
    """Fresh paged KV pool (shared arena + per-sequence page tables).
    ``num_pages`` undersized vs ``batch * ceil(capacity/page_size)``
    oversubscribes the pool — pair with an admission scheduler."""
    return PagedKVState.init(batch, capacity, n_kv_heads, head_dim,
                             dtype=dtype, per_head_scales=per_head_scales,
                             page_size=page_size, num_pages=num_pages)


# ---------------------------------------------------------------------------
# Kernel-level decode engine (one attention layer over one cache)
# ---------------------------------------------------------------------------

def prefill_attend(cache: KVCacheState, q_q: jax.Array, k_new: jax.Array,
                   v_new: jax.Array, s_q, s_out, *, causal: bool = True,
                   window: int = 0, lengths: jax.Array | None = None,
                   block_q: int = 128, block_kv: int = 128,
                   interpret: bool | None = None):
    """Quantized prefill: per-head-quantize and cache K/V, run the fused
    ITA kernel over the prompt. ``q_q`` (B, Hq, S, D) int8 at scale
    ``s_q``; ``k_new``/``v_new`` (B, S, G, D) float. ``lengths`` (B,)
    declares a ragged batch of right-padded prompts (per-sequence valid
    prefixes; causal masking keeps each row's valid outputs exact).
    Returns ``(out int8 at s_out, new_cache)``.

    Dispatch note: the ``bhsd_bsgd`` layout + per-head scales make the
    streaming XLA backend ineligible, so the registry lands on
    ``ita_onepass_pallas``, capability-driven like the decode layout.
    """
    k_q, k_scale = quantize_per_head(k_new)
    v_q, v_scale = quantize_per_head(v_new)
    cache = cache.prefill_write(k_q, v_q, lengths=lengths) \
                 .with_scales(k_scale, v_scale)
    # Paged or ring, the *prefill attention* streams the freshly projected
    # (B, S, G, D) tensors — only decode re-reads the pool.
    spec = AttentionSpec(mode="prefill", impl="ita", causal=causal,
                         window=window, layout="bhsd_bsgd",
                         scale_kind="per_head", out_dtype="int8",
                         q_len=q_q.shape[2])
    out = dispatch(q_q, k_q, v_q, spec=spec,
                   scales=QuantScales(s_q, k_scale, v_scale, s_out),
                   kv_len=lengths, block_q=block_q, block_kv=block_kv,
                   interpret=interpret)
    return out, cache


def decode_attend(cache: KVCacheState, q_q: jax.Array, k_new: jax.Array,
                  v_new: jax.Array, s_q, s_out, *, causal: bool = True,
                  window: int = 0, block_kv: int = 128,
                  interpret: bool | None = None):
    """One incremental decode step through the cache.

    Appends the new token's K/V (quantized onto the cache's standing
    per-head scales — the scales are frozen after prefill so cached bytes
    never need rescaling) and attends the single query over the valid
    prefix via the fused decode-shaped kernel (``bhsd_bsgd`` ring or
    ``bhsd_paged`` pool layout). The cache's per-sequence
    ``q_offset``/``valid_len`` vectors ride into the kernel's per-row
    meta, so a ragged batch (mixed prompt lengths) decodes in this one
    call. ``q_q``
    (B, Hq, 1, D) int8; ``k_new``/``v_new`` (B, 1, G, D) float. Returns
    ``(out, new_cache)``.
    """
    k_q = quantize_with_scale(k_new, cache.k_scale[None, None, :, None])
    v_q = quantize_with_scale(v_new, cache.v_scale[None, None, :, None])
    cache = cache.decode_append(k_q, v_q)
    paged = isinstance(cache, PagedKVState)
    spec = AttentionSpec(mode="decode", impl="ita", causal=causal,
                         window=window,
                         layout="bhsd_paged" if paged else "bhsd_bsgd",
                         scale_kind="per_head", out_dtype="int8",
                         q_len=q_q.shape[2])
    out = dispatch(q_q, cache.k, cache.v, spec=spec,
                   scales=QuantScales(s_q, cache.k_scale, cache.v_scale,
                                      s_out),
                   q_offset=cache.q_offset(1), kv_len=cache.valid_len(),
                   page_table=cache.page_table if paged else None,
                   block_kv=block_kv, interpret=interpret)
    return out, cache
