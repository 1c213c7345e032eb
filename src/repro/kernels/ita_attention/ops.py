"""Jitted plumbing for the fused ITA attention kernels.

This module is the thin compute layer behind the Pallas-backed entries of
the ``repro.attention`` backend registry (``ita_onepass_pallas``,
``ita_twopass_pallas``, ``ita_decode_pallas``) — there is no public
attention entry point here; call ``repro.attention.dispatch``.

``fused_attention`` handles (batch, heads, seq, dim) layouts, GQA
head-group sharing (via kernel index maps — no broadcast copies), padding
to block multiples and the quantization-scale plumbing:

    logit_mult = s_q * s_k / (sqrt(d) * EPS_MAX)   (requant onto ITA's grid)
    out_mult   = s_v / s_out     (s_v alone for the f32 output)

Scales may be scalars (per-tensor, the QAT-calibrated path) or per-head
vectors — ``s_q``/``s_out`` of shape (Hq,), ``s_k``/``s_v`` of shape (Hkv,)
(per-head KV-cache quantization); the multipliers are resolved to one
value per (batch·head) kernel row.

Kinds: ``onepass`` (flash-style), ``twopass`` (paper-faithful A matrix in
HBM), ``decode`` (onepass specialised to a single query tile against a KV
ring buffer — skips q-tiling and invalid KV tiles).

``interpret=None`` auto-resolves via ``repro.kernels.common.
resolve_interpret`` — compiled on TPU/GPU, interpret elsewhere,
``ITA_PALLAS_INTERPRET`` env override.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import EPS_MAX
from repro.kernels.common import resolve_interpret
from repro.kernels.ita_attention.kernel import (ita_attention_decode,
                                                ita_attention_decode_paged,
                                                ita_attention_onepass,
                                                ita_attention_onepass_paged,
                                                ita_attention_twopass)

KINDS = ("onepass", "twopass", "decode")


def _pad_seq(x, mult, hot: bool = False):
    """Zero-pad the seq axis (axis 1, any rank) to a multiple of ``mult``.

    ``hot=True`` marks the decode KV ring: padding there would be a
    per-step copy of the whole ring, so it is *statically forbidden* —
    ``KVCacheState.init`` block-aligns ring capacities (MIN_BLOCK_KV),
    making the pad a guaranteed no-op on the decode hot path, and this
    assert keeps it that way."""
    pad = (-x.shape[1]) % mult
    if pad and hot:
        raise ValueError(
            f"decode KV ring capacity {x.shape[1]} is not a block_kv="
            f"{mult} multiple — a per-step pad-copy of the whole ring; "
            f"allocate through KVCacheState.init (block-aligned) or pass "
            f"a block_kv that divides the capacity")
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    return x


def _per_head(s, h):
    """Scalar -> (h,); (h,) passes through."""
    s = jnp.asarray(s, jnp.float32).reshape(-1)
    if s.shape[0] == 1:
        return jnp.broadcast_to(s, (h,))
    assert s.shape[0] == h, (s.shape, h)
    return s


def _per_row(x, b, h):
    """Expand a dynamic decode offset to one value per (batch·head) kernel
    row (b-major, head-minor): scalars broadcast, (B,) per-sequence
    vectors (the ragged path) repeat per head."""
    x = jnp.asarray(x, jnp.int32).reshape(-1)
    if x.shape[0] == 1:
        return jnp.broadcast_to(x, (b * h,))
    assert x.shape[0] == b, (x.shape, b)
    return jnp.repeat(x, h)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "kind", "adaptive", "block_q", "block_kv",
    "interpret"))
def _fused(q_q, k_q, v_q, s_q, s_k, s_v, s_out, *, q_offset, kv_len,
           causal, window, kind, adaptive, block_q, block_kv,
           interpret, page_table=None, q_lens=None, layer=None):
    b, hq, sq, d = q_q.shape
    if page_table is not None and k_q.ndim == 4:
        # one layer's pool (P, Hkv, page, D): the stack of one
        k_q, v_q, layer = k_q[None], v_q[None], 0
    # (B, Hkv, Skv, D), or the stacked paged pool (L, P, Hkv, page, D)
    hkv, skv = k_q.shape[-3], k_q.shape[-2]
    assert hq % hkv == 0, (hq, hkv)
    rep = hq // hkv

    # per-(batch*head) requant multipliers (rows are b-major, head-minor)
    sk_h = jnp.repeat(_per_head(s_k, hkv), rep)
    sv_h = jnp.repeat(_per_head(s_v, hkv), rep)
    lmult = _per_head(s_q, hq) * sk_h / (np.sqrt(d) * EPS_MAX)
    # no s_out: the kernels emit the f32 output, dequantized through s_v
    float_out = s_out is None
    omult = sv_h if float_out else sv_h / _per_head(s_out, hq)
    lmult = jnp.tile(lmult, b)
    omult = jnp.tile(omult, b)

    if page_table is not None:
        # Pages are blocks: block_kv == page_size by construction, so the
        # pool is never padded/copied — tiles stream straight from the
        # arena through the page-table index maps. A lane-padded pool
        # (head dim past d) meets q padded alike: the zero lanes add
        # nothing to Q·Kᵀ, and the output's extra lanes are dropped.
        bq = min(block_q, max(8, sq))
        qf = _pad_seq(q_q.reshape(b * hq, sq, d), bq)
        qf = jnp.pad(qf, [(0, 0), (0, 0), (0, k_q.shape[-1] - d)])
        skv = page_table.shape[1] * skv
        kv_len = _per_row(skv if kv_len is None else kv_len, b, hq)
        q_offset = _per_row(q_offset, b, hq)
        q_len = None if q_lens is None else _per_row(q_lens, b, hq)
        common = dict(layer=layer, q_offset=q_offset, q_len=q_len,
                      causal=causal, window=window, adaptive=adaptive,
                      kv_rep=rep, hq=hq, float_out=float_out,
                      interpret=interpret)
        if kind == "decode":
            out = ita_attention_decode_paged(
                qf, k_q, v_q, page_table, lmult, omult, kv_len, **common)
        else:
            out = ita_attention_onepass_paged(
                qf, k_q, v_q, page_table, lmult, omult, kv_len, block_q=bq,
                **common)
        return out[:, :sq, :d].reshape(b, hq, sq, d)

    bq = min(block_q, max(8, sq))
    bkv = min(block_kv, max(128, skv)) if skv >= 128 else skv
    qf = _pad_seq(q_q.reshape(b * hq, sq, d), bq)
    kf = _pad_seq(k_q.reshape(b * hkv, skv, d), bkv, hot=kind == "decode")
    vf = _pad_seq(v_q.reshape(b * hkv, skv, d), bkv, hot=kind == "decode")

    kv_len = _per_row(skv if kv_len is None else kv_len, b, hq)
    q_offset = _per_row(q_offset, b, hq)
    q_len = None if q_lens is None else _per_row(q_lens, b, hq)
    common = dict(q_offset=q_offset, causal=causal, window=window,
                  adaptive=adaptive, block_kv=bkv, kv_rep=rep,
                  float_out=float_out, interpret=interpret)
    if kind == "decode":
        out = ita_attention_decode(qf, kf, vf, lmult, omult, kv_len,
                                   q_len=q_len, **common)
    elif kind == "onepass":
        out = ita_attention_onepass(qf, kf, vf, lmult, omult, kv_len,
                                    q_len=q_len, block_q=bq, **common)
    else:
        out, _ = ita_attention_twopass(qf, kf, vf, lmult, omult, kv_len,
                                       block_q=bq, **common)
    return out[:, :sq].reshape(b, hq, sq, d)


def fused_attention(q_q: jax.Array, k_q: jax.Array, v_q: jax.Array,
                    s_q, s_k, s_v, s_out, *,
                    q_offset: jax.Array | int = 0,
                    kv_len: jax.Array | int | None = None,
                    q_lens: jax.Array | None = None,
                    causal: bool = True, window: int = 0,
                    kind: str = "onepass", adaptive: bool = True,
                    block_q: int = 128, block_kv: int = 128,
                    page_table: jax.Array | None = None,
                    layer: jax.Array | int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Quantized multi-head attention with the ITA integer softmax.

    ``q_q``: (B, Hq, Sq, D) int8; ``k_q``/``v_q``: (B, Hkv, Skv, D) int8.
    GQA: Hkv must divide Hq; KV heads are shared per group via index
    maps — the broadcast never materializes.
    ``page_table`` (B, n_pages) int32 switches K/V to a shared head-major
    **paged pool** ``(num_pages, Hkv, page_size, D)``: logical KV tile ``j`` of
    sequence ``b`` streams from physical page ``page_table[b, j]``
    (scalar-prefetch index maps; ``block_kv`` is the page size — the
    ``block_kv`` argument is ignored). Bit-identical to the contiguous
    ring path when ``page_size`` equals the ring's ``block_kv``. A pool
    stacked over layers, ``(L, num_pages, Hkv, page_size, D)``, is read
    at ``layer`` (() int32) — the model's pools travel whole through its
    layer scan.
    ``q_offset``: logical position of query 0 (decode: valid_kv - Sq).
    ``kv_len``: valid prefix of the KV cache (defaults to Skv).
    Both accept (B,) per-sequence vectors — the ragged batch path: each
    (batch·head) kernel row masks/tile-skips against its own prefix.
    ``q_lens`` (B,) extends the raggedness to the query axis: row ``b``
    treats only its first ``q_lens[b]`` of the ``Sq`` query rows as real
    (the rest emit zeros) — one mixed call serves decode rows (1 query)
    next to chunked-prefill rows (``chunk`` queries).
    Returns (B, Hq, Sq, D) int8 at scale ``s_out``; with ``s_out=None``
    the f32 attention output itself (no int8 requant, so no clip).
    """
    assert kind in KINDS, kind
    assert not (page_table is not None and kind == "twopass"), \
        "the paged pool serves the onepass/decode kernels only"
    assert not (q_lens is not None and kind == "twopass"), \
        "ragged q_len serves the onepass/decode kernels only"
    return _fused(q_q, k_q, v_q, s_q, s_k, s_v, s_out, q_offset=q_offset,
                  kv_len=kv_len, causal=causal, window=window, kind=kind,
                  adaptive=adaptive, block_q=block_q, block_kv=block_kv,
                  page_table=page_table, q_lens=q_lens, layer=layer,
                  interpret=resolve_interpret(interpret))
