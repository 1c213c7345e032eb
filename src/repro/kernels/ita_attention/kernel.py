"""Fused ITA attention Pallas kernels: Q·Kᵀ → streaming integer softmax → A·V.

Two dataflows, both with the ITA integer softmax:

- ``onepass`` (beyond-paper, flash-style): the int8 attention tile never
  leaves VMEM. Per (q-tile, kv-tile): int8 Q·Kᵀ on the MXU → requant to the
  ITA logit grid → DA update of the per-row (max, Σ) stats → the *unnormal-
  ized* numerators ``u = 128 >> k`` (int8!) multiply V on the MXU and add
  into a running accumulator which is shift-corrected when the row max
  grows (the same correction silicon applies to Σ). DI happens once per row
  at the final kv tile and folds into the output requant as a per-row
  multiplier. HBM traffic for the S×S matrix: zero.

- ``twopass`` (paper-faithful): pass 1 streams Q·Kᵀ tiles, writes the int8
  attention matrix A to HBM exactly once and accumulates the (max, Σ) row
  stats on the fly (DA); DI inverts Σ per row; pass 2 re-streams A, norma-
  lizes each element with a pure shift (EN, ``p = Σ_inv >> k``) and feeds
  the MXU for A·V. This reproduces ITA's memory traffic: A written once,
  read once, softmax adds **no** extra passes.

Integer semantics notes:
- ``Σ p ≤ 2^(e_r)``... for paper mode (e_r = 8): ``Σ p ≤ 256`` so the A·V
  accumulator is bounded by 2^15 — f32 scratch holds it exactly (ints are
  exact in f32 below 2^24), so paper mode remains bit-exact integer.
- onepass uses ``u = 128 >> k`` (the missing factor 2 folds into the
  output requant). The numerator·V matmuls (onepass ``u``, twopass ``p``)
  run as bf16 x bf16 -> f32 on the MXU, which is exact: ``u, p <= 256``
  and ``|v| <= 128`` are exact in bf16, every product is exact in f32,
  and a tile's sum stays below 2^24 (``256 * 128 * bkv`` for bkv <= 256
  on live rows), where f32 integers are exact. Mosaic has no int32
  matmul, and ``u`` reaches 128, which int8 cannot hold.
- per-row scalars (the requant multipliers and the ``[kv_len, q_offset,
  q_len]`` meta) are whole 1-D arrays in SMEM, indexed by the kernel row
  ``pl.program_id(0)`` — Mosaic refuses ``(1, 1)`` VMEM blocks of a
  ``(bh, 1)`` array.

- ``decode`` (serving): the onepass dataflow specialised to incremental
  decode against a KV-cache ring buffer. The q grid dimension disappears
  (one tile holds all ``sq <= 8`` queries), KV tiles wholly beyond the
  cache's valid prefix are *skipped* — with a max_len ring only
  ``ceil(kv_len/bkv)`` of the tiles do work — and the requant multipliers
  are per-(batch·head) rows so per-head cache quantization scales flow
  straight into the kernel.

Ragged batches: ``kv_len``/``q_offset``/``q_len`` are per-(batch·head)
rows of the ``meta`` operand — every kernel row masks (and tile-skips)
against *its own* valid KV prefix, so a batch of sequences at different
positions decodes in one call with no padding to the longest. ``q_len``
extends the raggedness to the *query* axis: a row only treats its first
``q_len`` query rows as real (the rest emit zeros), which is how one
mixed serve call carries decode rows (q_len 1) next to chunked-prefill
rows (q_len = chunk). Scalars broadcast to all rows (the dense case).

Paged KV pool: the ``*_paged`` entry points consume one shared
head-major ``(num_pages, G, page_size, hd)`` int8 arena per layer,
stacked over the model's layers as ``(L, num_pages, G, page_size, hd)``
(each block is one kv head's ``(page_size, hd)`` page, a layout Mosaic
tiles) through a **page table** delivered as a flat scalar-prefetch
operand, next to the layer index — the KV BlockSpec index map reads
``page_table[b * n_pages + j]`` to translate logical KV tile ``j`` of
sequence ``b`` into a physical page of layer ``layer``'s arena, so
scattered pages stream through the very
same kernel bodies (``decode_kernel``/``onepass_kernel``) tile-for-tile.
With ``block_kv == page_size`` the DA tile schedule is identical to the
contiguous ring path, which is what keeps paged decode bit-identical to
the ring (the ``ita_fused`` family invariant).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import INT8_MAX, INT8_MIN, SOFTMAX_SHIFT
from repro.kernels.common import (MASK_K, NEG_SENTINEL, adaptive_inverse,
                                  da_update, paper_inverse, pow2_neg,
                                  tile_mask)


def _qk_logits(q_tile, k_tile, mult):
    """int8 Q (bq,d) x int8 K (bkv,d)^T -> int32 -> requant to int8 logit
    grid (returned widened to int32)."""
    acc = jax.lax.dot_general(q_tile, k_tile, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = jnp.round(acc.astype(jnp.float32) * mult)
    return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int32)


def _row_scalars(lmult_ref, omult_ref, meta_ref):
    """This kernel row's (logit_mult, out_mult, kv_len, q_offset, q_len)
    from the whole-array SMEM operands (meta is flat, 3 entries per
    row)."""
    r = pl.program_id(0)
    return (lmult_ref[r], omult_ref[r], meta_ref[3 * r], meta_ref[3 * r + 1],
            meta_ref[3 * r + 2])


def _pv(p, v_tile):
    """Numerator tile (int32, <= 256) x int8 V tile on the MXU as bf16 x
    bf16 -> f32 — exact, see the module notes."""
    return jax.lax.dot_general(p.astype(jnp.bfloat16),
                               v_tile.astype(jnp.bfloat16),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _stream_tile(q_tile, k_tile, v_tile, lmult, m_ref, sigma_ref, acc_ref,
                 valid):
    """One onepass DA step: logits -> (max, Σ) update -> shift-corrected
    A·V accumulate (the correction multiplies by 2^-delta, exact in f32,
    unlike the integer Σ shift)."""
    logits = _qk_logits(q_tile, k_tile, lmult)
    u, delta = da_update(m_ref, sigma_ref, logits, valid)
    corr = pow2_neg(delta)
    acc_ref[...] = acc_ref[...] * corr + _pv(u, v_tile)


def _finalize_onepass(o_ref, sigma_ref, acc_ref, omult, adaptive):
    if adaptive:
        inv, e_r = adaptive_inverse(sigma_ref[...])
    else:
        inv = paper_inverse(sigma_ref[...])
        e_r = jnp.full_like(inv, 8)
    # out = acc * 2 * inv * 2^-(e_r+8) * (s_v/s_out); the 2 restores the
    # halved numerator unit (u = 128>>k vs the paper's 256>>k).
    scale = 2.0 * inv.astype(jnp.float32) * pow2_neg(e_r + 8) * omult
    y = jnp.round(acc_ref[...] * scale)
    o_ref[0] = jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)


def _kv_tile(ref, paged):
    """(bkv, d) tile of a K/V block: (1, bkv, d) rows of the 3-D kernel
    layout, or (1, 1, 1, page, d) of the stacked head-major paged pool."""
    return ref[0, 0, 0] if paged else ref[0]


def onepass_kernel(q_ref, k_ref, v_ref, lmult_ref, omult_ref, meta_ref,
                   o_ref, m_ref, sigma_ref, acc_ref,
                   *, causal: bool, window: int, adaptive: bool,
                   bq: int, bkv: int, paged: bool = False):
    i, j = pl.program_id(1), pl.program_id(2)
    lmult, omult, kv_len, q_off, q_len = _row_scalars(lmult_ref, omult_ref,
                                                      meta_ref)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_SENTINEL)
        sigma_ref[...] = jnp.zeros_like(sigma_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # KV tiles wholly beyond this row's valid prefix are fully masked —
    # DA/acc no-ops — so skip their MXU work: chunked-prefill rows stream
    # only their occupied pages, not the whole pool.
    @pl.when(j * bkv < kv_len)
    def _tile():
        valid = tile_mask(i, j, bq, bkv, causal, window, kv_len, q_off, q_len)
        _stream_tile(q_ref[0], _kv_tile(k_ref, paged), _kv_tile(v_ref, paged),
                     lmult, m_ref, sigma_ref, acc_ref, valid)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        _finalize_onepass(o_ref, sigma_ref, acc_ref, omult, adaptive)


def qk_da_kernel(q_ref, k_ref, lmult_ref, meta_ref, a_ref, max_o_ref,
                 sigma_o_ref, m_ref, sigma_ref,
                 *, causal: bool, window: int, bq: int, bkv: int):
    """Two-pass, pass 1: logits to HBM once + DA stats."""
    i, j = pl.program_id(1), pl.program_id(2)
    r = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_SENTINEL)
        sigma_ref[...] = jnp.zeros_like(sigma_ref)

    logits = _qk_logits(q_ref[0], k_ref[0], lmult_ref[r])
    valid = tile_mask(i, j, bq, bkv, causal, window, meta_ref[3 * r],
                      meta_ref[3 * r + 1], meta_ref[3 * r + 2])
    da_update(m_ref, sigma_ref, logits, valid)
    a_ref[0] = logits.astype(jnp.int8)

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit_stats():
        max_o_ref[0] = m_ref[...]
        sigma_o_ref[0] = sigma_ref[...]


def av_en_kernel(a_ref, inv_ref, er_ref, max_ref, v_ref, omult_ref,
                 meta_ref, o_ref, acc_ref,
                 *, causal: bool, window: int, bq: int, bkv: int):
    """Two-pass, pass 2: re-stream A, EN by pure shifts, A·V on the MXU."""
    i, j = pl.program_id(1), pl.program_id(2)
    r = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0].astype(jnp.int32)
    valid = tile_mask(i, j, bq, bkv, causal, window, meta_ref[3 * r],
                      meta_ref[3 * r + 1], meta_ref[3 * r + 2])
    k = jax.lax.shift_right_logical(max_ref[0] - a, SOFTMAX_SHIFT)
    k = jnp.where(valid, jnp.minimum(k, 31), MASK_K)
    p = jax.lax.shift_right_logical(inv_ref[0], k)   # EN: p <= 256 live
    acc_ref[...] += _pv(p, v_ref[0])                 # exact: |acc| < 2^24

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        y = jnp.round(acc_ref[...] * pow2_neg(er_ref[0]) * omult_ref[r])
        o_ref[0] = jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)


def decode_kernel(q_ref, k_ref, v_ref, lmult_ref, omult_ref, meta_ref,
                  o_ref, m_ref, sigma_ref, acc_ref,
                  *, causal: bool, window: int, adaptive: bool,
                  bq: int, bkv: int, paged: bool = False):
    """Onepass dataflow without a q grid axis (decode: sq <= one tile)."""
    j = pl.program_id(1)
    lmult, omult, kv_len, q_off, q_len = _row_scalars(lmult_ref, omult_ref,
                                                      meta_ref)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_SENTINEL)
        sigma_ref[...] = jnp.zeros_like(sigma_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Ring buffers are allocated at max_len; tiles wholly beyond the valid
    # prefix are fully masked (max/sigma/acc all no-ops) — skip the MXU work.
    @pl.when(j * bkv < kv_len)
    def _tile():
        valid = tile_mask(0, j, bq, bkv, causal, window, kv_len, q_off, q_len)
        _stream_tile(q_ref[0], _kv_tile(k_ref, paged), _kv_tile(v_ref, paged),
                     lmult, m_ref, sigma_ref, acc_ref, valid)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        _finalize_onepass(o_ref, sigma_ref, acc_ref, omult, adaptive)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _row_mults(logit_mult, out_mult, bh):
    """Broadcast scalar or per-row requant multipliers to (bh,) f32."""
    lm = jnp.broadcast_to(jnp.asarray(logit_mult, jnp.float32).reshape(-1),
                          (bh,))
    om = jnp.broadcast_to(jnp.asarray(out_mult, jnp.float32).reshape(-1),
                          (bh,))
    return lm, om


def _row_meta(kv_len, q_offset, q_len, bh):
    """Per-row ``[kv_len, q_offset, q_len]`` meta, flat (bh * 3,) int32
    (row-major: row r's triple at ``3r``). Scalars (the dense case)
    broadcast to every row; (bh,) vectors pass through — the ragged path,
    one valid KV prefix / query position / query count per (batch·head)
    row. ``q_len`` is the row's count of *valid query rows* (ragged
    q_len: a mixed chunked-prefill/decode call); pass the static query
    width for the dense case."""
    kv = jnp.asarray(kv_len, jnp.int32).reshape(-1)
    off = jnp.asarray(q_offset, jnp.int32).reshape(-1)
    qn = jnp.asarray(q_len, jnp.int32).reshape(-1)
    assert kv.shape[0] in (1, bh), (kv.shape, bh)
    assert off.shape[0] in (1, bh), (off.shape, bh)
    assert qn.shape[0] in (1, bh), (qn.shape, bh)
    return jnp.stack([jnp.broadcast_to(kv, (bh,)),
                      jnp.broadcast_to(off, (bh,)),
                      jnp.broadcast_to(qn, (bh,))], axis=1).reshape(-1)


def ita_attention_onepass(q_q, k_q, v_q, logit_mult, out_mult, kv_len, *,
                          q_offset=0, q_len=None, causal: bool,
                          window: int = 0,
                          adaptive: bool = True, block_q: int = 128,
                          block_kv: int = 128, kv_rep: int = 1,
                          interpret: bool = True):
    """q (BH, Sq, D) int8; k/v (BH/kv_rep, Skv, D) int8; returns (BH, Sq, D)
    int8. GQA: q row r reads kv row r // kv_rep via the index map — the KV
    head broadcast never materializes."""
    bh, sq, d = q_q.shape
    skv = k_q.shape[1]
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    assert k_q.shape[0] * kv_rep == bh, (k_q.shape, kv_rep, bh)
    kern = functools.partial(onepass_kernel, causal=causal, window=window,
                             adaptive=adaptive, bq=bq, bkv=bkv)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len, bh)
    kv_spec = pl.BlockSpec((1, bkv, d), lambda b, i, j: (b // kv_rep, j, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq, skv // bkv),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  kv_spec, kv_spec, _SMEM, _SMEM, _SMEM],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="ita_onepass",
    )(q_q, k_q, v_q, lmult, omult, meta)


def ita_attention_twopass(q_q, k_q, v_q, logit_mult, out_mult, kv_len, *,
                          q_offset=0, causal: bool, window: int = 0,
                          adaptive: bool = False, block_q: int = 128,
                          block_kv: int = 128, kv_rep: int = 1,
                          interpret: bool = True):
    """Paper-faithful dataflow. Returns (out int8, a_mat int8) — A is the
    materialized int8 attention matrix (written once, read once).
    GQA via ``kv_rep`` index maps as in onepass. Row stats travel as
    (bh, sq, 1) columns, the layout the (bq, 1) DA scratch already has."""
    bh, sq, d = q_q.shape
    skv = k_q.shape[1]
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    assert k_q.shape[0] * kv_rep == bh, (k_q.shape, kv_rep, bh)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq, bh)
    grid = (bh, sq // bq, skv // bkv)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bkv, d), lambda b, i, j: (b // kv_rep, j, 0))
    a_spec = pl.BlockSpec((1, bq, bkv), lambda b, i, j: (b, i, j))
    col_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    col = jax.ShapeDtypeStruct((bh, sq, 1), jnp.int32)

    k1 = functools.partial(qk_da_kernel, causal=causal, window=window,
                           bq=bq, bkv=bkv)
    a_mat, row_max, sigma = pl.pallas_call(
        k1,
        grid=grid,
        in_specs=[q_spec, kv_spec, _SMEM, _SMEM],
        out_specs=[a_spec, col_spec, col_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, skv), jnp.int8), col, col],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, 1), jnp.int32)],
        interpret=interpret,
        name="ita_twopass_qk",
    )(q_q, k_q, lmult, meta)

    # DI — one integer inversion per row (two serial dividers in silicon,
    # a vectorized integer divide here), overlapped by XLA with pass 2 setup.
    if adaptive:
        sigma_inv, e_r = adaptive_inverse(sigma)
    else:
        sigma_inv = paper_inverse(sigma)
        e_r = jnp.full_like(sigma_inv, 8)

    k2 = functools.partial(av_en_kernel, causal=causal, window=window,
                           bq=bq, bkv=bkv)
    out = pl.pallas_call(
        k2,
        grid=grid,
        in_specs=[a_spec, col_spec, col_spec, col_spec, kv_spec, _SMEM,
                  _SMEM],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="ita_twopass_av",
    )(a_mat, sigma_inv, e_r, row_max, v_q, omult, meta)
    return out, a_mat


def ita_attention_decode(q_q, k_q, v_q, logit_mult, out_mult, kv_len, *,
                         q_offset=0, q_len=None, causal: bool = True,
                         window: int = 0,
                         adaptive: bool = True, block_kv: int = 128,
                         kv_rep: int = 1, interpret: bool = True):
    """Fused decode step: q (BH, Sq<=8, D) int8 against an int8 KV ring
    buffer ``(BH/kv_rep, C, D)`` with ``kv_len`` valid entries. Single q
    tile (no q grid axis); KV tiles past ``kv_len`` are skipped inside
    the kernel, so cost scales with the *occupied* prefix, not the ring
    capacity — per row: ``kv_len``/``q_offset`` may be (BH,) vectors
    (ragged batch), each row masking and tile-skipping against its own
    prefix. Streaming DA semantics are identical to ``onepass`` at equal
    ``block_kv`` — decode outputs are bit-identical to the matching
    prefill rows. GQA via the ``kv_rep`` row index map."""
    bh, sq, d = q_q.shape
    skv = k_q.shape[1]
    bkv = min(block_kv, skv)
    assert skv % bkv == 0, (skv, bkv)
    assert k_q.shape[0] * kv_rep == bh, (k_q.shape, kv_rep, bh)
    kern = functools.partial(decode_kernel, causal=causal, window=window,
                             adaptive=adaptive, bq=sq, bkv=bkv)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len, bh)
    kv_spec = pl.BlockSpec((1, bkv, d), lambda r, j: (r // kv_rep, j, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, skv // bkv),
        in_specs=[pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
                  kv_spec, kv_spec, _SMEM, _SMEM, _SMEM],
        out_specs=pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.int8),
        scratch_shapes=[pltpu.VMEM((sq, 1), jnp.int32),
                        pltpu.VMEM((sq, 1), jnp.int32),
                        pltpu.VMEM((sq, d), jnp.float32)],
        interpret=interpret,
        name="ita_decode",
    )(q_q, k_q, v_q, lmult, omult, meta)


# ---------------------------------------------------------------------------
# Paged-pool variants: same kernel bodies, page-table-indexed KV blocks
# ---------------------------------------------------------------------------

def _swallow_pt(kern):
    """Scalar-prefetch calling convention hands the page-table and layer
    refs to the kernel body as its first arguments; the compute bodies
    never touch them (all translation happens in the index maps), so
    drop them here — the paged kernels stay byte-for-byte the ring
    kernels."""
    def wrapped(pt_ref, layer_ref, *refs):
        return kern(*refs)
    return wrapped


def _paged_kv_spec(page, d, hq, kv_rep, n_pages, with_q_axis):
    """K/V BlockSpec of the stacked head-major pool: kernel row ``r``
    (batch ``r // hq``, head ``r % hq``) reads its kv head's page
    ``pt[(r // hq) * n_pages + j]`` of layer ``layer[0]`` for logical KV
    tile ``j``."""
    def page_of(r, j, pt, layer):
        return (layer[0], pt[(r // hq) * n_pages + j], (r % hq) // kv_rep,
                0, 0)
    if with_q_axis:
        return pl.BlockSpec((1, 1, 1, page, d),
                            lambda r, i, j, pt, layer: page_of(r, j, pt,
                                                               layer))
    return pl.BlockSpec((1, 1, 1, page, d), page_of)


def _paged_call(name, kern, grid, q_spec, kv_spec, q_q, k_pool, v_pool,
                page_table, layer, lmult, omult, meta, bq, interpret):
    """Shared pallas_call of the paged kernels, named ``name``: the flat
    page table and the layer index are the scalar-prefetch operands the
    K/V index maps read."""
    bh, sq, d = q_q.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, _SMEM, _SMEM, _SMEM],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, d), jnp.float32)])
    return pl.pallas_call(
        _swallow_pt(kern),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), jnp.int8),
        interpret=interpret,
        name=name,
    )(page_table.reshape(-1), jnp.asarray(layer, jnp.int32).reshape(1), q_q,
      k_pool, v_pool, lmult, omult, meta)


def ita_attention_decode_paged(q_q, k_pool, v_pool, page_table, logit_mult,
                               out_mult, kv_len, *, layer=0, q_offset=0,
                               q_len=None, causal: bool = True,
                               window: int = 0, adaptive: bool = True,
                               kv_rep: int = 1, hq: int = 1,
                               interpret: bool = True):
    """Fused decode step over a paged KV pool.

    ``q_q`` (BH, Sq<=8, D) int8; ``k_pool``/``v_pool``
    ``(L, num_pages, G, page_size, D)`` int8 shared head-major arenas,
    one per layer, of which ``layer`` (() int32) is read;
    ``page_table`` ``(B, n_pages)`` int32 maps each sequence's logical KV
    page to a physical arena page (entries beyond the valid prefix may
    point anywhere — those tiles are skipped/masked via ``kv_len``).

    ``block_kv`` is the page size: logical tile ``j`` of kernel row ``r``
    is DMA'd from ``pool[layer, page_table[r // hq, j], kv head]`` by a
    scalar-prefetch index map, and the DA streaming schedule is identical
    to ``ita_attention_decode`` at ``block_kv == page_size`` — paged
    decode is bit-identical to the contiguous ring path (family
    ``ita_fused``).
    """
    bh, sq, d = q_q.shape
    page = k_pool.shape[3]
    n_pages = page_table.shape[1]
    assert bh % hq == 0 and page_table.shape[0] * hq == bh, \
        (bh, hq, page_table.shape)
    kern = functools.partial(decode_kernel, causal=causal, window=window,
                             adaptive=adaptive, bq=sq, bkv=page, paged=True)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len, bh)
    return _paged_call(
        "ita_decode_paged", kern, (bh, n_pages),
        pl.BlockSpec((1, sq, d), lambda b, j, *_: (b, 0, 0)),
        _paged_kv_spec(page, d, hq, kv_rep, n_pages, with_q_axis=False),
        q_q, k_pool, v_pool, page_table, layer, lmult, omult, meta, sq,
        interpret)


def ita_attention_onepass_paged(q_q, k_pool, v_pool, page_table, logit_mult,
                                out_mult, kv_len, *, layer=0, q_offset=0,
                                q_len=None, causal: bool, window: int = 0,
                                adaptive: bool = True, block_q: int = 128,
                                kv_rep: int = 1, hq: int = 1,
                                interpret: bool = True):
    """Flash-style onepass over a paged KV pool (prefill-from-pool, decode
    bursts longer than the decode kernel's single tile, and the mixed
    chunked-prefill/decode serve step). Grid, layer and page translation
    as in
    ``ita_attention_decode_paged``, with the q tiling axis of
    ``ita_attention_onepass`` restored. ``q_len`` (scalar or per-row)
    marks each row's count of valid query rows — ragged q_len: one call
    serves rows with q widths in {1, chunk} (pad rows emit zeros)."""
    bh, sq, d = q_q.shape
    page = k_pool.shape[3]
    n_pages = page_table.shape[1]
    bq = min(block_q, sq)
    assert sq % bq == 0, (sq, bq)
    assert bh % hq == 0 and page_table.shape[0] * hq == bh, \
        (bh, hq, page_table.shape)
    kern = functools.partial(onepass_kernel, causal=causal, window=window,
                             adaptive=adaptive, bq=bq, bkv=page, paged=True)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len, bh)
    return _paged_call(
        "ita_onepass_paged", kern, (bh, sq // bq, n_pages),
        pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
        _paged_kv_spec(page, d, hq, kv_rep, n_pages, with_q_axis=True),
        q_q, k_pool, v_pool, page_table, layer, lmult, omult, meta, bq,
        interpret)
