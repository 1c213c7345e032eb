"""Fused ITA attention Pallas kernels: Q·Kᵀ → streaming integer softmax → A·V.

Two dataflows, both with the ITA integer softmax:

- ``onepass`` (beyond-paper, flash-style): the int8 attention tile never
  leaves VMEM. Per (q-tile, kv-tile): int8 Q·Kᵀ on the MXU → requant to the
  ITA logit grid → grid-aligned DA update of the per-row (max bucket, Σ)
  stats → the *unnormalized* numerators ``u = 128 >> k`` multiply V on the
  MXU and add into a running int32 accumulator which is shift-corrected
  when the row's max bucket grows (the correction Σ gets too). DI happens
  once per row at the final kv tile and folds into the output requant as a
  per-row multiplier. HBM traffic for the S×S matrix: zero.

- ``twopass`` (paper-faithful): pass 1 streams Q·Kᵀ tiles, writes the int8
  attention matrix A to HBM exactly once and accumulates the (max, Σ) row
  stats on the fly (DA); DI inverts Σ per row; pass 2 re-streams A, norma-
  lizes each element with a pure shift (EN, ``p = Σ_inv >> k``) and feeds
  the MXU for A·V. This reproduces ITA's memory traffic: A written once,
  read once, softmax adds **no** extra passes.

Integer semantics notes:
- the fused kernels (onepass, decode and their paged forms) take a key's
  exponent on the 2^5 grid: ``k = (max >> 5) - (x >> 5)``, the difference
  of the row max's and the logit's 3-bit buckets
  (``kernels.common.da_update_aligned``; pure-jnp twin
  ``core.softmax.ita_softmax_aligned_int``). The running max is kept as
  its bucket, so it grows by whole buckets, ``delta``, and a late
  correction composes exactly with every earlier shift. Once a row has
  seen all its tiles each key carries ``k`` against the final bucket,
  ``k <= 7`` on live keys, so every term ``u = 2^(7-k)`` of the
  accumulators is divisible by 2^delta at each correction: ``Σ >> delta``
  and ``acc >> delta`` drop no bit. The kernels therefore equal the
  one-shot arithmetic (``ref.ita_attention_fused_ref``) bit for bit at
  any tiling and whatever order the row maxima arrive in, and paged
  decode equals the ring and prefill at any page size.
- What departs from the paper: the paper's multi-part update keeps the
  max itself and corrects by ``(m_new - m_old) >> 5``. Those floors do
  not compose (``floor(a) + floor(b)`` can be one short of ``floor(a +
  b)``), so each max update can leave every earlier key with twice its
  one-shot weight, and the integer ``Σ`` shift drops bits: over a 4k
  context whose max keeps growing the error builds up with depth. The
  aligned grid keeps the paper's quantisation (one factor of two per
  bucket of 32 logit steps), with the bucket edges fixed on the logit
  grid instead of hung from the row max; a row whose max is 127 (the
  int8 clip, a bucket edge) gets exactly the paper's one-shot result.
  The two-pass kernel and ``core.softmax.ita_softmax_streaming`` keep
  the paper's update.
- Σ and the A·V accumulator: ``Σ = Σ 2u <= 256 * kv_len`` (2^20 at 4k).
  The numerator·V matmuls (onepass ``u``, twopass ``p``) run as bf16 x
  bf16 -> f32 on the MXU, which is exact: ``u, p <= 256`` and ``|v| <=
  128`` are exact in bf16, every product is exact in f32, and a tile's
  sum stays below 2^24 (``128 * 128 * bkv`` = 2^21 at bkv 128), where f32
  integers are exact. The fused kernels convert each tile's sum to int32
  and keep the running accumulator there: ``|acc| <= 128 * 128 *
  kv_len``, 2^26 at 4k, exact up to 2^17 keys. The output requant
  converts it to f32 once (rounding only past 2^24, as the one-shot twin
  does). Mosaic has no int32 matmul, and ``u`` reaches 128, which int8
  cannot hold. The two-pass A·V accumulator stays f32: for paper mode
  (e_r = 8) ``Σ p <= 256`` bounds it by 2^15, exact.
- onepass uses ``u = 128 >> k`` (the missing factor 2 folds into the
  output requant).
- the output: int8 on the ``s_out`` grid (``out_mult = s_v / s_out``,
  rounded and clipped), or with ``float_out`` the f32 attention output
  itself (``out_mult = s_v``), which the model's float specs take. An
  output past ``127 * s_out`` is clipped on the int8 grid; a model whose
  outputs reach that far (a peaked row returns one value row of V) loses
  them at every layer, and over 32 layers that was most of the served
  path's distance from float32 (PERF.md).
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
(a block is the page of every kv head, a layout Mosaic tiles) through a
**page table** delivered as a flat scalar-prefetch operand, next to the
layer index — the KV BlockSpec index map reads
``page_table[b * n_pages + j]`` to translate logical KV tile ``j`` of
sequence ``b`` into a physical page of layer ``layer``'s arena, so
scattered pages stream through the very same per-head arithmetic
(``paged_heads_kernel``) tile-for-tile. The aligned DA makes the result
independent of the tile schedule, so paged decode is bit-identical to
the ring (the ``ita_fused`` family invariant) at ``block_kv ==
page_size`` and at any other page size.

Both paged kernels take one grid step per (sequence, q tile, logical
page), not per (sequence, head, q tile, page): the block is the page of
every kv head, and ``paged_heads_kernel`` streams the sequence's query
heads through it: the ``kv_rep`` heads of a kv head as one stacked tile
(GQA), and as many kv heads laid out together as fit ``HEAD_ROWS`` rows
(16 MHA heads of a decode tile, 4 of a 32-row chunk). A step per head
spent most of a call in the grid's per-step cost: 28 heads x 130 pages
x 16 slots a layer of chunked prefill for qwen2-7b at 16k. Paged decode
is the case of one q tile of ``sq <= 8`` rows. The page table both read
stops at each sequence's last live page (``_live_page_table``), so pages
past it cost neither DMA nor work, and an empty slot costs one step per
page. A q tile wider than ``NARROW`` (8) rows takes its width from the
sequence's live query rows in it: a mixed serve step's decoding rows (one
query each) stream each head's first 8 rows, the decode call's tile and
head layout, instead of the chunk-wide tile, and a sequence that carries
no query (a finished row) does no work and reads no page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import INT8_MAX, INT8_MIN, SOFTMAX_SHIFT
from repro.kernels.common import (MASK_K, NEG_BUCKET, NEG_SENTINEL,
                                  adaptive_inverse, da_update,
                                  da_update_aligned, paper_inverse, pow2_neg,
                                  tile_mask)


def _qk_logits(q_tile, k_tile, mult):
    """int8 Q (bq,d) x int8 K (bkv,d)^T -> int32 -> requant to int8 logit
    grid (returned widened to int32)."""
    acc = jax.lax.dot_general(q_tile, k_tile, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = jnp.round(acc.astype(jnp.float32) * mult)
    return jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int32)


def _row_scalars(lmult_ref, omult_ref, meta_ref, r=None):
    """Kernel row ``r``'s (logit_mult, out_mult, kv_len, q_offset, q_len)
    from the whole-array SMEM operands (meta is flat, 3 entries per
    row); ``r`` defaults to the grid's row ``pl.program_id(0)``."""
    if r is None:
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


def _stream_tile(q_tile, k_tile, v_tile, lmult, b_ref, sigma_ref, acc_ref,
                 valid, at=...):
    """One onepass DA step: logits -> grid-aligned (max bucket, Σ) update
    -> shift-corrected A·V accumulate. The tile's exact f32 product joins
    the int32 accumulator, whose ``>> delta`` correction drops no bit (see
    ``da_update_aligned``). ``at`` picks a head's slot of the scratch."""
    logits = _qk_logits(q_tile, k_tile, lmult)
    u, delta = da_update_aligned(b_ref, sigma_ref, logits, valid, at)
    acc_ref[at] = (acc_ref[at] >> delta) \
        + _pv(u, v_tile).astype(jnp.int32)


def _init_stream(b_ref, sigma_ref, acc_ref):
    b_ref[...] = jnp.full_like(b_ref, NEG_BUCKET)
    sigma_ref[...] = jnp.zeros_like(sigma_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _emit(o_ref, y, float_out, h=0):
    """Output tile ``h``: ``y`` as it is (f32, the dequantized attention
    output), or rounded and clipped onto the int8 ``s_out`` grid."""
    if float_out:
        o_ref[h] = y
    else:
        o_ref[h] = jnp.clip(jnp.round(y), INT8_MIN, INT8_MAX).astype(jnp.int8)


def _finalize_onepass(o_ref, sigma_ref, acc_ref, omult, adaptive,
                      float_out, at=..., h=0, pad=0):
    """DI and the output requant of a row's stats: ``at`` picks a head's
    rows of the scratch, ``h`` its tile of the output block (one row's
    whole scratch and tile by default); ``pad`` zero rows follow them in
    the tile."""
    if adaptive:
        inv, e_r = adaptive_inverse(sigma_ref[at])
    else:
        inv = paper_inverse(sigma_ref[at])
        e_r = jnp.full_like(inv, 8)
    # out = acc * 2 * inv * 2^-(e_r+8) * omult (s_v/s_out, or s_v for a
    # float output); the 2 restores the halved numerator unit (u = 128>>k
    # vs the paper's 256>>k).
    scale = 2.0 * inv.astype(jnp.float32) * pow2_neg(e_r + 8) * omult
    y = acc_ref[at].astype(jnp.float32) * scale
    if pad:
        y = jnp.concatenate([y, jnp.zeros((pad, y.shape[1]), y.dtype)])
    _emit(o_ref, y, float_out, h)


def onepass_kernel(q_ref, k_ref, v_ref, lmult_ref, omult_ref, meta_ref,
                   o_ref, b_ref, sigma_ref, acc_ref,
                   *, causal: bool, window: int, adaptive: bool,
                   bq: int, bkv: int, float_out: bool = False):
    i, j = pl.program_id(1), pl.program_id(2)
    lmult, omult, kv_len, q_off, q_len = _row_scalars(lmult_ref, omult_ref,
                                                      meta_ref)

    @pl.when(j == 0)
    def _init():
        _init_stream(b_ref, sigma_ref, acc_ref)

    # KV tiles wholly beyond this row's valid prefix are fully masked —
    # DA/acc no-ops — so skip their MXU work: chunked-prefill rows stream
    # only their occupied pages, not the whole pool.
    @pl.when(j * bkv < kv_len)
    def _tile():
        valid = tile_mask(i, j, bq, bkv, causal, window, kv_len, q_off, q_len)
        _stream_tile(q_ref[0], k_ref[0], v_ref[0], lmult, b_ref, sigma_ref,
                     acc_ref, valid)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        _finalize_onepass(o_ref, sigma_ref, acc_ref, omult, adaptive,
                          float_out)


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
                 *, causal: bool, window: int, bq: int, bkv: int,
                 float_out: bool = False):
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
        _emit(o_ref, acc_ref[...] * pow2_neg(er_ref[0]) * omult_ref[r],
              float_out)


def decode_kernel(q_ref, k_ref, v_ref, lmult_ref, omult_ref, meta_ref,
                  o_ref, b_ref, sigma_ref, acc_ref,
                  *, causal: bool, window: int, adaptive: bool,
                  bq: int, bkv: int, float_out: bool = False):
    """Onepass dataflow without a q grid axis (decode: sq <= one tile)."""
    j = pl.program_id(1)
    lmult, omult, kv_len, q_off, q_len = _row_scalars(lmult_ref, omult_ref,
                                                      meta_ref)

    @pl.when(j == 0)
    def _init():
        _init_stream(b_ref, sigma_ref, acc_ref)

    # Ring buffers are allocated at max_len; tiles wholly beyond the valid
    # prefix are fully masked (max/sigma/acc all no-ops) — skip the MXU work.
    @pl.when(j * bkv < kv_len)
    def _tile():
        valid = tile_mask(0, j, bq, bkv, causal, window, kv_len, q_off, q_len)
        _stream_tile(q_ref[0], k_ref[0], v_ref[0], lmult, b_ref, sigma_ref,
                     acc_ref, valid)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        _finalize_onepass(o_ref, sigma_ref, acc_ref, omult, adaptive,
                          float_out)


# The paged kernels stack the query heads that share a kv head into one
# (kv_rep * bq, d) tile (one Q·Kᵀ and one A·V matmul per kv head), and
# lay out together the most kv heads that divide the kv head count and
# whose stacked rows fit in HEAD_ROWS (one kv head at least). One
# layer-call on a v5e, 16 sequences, ms by kv heads together:
# - decode, phi3-mini (32 heads, MHA, 300-3500 tokens): 16 (the rule)
#   0.83, 8 1.10, 32 1.18, 1 4.0;
# - 32-row chunks, phi3-mini (2 chunk rows, 14 decode rows): 4 (the
#   rule) 2.54, 2 3.52, 8 2.86, 16 3.53; one step per head 8.57;
# - 32-row chunks, qwen2-7b (28/4 heads, 8 chunk rows, 8 decode rows,
#   2k-16k tokens): 7 heads stacked (224 rows, the rule) 4.35, two kv
#   heads 5.50, four 7.19; heads not stacked, 4 together 6.75, 7 7.71,
#   14 9.35, 1 16.7; one step per head 28.5.
HEAD_ROWS = 128

# The decode call's query tile: a q tile whose sequence carries at most
# this many live query rows streams each head's first NARROW rows, as
# the decode-paged call does, instead of the whole tile.
NARROW = 8


def _head_group(g: int, rows: int) -> int:
    """Kv heads laid out together, of ``g``, each ``rows`` stacked rows."""
    return max(n for n in range(1, g + 1)
               if g % n == 0 and (n == 1 or n * rows <= HEAD_ROWS))


def paged_heads_kernel(*refs, causal: bool, window: int, adaptive: bool,
                       hq: int, kv_rep: int, group: int, narrow_group: int,
                       bq: int, bkv: int, q_tiles: int,
                       float_out: bool = False):
    """The onepass dataflow with one grid step per (sequence, q tile, KV
    page): the block holds the page of every kv head and the body streams
    each of the sequence's ``hq`` query heads through it, as
    ``onepass_kernel`` streams row ``b * hq + h`` (the same arithmetic on
    the same tiles, so the same bits). A grid step per head would pay the
    grid's per-step cost ``hq`` times over for one head's tile of work.

    The ``kv_rep`` query heads of a kv head stream as one stacked
    ``(kv_rep * bq, d)`` tile: the DA statistics are per row, so each
    head's rows see the same arithmetic as alone, under their own mask
    and logit multiplier. A page is skipped when it lies past every
    head's valid prefix; within a live page a head past its own prefix
    streams fully masked rows, which change none of its (bucket, Σ,
    accumulator), so the kv heads' tiles run with no branch between them
    and ``group`` of them are laid out together for the scheduler to
    overlap. The scratch holds a ``(kv_rep * bq, ·)`` slot per kv head.

    With ``narrow_group`` (a tile wider than ``NARROW`` rows) the refs
    hold a second view of the q block, each head's first ``NARROW`` rows
    of the tile, and the tile's width follows the sequence's live query
    rows in it (the most of its heads'): more than ``NARROW`` streams the
    whole tile; 1 to ``NARROW`` streams the narrow view, ``(kv_rep *
    NARROW, d)`` stacks at the head of each kv head's slot,
    ``narrow_group`` kv heads together (the decode call's tile and
    layout), and pads the output tile with zero rows, which is what the
    wide tile gives rows with no query; none does no work on any page."""
    if narrow_group:
        q_ref, q8_ref, *refs = refs
    else:
        q_ref, *refs = refs
    (k_ref, v_ref, lmult_ref, omult_ref, meta_ref, o_ref, b_ref, sigma_ref,
     acc_ref) = refs
    s, j = pl.program_id(0), pl.program_id(2)
    i = pl.program_id(1) if q_tiles > 1 else 0
    rows = s * hq
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        _init_stream(b_ref, sigma_ref, acc_ref)

    def lengths(h, carry):  # the longest prefix (and most live q rows)
        r = 3 * (rows + h)
        kv_len = jnp.maximum(carry[0], meta_ref[r])
        if not narrow_group:
            return (kv_len,)
        return kv_len, jnp.maximum(
            carry[1], jnp.clip(meta_ref[r + 2] - i * bq, 0, bq))
    seq_len, *live = jax.lax.fori_loop(
        0, hq, lengths, (jnp.int32(0),) * (2 if narrow_group else 1))

    def kv_head(kv, q_tile, tile):
        h0 = kv * kv_rep
        heads = [_row_scalars(lmult_ref, omult_ref, meta_ref, rows + h0 + r)
                 for r in range(kv_rep)]
        # the tile's rows, counted in tiles of its own width
        masks = [tile_mask(i * (bq // tile), j, tile, bkv, causal, window,
                           kv_len, q_off, q_len)
                 for _, _, kv_len, q_off, q_len in heads]
        if kv_rep == 1:
            lmult, valid = heads[0][0], masks[0]
        else:        # one logit multiplier per row: each head's own
            lmult = jnp.concatenate([jnp.full((tile, 1), m)
                                     for m, *_ in heads])
            valid = jnp.concatenate(masks)
        q = q_tile[pl.ds(h0, kv_rep)].reshape(kv_rep * tile, d)
        at = kv if tile == bq else (kv, pl.ds(0, kv_rep * tile))
        _stream_tile(q, k_ref[0, 0, kv], v_ref[0, 0, kv], lmult, b_ref,
                     sigma_ref, acc_ref, valid, at)

    def stream(q_tile, tile, n):
        def kv_heads(t, carry):
            for u in range(n):
                kv_head(t * n + u, q_tile, tile)
            return carry
        jax.lax.fori_loop(0, hq // kv_rep // n, kv_heads, 0)

    def finalize(tile):
        def head(h, carry):
            at = (h // kv_rep,
                  pl.ds(pl.multiple_of(h % kv_rep * tile, tile), tile))
            _finalize_onepass(o_ref, sigma_ref, acc_ref, omult_ref[rows + h],
                              adaptive, float_out, at, h, bq - tile)
            return carry
        jax.lax.fori_loop(0, hq, head, 0)

    page = j * bkv < seq_len
    if not narrow_group:
        pl.when(page)(lambda: stream(q_ref, bq, group))
        pl.when(j == pl.num_programs(2) - 1)(lambda: finalize(bq))
        return
    live, = live
    wide, last = live > NARROW, j == pl.num_programs(2) - 1
    pl.when(page & wide)(lambda: stream(q_ref, bq, group))
    pl.when(page & (live > 0) & ~wide)(
        lambda: stream(q8_ref, NARROW, narrow_group))
    pl.when(last & wide)(lambda: finalize(bq))
    pl.when(last & ~wide)(lambda: finalize(NARROW))


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _out_dtype(float_out: bool):
    """int8 on the ``s_out`` grid (``out_mult = s_v / s_out``), or, with
    ``float_out``, the f32 attention output itself (``out_mult = s_v``):
    what a caller that wants float gets without the int8 requant, whose
    clip at ``127 * s_out`` would cut every larger output."""
    return jnp.float32 if float_out else jnp.int8


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
                          float_out: bool = False, interpret: bool = True):
    """q (BH, Sq, D) int8; k/v (BH/kv_rep, Skv, D) int8; returns (BH, Sq, D)
    int8 (f32 with ``float_out``, see ``_out_dtype``). GQA: q row r reads
    kv row r // kv_rep via the index map — the KV head broadcast never
    materializes."""
    bh, sq, d = q_q.shape
    skv = k_q.shape[1]
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    assert k_q.shape[0] * kv_rep == bh, (k_q.shape, kv_rep, bh)
    kern = functools.partial(onepass_kernel, causal=causal, window=window,
                             adaptive=adaptive, bq=bq, bkv=bkv,
                             float_out=float_out)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len, bh)
    kv_spec = pl.BlockSpec((1, bkv, d), lambda b, i, j: (b // kv_rep, j, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, sq // bq, skv // bkv),
        in_specs=[pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
                  kv_spec, kv_spec, _SMEM, _SMEM, _SMEM],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), _out_dtype(float_out)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, 1), jnp.int32),
                        pltpu.VMEM((bq, d), jnp.int32)],
        interpret=interpret,
        name="ita_onepass",
    )(q_q, k_q, v_q, lmult, omult, meta)


def ita_attention_twopass(q_q, k_q, v_q, logit_mult, out_mult, kv_len, *,
                          q_offset=0, causal: bool, window: int = 0,
                          adaptive: bool = False, block_q: int = 128,
                          block_kv: int = 128, kv_rep: int = 1,
                          float_out: bool = False, interpret: bool = True):
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
                           bq=bq, bkv=bkv, float_out=float_out)
    out = pl.pallas_call(
        k2,
        grid=grid,
        in_specs=[a_spec, col_spec, col_spec, col_spec, kv_spec, _SMEM,
                  _SMEM],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), _out_dtype(float_out)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="ita_twopass_av",
    )(a_mat, sigma_inv, e_r, row_max, v_q, omult, meta)
    return out, a_mat


def ita_attention_decode(q_q, k_q, v_q, logit_mult, out_mult, kv_len, *,
                         q_offset=0, q_len=None, causal: bool = True,
                         window: int = 0,
                         adaptive: bool = True, block_kv: int = 128,
                         kv_rep: int = 1, float_out: bool = False,
                         interpret: bool = True):
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
                             adaptive=adaptive, bq=sq, bkv=bkv,
                             float_out=float_out)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len, bh)
    kv_spec = pl.BlockSpec((1, bkv, d), lambda r, j: (r // kv_rep, j, 0))
    return pl.pallas_call(
        kern,
        grid=(bh, skv // bkv),
        in_specs=[pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
                  kv_spec, kv_spec, _SMEM, _SMEM, _SMEM],
        out_specs=pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), _out_dtype(float_out)),
        scratch_shapes=[pltpu.VMEM((sq, 1), jnp.int32),
                        pltpu.VMEM((sq, 1), jnp.int32),
                        pltpu.VMEM((sq, d), jnp.int32)],
        interpret=interpret,
        name="ita_decode",
    )(q_q, k_q, v_q, lmult, omult, meta)


# ---------------------------------------------------------------------------
# Paged-pool variants: same kernel bodies, page-table-indexed KV blocks
# ---------------------------------------------------------------------------

def _swallow_pt(kern):
    """Scalar-prefetch calling convention hands the page-table and layer
    refs to the kernel body as its first arguments; the compute body
    never touches them (all translation happens in the index maps), so
    drop them here."""
    def wrapped(pt_ref, layer_ref, *refs):
        return kern(*refs)
    return wrapped


def _live_page_table(page_table, kv_len, hq, page_size, q_len=None):
    """The page table with every entry past a sequence's last live page
    (the most of its heads' ``kv_len``) replaced by that page: the kernel
    skips those tiles' work, and a block index that repeats the previous
    step's costs no DMA, so a call reads each sequence's live pages and
    nothing past them. With ``q_len``, a sequence none of whose heads
    carries a query has no live page."""
    b, n_pages = page_table.shape
    live = kv_len.reshape(b, hq).max(1)
    if q_len is not None:
        live = jnp.where(q_len.reshape(b, hq).max(1) > 0, live, 0)
    last = jnp.clip(-(-live // page_size) - 1, 0, n_pages - 1)
    tiles = jnp.minimum(jnp.arange(n_pages, dtype=jnp.int32)[None, :],
                        last[:, None])
    return jnp.take_along_axis(page_table, tiles, axis=1)


def _paged_heads_call(name, q_q, k_pool, v_pool, page_table, logit_mult,
                      out_mult, kv_len, *, layer, q_offset, q_len, causal,
                      window, adaptive, bq, kv_rep, hq, float_out,
                      interpret):
    """The paged kernels' shared ``pallas_call``, named ``name``: grid
    (sequence, q tile, logical page), ``paged_heads_kernel`` as the body.
    Logical page ``j`` of sequence ``s`` is DMA'd for all ``G`` kv heads
    at once from ``pool[layer, page_table[s, j]]`` by a scalar-prefetch
    index map (the flat page table and the layer index are the two
    scalar-prefetch operands), after the table is cut at each sequence's
    last live page. The q and output blocks are the sequence's ``hq``
    rows of a q tile; the (max bucket, Σ, accumulator) scratch has a slot
    of ``kv_rep * bq`` rows per kv head. A tile wider than ``NARROW``
    rows (a multiple of it) also reads the q operand as a narrow block,
    each head's first ``NARROW`` rows of the tile, and the table cut
    drops the sequences that carry no query."""
    bh, sq, d = q_q.shape
    page, g = k_pool.shape[3], k_pool.shape[2]
    b, n_pages = page_table.shape
    assert sq % bq == 0, (sq, bq)
    assert b * hq == bh and hq == g * kv_rep, \
        (bh, hq, g, kv_rep, page_table.shape)
    narrow = bq > NARROW and bq % NARROW == 0
    kern = functools.partial(
        paged_heads_kernel, causal=causal, window=window, adaptive=adaptive,
        hq=hq, kv_rep=kv_rep, group=_head_group(g, kv_rep * bq),
        narrow_group=_head_group(g, kv_rep * NARROW) if narrow else 0,
        bq=bq, bkv=page, q_tiles=sq // bq, float_out=float_out)
    lmult, omult = _row_mults(logit_mult, out_mult, bh)
    meta = _row_meta(kv_len, q_offset, sq if q_len is None else q_len,
                     bh).reshape(bh, 3)
    page_table = _live_page_table(page_table, meta[:, 0], hq, page,
                                  meta[:, 2] if narrow else None)

    def kv_block(s, i, j, pt, lyr):
        return (lyr[0], pt[s * n_pages + j], 0, 0, 0)
    q_spec = pl.BlockSpec((hq, bq, d), lambda s, i, j, *_: (s, i, 0))
    kv_spec = pl.BlockSpec((1, 1, g, page, d), kv_block)
    q_specs, q_args = [q_spec], [q_q]
    if narrow:
        q_specs.append(pl.BlockSpec(
            (hq, NARROW, d), lambda s, i, j, *_: (s, i * (bq // NARROW), 0)))
        q_args.append(q_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, sq // bq, n_pages),
        in_specs=[*q_specs, kv_spec, kv_spec, _SMEM, _SMEM, _SMEM],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((g, kv_rep * bq, 1), jnp.int32),
                        pltpu.VMEM((g, kv_rep * bq, 1), jnp.int32),
                        pltpu.VMEM((g, kv_rep * bq, d), jnp.int32)])
    return pl.pallas_call(
        _swallow_pt(kern),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), _out_dtype(float_out)),
        interpret=interpret,
        name=name,
    )(page_table.reshape(-1), jnp.asarray(layer, jnp.int32).reshape(1),
      *q_args, k_pool, v_pool, lmult, omult, meta.reshape(-1))


def ita_attention_decode_paged(q_q, k_pool, v_pool, page_table, logit_mult,
                               out_mult, kv_len, *, layer=0, q_offset=0,
                               q_len=None, causal: bool = True,
                               window: int = 0, adaptive: bool = True,
                               kv_rep: int = 1, hq: int = 1,
                               float_out: bool = False,
                               interpret: bool = True):
    """Fused decode step over a paged KV pool.

    ``q_q`` (BH, Sq<=8, D) int8; ``k_pool``/``v_pool``
    ``(L, num_pages, G, page_size, D)`` int8 shared head-major arenas,
    one per layer, of which ``layer`` (() int32) is read;
    ``page_table`` ``(B, n_pages)`` int32 maps each sequence's logical KV
    page to a physical arena page (entries beyond the valid prefix may
    point anywhere — those tiles are skipped/masked via ``kv_len``).

    One q tile holds every query: the grid is (sequence, 1, logical
    page), see ``_paged_heads_call``. Pages past a sequence's last live
    page are neither read nor computed. Per head the DA streaming
    schedule is ``ita_attention_decode``'s at ``block_kv == page_size`` —
    paged decode is bit-identical to the contiguous ring path (family
    ``ita_fused``).
    """
    return _paged_heads_call(
        "ita_decode_paged", q_q, k_pool, v_pool, page_table, logit_mult,
        out_mult, kv_len, layer=layer, q_offset=q_offset, q_len=q_len,
        causal=causal, window=window, adaptive=adaptive, bq=q_q.shape[1],
        kv_rep=kv_rep, hq=hq, float_out=float_out, interpret=interpret)


def ita_attention_onepass_paged(q_q, k_pool, v_pool, page_table, logit_mult,
                                out_mult, kv_len, *, layer=0, q_offset=0,
                                q_len=None, causal: bool, window: int = 0,
                                adaptive: bool = True, block_q: int = 128,
                                kv_rep: int = 1, hq: int = 1,
                                float_out: bool = False,
                                interpret: bool = True):
    """Flash-style onepass over a paged KV pool (prefill-from-pool, decode
    bursts longer than the decode kernel's single tile, and the mixed
    chunked-prefill/decode serve step). Pool, page table and layer as in
    ``ita_attention_decode_paged``, with the q tiling axis of
    ``ita_attention_onepass`` restored: the grid is (sequence, q tile,
    logical page), see ``_paged_heads_call``. ``q_len`` (scalar or
    per-row) marks each row's count of valid query rows — ragged q_len:
    one call serves rows with q widths in {1, chunk} (pad rows emit
    zeros)."""
    return _paged_heads_call(
        "ita_onepass_paged", q_q, k_pool, v_pool, page_table, logit_mult,
        out_mult, kv_len, layer=layer, q_offset=q_offset, q_len=q_len,
        causal=causal, window=window, adaptive=adaptive,
        bq=min(block_q, q_q.shape[1]), kv_rep=kv_rep, hq=hq,
        float_out=float_out, interpret=interpret)
