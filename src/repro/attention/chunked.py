"""Streaming (chunked) attention — the paper's DA/DI/EN dataflow expressed
at the XLA level, so the S×S attention matrix is never materialized (at
train_4k on the assigned configs that matrix would be hundreds of TB).

One skeleton, three arithmetics:

- ``float``    — classic online softmax (exp rescale corrections).
- ``ita_ste``  — QAT forward: base-2, STE-floored exponent shifts and the
                 *same shift-based running-max correction the silicon
                 applies* (training sees deployed semantics).
- ``ita_int``  — serve path: int8 Q·Kᵀ chunks requantized onto the ITA
                 logit grid, grid-aligned integer DA (the max kept as its
                 2^5 bucket, Σ >>= Δbucket, exact), fused ``u=128>>k``
                 numerators, adaptive power-of-two DI — mirrors the Pallas
                 onepass kernel exactly (same semantics at chunk granularity).

Chunking: python loop over q chunks (static) × ``lax.scan`` over the
causally-reachable kv chunks per q chunk (so causal/windowed FLOPs are
~half of dense, matching the analytic roofline). ``scan_unroll`` unrolls
the kv scan for cost-true dry-run lowering.

Lives behind the ``float_xla`` / ``ita_chunked_xla`` registry backends —
call ``repro.attention.dispatch`` rather than this module directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.attention.xla import softcap as _softcap
from repro.core.quant import EPS_MAX, SOFTMAX_SHIFT
from repro.core.softmax import _ste_floor, _ste_round, ita_bucket

NEG = -1e30
Q_CHUNK = 512
KV_CHUNK = 512


def _chunk_mask(cq, ckv, q0, k0, causal, window, kv_len):
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (cq, ckv), 0)
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, (cq, ckv), 1)
    valid = jnp.ones((cq, ckv), jnp.bool_)
    if causal or window > 0:
        valid &= qi >= kj
    if window > 0:
        valid &= (qi - kj) < window
    if kv_len is not None:
        valid &= kj < kv_len
    return valid[None, None, None]


def _gqa_chunk_logits(qc, kc):
    """qc (B,cq,G,M,hd) x kc (B,ckv,G,hd) -> (B,G,M,cq,ckv)."""
    return jnp.einsum("bqgmd,bkgd->bgmqk", qc, kc)


def streaming_attention(q, k, v, *, impl, scale, s_q=None, s_k=None,
                        s_v=None, causal=True, window=0, kv_len=None,
                        softcap=0.0, adaptive=True, q_chunk=Q_CHUNK,
                        kv_chunk=KV_CHUNK, scan_unroll=False):
    """q (B,Sq,H,hd); k/v (B,Skv,G,hd) (int8 for ita_int). Returns
    (B,Sq,H,hd) f32-ish output of softmax(QKᵀ)·V in the chosen arithmetic.
    Static q_offset=0 (decode uses the direct path)."""
    b, sq_in, h, hd = q.shape
    skv_in, g = k.shape[1], k.shape[2]
    m_ = h // g
    # pad sequences to chunk multiples; padded kv is masked via kv_len,
    # padded q rows are sliced off at the end
    cq = min(q_chunk, sq_in)
    ckv = min(kv_chunk, skv_in)
    pad_q, pad_kv = (-sq_in) % cq, (-skv_in) % ckv
    if pad_kv and kv_len is None:
        kv_len = skv_in
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    sq, skv = sq_in + pad_q, skv_in + pad_kv
    n_q = sq // cq
    unroll = bool(scan_unroll)

    if impl == "ita_int":
        # int8 operands stay int8: the dots carry preferred_element_type
        # int32 so XLA emits the MXU int8 path (v5e: 2x bf16 throughput)
        q_i = q.astype(jnp.int8).reshape(b, sq, g, m_, hd)
        k_i = k.astype(jnp.int8)
        v_f = v.astype(jnp.int8)
        lmult = jnp.asarray(s_q * s_k * scale / EPS_MAX, jnp.float32)
        fmult = jnp.asarray(s_q * s_k * scale, jnp.float32)
    elif impl == "ita_ste":
        qq = jnp.clip(_ste_round(q.astype(jnp.float32) / (s_q * 1.0)), -128,
                      127).reshape(b, sq, g, m_, hd)
        kq = jnp.clip(_ste_round(k.astype(jnp.float32) / s_k), -128, 127)
        v_f = v.astype(jnp.float32)
        lmult = s_q * s_k * scale / EPS_MAX
        fmult = s_q * s_k * scale
    else:
        qf = q.astype(jnp.float32).reshape(b, sq, g, m_, hd)
        kf = k.astype(jnp.float32)
        v_f = v.astype(jnp.float32)

    outs = []
    for iq in range(n_q):
        q0 = iq * cq
        # causally reachable kv chunk range (static)
        hi = (min(q0 + cq, skv) + ckv - 1) // ckv if causal \
            else skv // ckv
        lo = 0
        if window > 0:
            lo = max(0, (q0 - window + 1) // ckv)
        n_steps = max(hi - lo, 1)

        if impl == "ita_int":
            qc = q_i[:, q0:q0 + cq]
            carry = (jnp.full((b, g, m_, cq, 1), -8, jnp.int32),
                     jnp.zeros((b, g, m_, cq, 1), jnp.int32),
                     jnp.zeros((b, g, m_, cq, hd), jnp.float32))
        else:
            qc = (qq if impl == "ita_ste" else qf)[:, q0:q0 + cq]
            carry = (jnp.full((b, g, m_, cq, 1), NEG, jnp.float32),
                     jnp.zeros((b, g, m_, cq, 1), jnp.float32),
                     jnp.zeros((b, g, m_, cq, hd), jnp.float32))

        def body(carry, step, qc=qc, q0=q0, lo=lo):
            m, sig, acc = carry
            k0 = (lo + step) * ckv
            kc = jax.lax.dynamic_slice_in_dim(
                k_i if impl == "ita_int" else kf if impl == "float" else kq,
                k0, ckv, 1)
            vc = jax.lax.dynamic_slice_in_dim(v_f, k0, ckv, 1)
            valid = _chunk_mask(cq, ckv, q0, k0, causal, window, kv_len)

            if impl == "ita_int":
                acc32 = jnp.einsum("bqgmd,bkgd->bgmqk", qc, kc,
                                   preferred_element_type=jnp.int32)
                # softcap=0 keeps the pre-multiplied lmult formula —
                # bit-identical requant vs the fused Pallas kernels
                lf = (acc32.astype(jnp.float32) * lmult if not softcap
                      else _softcap(acc32.astype(jnp.float32) * fmult,
                                    softcap) / EPS_MAX)
                lg = jnp.clip(jnp.round(lf), -128, 127).astype(jnp.int32)
                # grid-aligned DA (core.softmax.ita_da_update_aligned): m
                # is the running max bucket, every correction exact
                xb = ita_bucket(lg)
                new_m = jnp.maximum(m, jnp.max(jnp.where(valid, xb, -8), -1,
                                               keepdims=True))
                delta = jnp.clip(new_m - m, 0, 31)
                kk = jnp.where(valid, jnp.clip(new_m - xb, 0, 31), 31)
                # u = 128>>k clipped to int8 (127) so the A·V product also
                # rides the int8 MXU; Σ uses the same clipped numerators so
                # normalization stays consistent (<=0.8% skew on the max
                # element; in silicon u is uint8 and 128 fits exactly).
                u = jnp.minimum(jax.lax.shift_right_logical(
                    jnp.int32(128), kk), 127)
                sig = jax.lax.shift_right_logical(sig, delta) \
                    + 2 * jnp.sum(u, -1, keepdims=True)
                pv = jnp.einsum("bgmqk,bkgd->bgmqd", u.astype(jnp.int8), vc,
                                preferred_element_type=jnp.int32)
                acc = acc * jnp.exp2(-delta.astype(jnp.float32)) \
                    + pv.astype(jnp.float32)
                return (new_m, sig, acc), None

            s = _gqa_chunk_logits(qc, kc)
            if impl == "ita_ste":
                lf = (s * lmult if not softcap
                      else _softcap(s * fmult, softcap) / EPS_MAX)
                lg = jnp.clip(_ste_round(lf), -128.0, 127.0)
                x = jnp.where(valid, lg, NEG)
                new_m = jnp.maximum(m, jnp.max(x, -1, keepdims=True))
                delta = _ste_floor(jnp.clip(
                    (new_m - m) / 2.0 ** SOFTMAX_SHIFT, 0.0, 1e4))
                kk = _ste_floor((new_m - lg) / 2.0 ** SOFTMAX_SHIFT)
                w = jnp.where(valid, jnp.exp2(-jnp.clip(kk, 0.0, 30.0)), 0.0)
                corr = jnp.exp2(-jnp.minimum(delta, 30.0))
            else:
                s = _softcap(s * scale, softcap)
                x = jnp.where(valid, s, NEG)
                new_m = jnp.maximum(m, jnp.max(x, -1, keepdims=True))
                w = jnp.where(valid, jnp.exp(s - new_m), 0.0)
                corr = jnp.exp(m - new_m)
            sig = sig * corr + jnp.sum(w, -1, keepdims=True)
            acc = acc * corr + jnp.einsum("bgmqk,bkgd->bgmqd", w, vc)
            return (new_m, sig, acc), None

        (m, sig, acc), _ = jax.lax.scan(
            body, carry, jnp.arange(n_steps),
            unroll=n_steps if unroll else 1)

        if impl == "ita_int":
            sig = jnp.maximum(sig, 1)
            if adaptive:
                e_r = 31 - jax.lax.clz(sig)
            else:                       # paper DI: e_r pinned to 8 (2^16/σ)
                e_r = jnp.full_like(sig, 8)
            pre = jnp.maximum(e_r + 8 - 30, 0)
            inv = (jnp.int32(1) << jnp.minimum(e_r + 8 - pre, 30)) \
                // jax.lax.shift_right_logical(sig, pre)
            o = acc * (2.0 * inv.astype(jnp.float32)
                       * jnp.exp2(-(e_r + 8).astype(jnp.float32))) \
                * jnp.asarray(s_v, jnp.float32)
        else:
            o = acc / jnp.maximum(sig, 1e-9)
        outs.append(o)                              # (B,G,M,cq,hd)

    out = jnp.concatenate(outs, axis=3) if n_q > 1 else outs[0]
    out = jnp.moveaxis(out, 3, 1)                   # (B,Sq,G,M,hd)
    return out.reshape(b, sq, h, hd)[:, :sq_in]
