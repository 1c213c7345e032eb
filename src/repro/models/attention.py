"""Multi-head attention layer: projections + RoPE + KV caching around the
unified attention engine (``repro.attention``).

``attention_impl``:
- ``float`` — bf16/f32 softmax attention (baseline).
- ``ita``   — 8-bit quantized pipeline with the ITA integer softmax:
              * serve (prefill/decode): true integer path — int8 Q·Kᵀ
                (int32 accum), requant onto the ITA logit grid, shift-only
                softmax (adaptive per-row scale by default), int A·V; the
                KV cache is stored int8 (halving cache bytes vs bf16).
              * train: differentiable QAT forward (STE round/floor) matching
                the deployed integer semantics — the paper's QAT-trained
                clipping in action.
- ``ibert`` — same quantized pipeline with I-BERT's 32-bit polynomial
              softmax (the paper's accuracy baseline).

This module owns the *layer*: weight init, projections, RoPE, sharding
hints and ring-buffer bookkeeping (``repro.attention.KVCacheState``). The
attention computation itself — which kernel/XLA path serves a given
(mode, features) combination — is entirely the registry's decision:
one ``AttentionSpec`` + ``QuantScales`` per call, ``dispatch`` picks the
backend (``cfg.attention_backend`` pins one explicitly). GQA is native
(no KV broadcast); sliding-window, logit softcap and cross-attention
(audio/vision memory) are supported — see DESIGN.md §Arch-applicability
for how each assigned architecture uses these, and DESIGN.md §Backends
for the capability matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import attention as ATT
from repro.attention.xla import quantize_to_int8
from repro.launch import hints
from repro.models.layers import _normal, dense, rope


def init_attention(key, cfg, cross: bool = False):
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    # cross-attn consumes the frontend memory *after* projection to d_model
    kv_in = d
    p = {"wq": _normal(ks[0], (d, h * hd), d ** -0.5),
         "wk": _normal(ks[1], (kv_in, g * hd), kv_in ** -0.5),
         "wv": _normal(ks[2], (kv_in, g * hd), kv_in ** -0.5),
         "wo": _normal(ks[3], (h * hd, d), (h * hd) ** -0.5)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((g * hd,), jnp.float32)
        p["bv"] = jnp.zeros((g * hd,), jnp.float32)
    if cfg.attention_impl != "float":
        # Calibrated quantization scales (QAT-trainable), one per tensor
        # role — the clipping thresholds the paper learns with QAT.
        # s_out requantizes the attention output onto an int8 grid between
        # blocks (the fused decode kernel's out_mult = s_v / s_out).
        for name in ("s_q", "s_k", "s_v", "s_out"):
            p[name] = jnp.asarray(0.05, jnp.float32)
    return p


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def make_spec(cfg, *, mode, causal, window, q_len=None,
              has_s_out=True, layout="bshd",
              ragged_q=False) -> ATT.AttentionSpec:
    """The layer's view of the engine: one spec per (cfg, call site).
    ``has_s_out=False`` declares a legacy param set without the output
    requant scale — the fused kernels then decline and the XLA paths
    serve (PR-1 fallback semantics, now a capability). ``layout``
    deviates from the model's ``bshd`` only for paged-pool decode
    (``bhsd_paged``), where the KV operand is the shared arena.
    ``ragged_q`` declares the mixed chunked-prefill/decode call (per-row
    valid query counts ride the ``q_lens`` dispatch operand)."""
    return ATT.AttentionSpec(
        mode=mode, impl=cfg.attention_impl, causal=causal, window=window,
        softcap=cfg.attn_softcap, query_scale=cfg.query_scale,
        softmax="paper" if cfg.softmax_impl == "ita_paper" else "adaptive",
        layout=layout, scale_kind="per_tensor", out_dtype="float",
        has_s_out=has_s_out, q_len=q_len, n_heads=cfg.n_heads,
        ragged_q=ragged_q)


def apply_attention(params, x, *, cfg, kind="global", positions=None,
                    mem=None, cache=None, mode="train", lengths=None,
                    live=None, q_lens=None):
    """Full attention layer: projections + RoPE + engine dispatch + output
    projection.

    ``kind``: global | local (cfg.local_window) | swa (cfg.window) | cross.
    ``cache`` (serve): ``KVCacheState`` ring buffer (int8 for quantized
    impls, compute dtype for float) or a ``PagedKVState`` pool
    (continuous batching — decode attends through the shared arena via
    the ``bhsd_paged`` capability), or a ``{"k8", "v8"}`` dict for the
    static cross-attention memory; returns (y, new_cache).
    ``lengths`` (B,): ragged prefill — per-sequence valid prompt lengths
    of a right-padded batch; the ring buffer records them as each row's
    stream position so decode continues raggedly (causal masking keeps
    valid rows exact; pad rows are garbage the caller never reads).
    ``live`` (B,): decode-time slot mask — dead slots (continuous
    batching) skip the cache write and position advance.
    ``q_lens`` (B,): the mixed chunked-prefill/decode step (paged caches
    only) — row ``b`` carries ``q_lens[b]`` real tokens of the presented
    width (decode rows 1, prefill rows a chunk, dead rows 0); K/V append
    page-natively via ``append_chunk`` and attention runs the ragged-q
    paged kernel, so prompt chunks never touch a ring scratch.
    """
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    cross = kind == "cross"
    window = {"global": 0, "cross": 0, "local": cfg.local_window,
              "swa": cfg.window}[kind]
    causal = not cross and cfg.causal

    scales = ATT.QuantScales.from_params(params)
    quant_cache = cfg.attention_impl != "float"

    def _q(t, s):
        return quantize_to_int8(t, params[s]) if quant_cache else t

    with jax.named_scope("attn_qkv"):
        q = dense(x, params["wq"].astype(dt))
        if cfg.qkv_bias:
            q = q + params["bq"].astype(dt)
        q = _split_heads(q, h, hd)

        kv_src = mem if cross else x
        if cross and cache is not None and "k8" in cache \
                and mode == "decode":
            k = v = None                           # static cross KV cached
        else:
            k = dense(kv_src, params["wk"].astype(dt))
            v = dense(kv_src, params["wv"].astype(dt))
            if cfg.qkv_bias:
                k = k + params["bk"].astype(dt)
                v = v + params["bv"].astype(dt)
            k, v = _split_heads(k, g, hd), _split_heads(v, g, hd)

        if positions is not None and not cross and cfg.rope_theta > 0:
            q = rope(q, positions, cfg.rope_theta)
            if k is not None:
                k = rope(k, positions, cfg.rope_theta)

        # TP hints: heads over 'model' when divisible, else
        # sequence-parallel attention (Sq over 'model'); KV heads likewise
        # (replicated if small).
        if hints.heads_shardable(h):
            q = hints.constrain(q, "batch", None, "heads", None)
        else:
            q = hints.constrain(q, "batch", "seq", None, None)
        if k is not None:
            k = hints.constrain(k, "batch", None, "kv_heads", None)
            v = hints.constrain(v, "batch", None, "kv_heads", None)
        # the K/V the cache stores (int8 for the quantized impls)
        k8 = v8 = None
        if cache is not None and k is not None:
            k8, v8 = _q(k, "s_k"), _q(v, "s_v")

    def run(qq, kk, vv, *, mode, causal=causal, window=window,
            q_offset=0, kv_len=None, layout="bshd", page_table=None,
            q_lens=None, layer=None):
        q_len = qq.shape[2] if layout == "bhsd_paged" else qq.shape[1]
        spec = make_spec(cfg, mode=mode, causal=causal, window=window,
                         q_len=q_len, has_s_out=scales.s_out is not None,
                         layout=layout, ragged_q=q_lens is not None)
        # cfg.attention_backend is a *preference*: it pins the backend at
        # every call site it can serve (no backend serves all of
        # train/prefill/decode), and capability dispatch covers the rest.
        backend = cfg.attention_backend or None
        if backend is not None \
                and ATT.get_backend(backend).supports(spec) is not True:
            backend = None
        with jax.named_scope("attn_kernel"):
            out = ATT.dispatch(qq, kk, vv, spec=spec, scales=scales,
                               q_offset=q_offset, kv_len=kv_len,
                               page_table=page_table, q_lens=q_lens,
                               layer=layer, backend=backend,
                               q_chunk=cfg.attn_q_chunk,
                               kv_chunk=cfg.attn_kv_chunk,
                               scan_unroll=cfg.scan_unroll)
            return out.astype(dt)

    new_cache = cache
    if cache is None:
        y = run(q, k, v, mode=mode)
    elif cross:
        if mode != "decode":                        # (re)compute at prefill
            cache = dict(cache, k8=k8, v8=v8)
        new_cache = cache
        y = run(q, cache["k8"], cache["v8"], mode=mode)
    elif mode == "prefill":
        # Full in-layer attention; then write the canonical ring-buffer
        # tail (token t lives at slot t % cache_size) so decode can append.
        y = run(q, k, v, mode=mode)
        new_cache = cache.prefill_write(k8, v8, lengths=lengths)
    elif q_lens is not None:                        # mixed chunk append
        # Chunked-prefill serve step: per-row ragged widths, K/V written
        # straight into pool pages (append_chunk), attention through the
        # ragged-q paged kernel — no ring scratch, no host bytes-copy.
        if not isinstance(cache, ATT.PagedKVState):
            raise ValueError(
                "q_lens= (mixed chunked prefill) requires paged KV caches; "
                "ring caches serve uniform decode/prefill only")
        n_new = jnp.asarray(q_lens, jnp.int32)
        new_cache = cache.append_chunk(k8, v8, n_new)
        y = run(jnp.swapaxes(q, 1, 2), new_cache.k, new_cache.v,
                mode=mode, q_offset=new_cache.q_offset(n_new),
                kv_len=new_cache.valid_len(), layout="bhsd_paged",
                page_table=new_cache.page_table, q_lens=n_new,
                layer=new_cache.layer)
        y = jnp.swapaxes(y, 1, 2)
    else:                                           # decode append
        s_new = q.shape[1]
        new_cache = cache.decode_append(k8, v8, live=live)
        if isinstance(new_cache, ATT.PagedKVState):
            # paged pool: q in kernel layout, K/V = the shared arena read
            # through this layer's page table (bhsd_paged capability)
            y = run(jnp.swapaxes(q, 1, 2), new_cache.k, new_cache.v,
                    mode=mode, q_offset=new_cache.q_offset(s_new),
                    kv_len=new_cache.valid_len(), layout="bhsd_paged",
                    page_table=new_cache.page_table, layer=new_cache.layer)
            y = jnp.swapaxes(y, 1, 2)
        else:
            y = run(q, new_cache.k, new_cache.v, mode=mode,
                    q_offset=new_cache.q_offset(s_new),
                    kv_len=new_cache.valid_len())

    with jax.named_scope("attn_out"):
        y = dense(y.reshape(*y.shape[:-2], h * hd), params["wo"].astype(dt))
        y = hints.constrain(y, "batch", "seq", None)
    return y, new_cache
