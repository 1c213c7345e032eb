"""Model assembly: blocks → scanned layer groups → full LM / enc-dec.

All parameters are plain dict pytrees. Layer stacks run as ``lax.scan`` over
period-stacked parameters (HLO stays compact for 100-layer × 512-device
lowering); heterogeneous patterns (gemma2 local/global, recurrentgemma
2×RG-LRU+attn, llama-vision 4×self+cross) unroll *inside* the scan body.

Modes: ``train`` (teacher-forced logits), ``prefill`` (logits + caches),
``decode`` (one step with caches). Caches are per-group pytrees stacked on
the period axis, scanned alongside parameters.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.attention import KVCacheState, PagedKVState
from repro.launch import hints
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import rwkv6 as RW
from repro.models.layers import (apply_mlp, apply_norm, embed, init_embedding,
                                 init_mlp, init_norm, sinusoidal_positions,
                                 unembed)

ATTN_KINDS = ("attn", "local", "swa", "enc")


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def init_block(key, cfg, kind: str):
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {}
    if kind in ATTN_KINDS:
        p["norm1"] = init_norm(ks[0], cfg.d_model, cfg.norm_type)
        p["attn"] = A.init_attention(ks[1], cfg)
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm_type)
        p["mlp"] = (MOE.init_moe(ks[3], cfg) if cfg.mlp_type == "moe"
                    else init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp_type))
        if cfg.attn_softcap or cfg.name.startswith("gemma2"):
            p["post_norm1"] = init_norm(ks[4], cfg.d_model, cfg.norm_type)
            p["post_norm2"] = init_norm(ks[5], cfg.d_model, cfg.norm_type)
    elif kind == "cross":
        p["norm1"] = init_norm(ks[0], cfg.d_model, cfg.norm_type)
        p["attn"] = A.init_attention(ks[1], cfg, cross=True)
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm_type)
        p["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff,
                            "swiglu" if cfg.mlp_type == "moe" else cfg.mlp_type)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_mlp"] = jnp.zeros((), jnp.float32)
    elif kind == "attn_cross":
        p["norm1"] = init_norm(ks[0], cfg.d_model, cfg.norm_type)
        p["attn"] = A.init_attention(ks[1], cfg)
        p["norm_x"] = init_norm(ks[2], cfg.d_model, cfg.norm_type)
        p["xattn"] = A.init_attention(ks[3], cfg, cross=True)
        p["norm2"] = init_norm(ks[4], cfg.d_model, cfg.norm_type)
        p["mlp"] = init_mlp(ks[5], cfg.d_model, cfg.d_ff, cfg.mlp_type)
    elif kind == "rglru":
        p["norm1"] = init_norm(ks[0], cfg.d_model, cfg.norm_type)
        p["mixer"] = RG.init_rglru(ks[1], cfg)
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm_type)
        p["mlp"] = init_mlp(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp_type)
    elif kind == "rwkv":
        p["norm1"] = init_norm(ks[0], cfg.d_model, cfg.norm_type)
        p["mixer"] = RW.init_time_mix(ks[1], cfg)
        p["norm2"] = init_norm(ks[2], cfg.d_model, cfg.norm_type)
        p["mlp"] = RW.init_channel_mix(ks[3], cfg)
    else:
        raise ValueError(kind)
    return p


def init_block_cache(cfg, kind: str, batch: int, max_len: int,
                     paged: bool = False, page_size: int = 128,
                     num_pages: int | None = None):
    """Zero cache template for one block (None entries where stateless).

    ``paged=True`` allocates attention KV as ``PagedKVState`` pools (one
    shared arena + page tables per layer) instead of per-sequence rings —
    the continuous-batching layout; ``num_pages`` sizes each layer's
    arena (None = fully provisioned)."""
    g, hd = cfg.n_kv_heads, cfg.head_dim
    quant = cfg.attention_impl != "float"
    kv_dt = jnp.int8 if quant else cfg.compute_dtype()

    def kv_cache(size):
        if paged:
            return PagedKVState.init(batch, size, g, hd, dtype=kv_dt,
                                     page_size=page_size,
                                     num_pages=num_pages)
        return KVCacheState.init(batch, size, g, hd, dtype=kv_dt)

    if kind in ("attn", "enc"):
        return {"mix": kv_cache(max_len)}
    if kind == "local":
        return {"mix": kv_cache(min(max_len, cfg.local_window))}
    if kind == "swa":
        return {"mix": kv_cache(min(max_len, cfg.window))}
    if kind == "cross":
        return {"mix": {
            "k8": jnp.zeros((batch, cfg.n_frontend_tokens, g, hd), kv_dt),
            "v8": jnp.zeros((batch, cfg.n_frontend_tokens, g, hd), kv_dt)}}
    if kind == "attn_cross":
        c = init_block_cache(cfg, "attn", batch, max_len, paged=paged,
                             page_size=page_size, num_pages=num_pages)
        c["cross"] = init_block_cache(cfg, "cross", batch, max_len)["mix"]
        return c
    if kind == "rglru":
        return {"mix": RG.init_rglru_state(batch, cfg, cfg.compute_dtype())}
    if kind == "rwkv":
        st = RW.init_rwkv_state(batch, cfg)
        return {"mix": st["tm"], "mlp": st["cm"]}
    raise ValueError(kind)


def apply_block(p, x, kind, cfg, *, positions, mem, cache, mode,
                lengths=None, live=None, q_lens=None):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    cm = None if cache is None else cache.get("mix")

    def residual(y, post_key):
        if post_key in p:
            return x + apply_norm(p[post_key], y, cfg.norm_type)
        return x + y

    if kind in ATTN_KINDS or kind == "cross":
        akind = {"attn": "global", "enc": "global", "local": "local",
                 "swa": "swa", "cross": "cross"}[kind]
        with jax.named_scope("attn_qkv"):
            h = apply_norm(p["norm1"], x, cfg.norm_type)
        y, new_mix = A.apply_attention(p["attn"], h, cfg=cfg, kind=akind,
                                       positions=positions, mem=mem,
                                       cache=cm, mode=mode, lengths=lengths,
                                       live=live, q_lens=q_lens)
        with jax.named_scope("attn_out"):
            if kind == "cross":
                y = y * jnp.tanh(p["gate_attn"]).astype(y.dtype)
            x = residual(y, "post_norm1")
        with jax.named_scope("mlp"):
            h = apply_norm(p["norm2"], x, cfg.norm_type)
            if cfg.mlp_type == "moe" and kind != "cross":
                y = MOE.apply_moe(p["mlp"], h, cfg)
                aux = MOE.moe_aux_loss(p["mlp"], h, cfg) \
                    if mode == "train" else aux
            else:
                y = apply_mlp(p["mlp"], h,
                              "swiglu" if cfg.mlp_type in ("moe", "rwkv")
                              else cfg.mlp_type)
            if kind == "cross":
                y = y * jnp.tanh(p["gate_mlp"]).astype(y.dtype)
            x = residual(y, "post_norm2")
        return x, (None if cache is None else dict(cache, mix=new_mix)), aux

    if kind == "attn_cross":                       # whisper decoder layer
        h = apply_norm(p["norm1"], x, cfg.norm_type)
        y, new_self = A.apply_attention(p["attn"], h, cfg=cfg, kind="global",
                                        positions=positions, cache=cm,
                                        mode=mode, lengths=lengths,
                                        live=live, q_lens=q_lens)
        x = x + y
        h = apply_norm(p["norm_x"], x, cfg.norm_type)
        y, new_cross = A.apply_attention(
            p["xattn"], h, cfg=cfg, kind="cross", positions=None, mem=mem,
            cache=None if cache is None else cache.get("cross"), mode=mode)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm_type)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_type)
        nc = None if cache is None else dict(cache, mix=new_self,
                                             cross=new_cross)
        return x, nc, aux

    if kind == "rglru":
        h = apply_norm(p["norm1"], x, cfg.norm_type)
        y, new_mix = RG.apply_rglru(p["mixer"], h, cfg,
                                    None if mode == "train" else cm)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm_type)
        x = x + apply_mlp(p["mlp"], h, cfg.mlp_type)
        return x, (None if cache is None else dict(cache, mix=new_mix)), aux

    if kind == "rwkv":
        h = apply_norm(p["norm1"], x, cfg.norm_type)
        y, new_tm = RW.apply_time_mix(p["mixer"], h, cfg,
                                      None if mode == "train" else cm)
        x = x + y
        h = apply_norm(p["norm2"], x, cfg.norm_type)
        y, new_cm = RW.apply_channel_mix(
            p["mlp"], h, cfg,
            None if mode == "train" or cache is None else cache.get("mlp"))
        x = x + y
        nc = None if cache is None else dict(cache, mix=new_tm, mlp=new_cm)
        return x, nc, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Scanned layer groups
# ---------------------------------------------------------------------------

def init_group(key, cfg, pattern, n_periods):
    """Stacked params: tuple over pattern positions, each (n_periods, ...)."""
    def one_period(k):
        ks = jax.random.split(k, len(pattern))
        return tuple(init_block(ks[i], cfg, kind)
                     for i, kind in enumerate(pattern))
    keys = jax.random.split(key, n_periods)
    per = [one_period(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def init_group_cache(cfg, pattern, n_periods, batch, max_len, paged=False,
                     page_size=128, num_pages=None):
    # broadcast (not zero) the per-block template over the period axis:
    # ring leaves are all-zero either way, but the paged pool's free
    # stack / free_top initialization must survive the stacking
    tmpl = tuple(init_block_cache(cfg, kind, batch, max_len, paged=paged,
                                  page_size=page_size, num_pages=num_pages)
                 for kind in pattern)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape), tmpl)


def apply_group(params, x, cfg, pattern, *, positions, mem, caches, mode,
                lengths=None, live=None, q_lens=None):
    """Scan the group over its periods. Returns (x, new_caches, aux_sum).

    Caches ride the scan *carry*, each period reading its slice and
    writing it back in place (``dynamic_update_index_in_dim``): as scan
    ``xs -> ys`` they would be a second full copy of every layer's KV
    (``ys`` cannot alias ``xs``), which a paged pool at full width cannot
    afford on one chip.

    A paged pool (``PagedKVState``) is not sliced: only its bookkeeping
    (page tables, positions, free stack, refcounts; a few KB) takes the
    slice and write-back, while its int8 K/V stay whole, stacked over the
    periods, and travel with the period index (``at_layer`` /
    ``put_layer``). The pool write (``ita_kv_write``) and the paged
    attention kernels address the stack at that index, so the only ops
    that touch a layer's pool move the pages a step reads or writes; a
    slice and write-back of the pool itself would copy it whole every
    layer of every step. Ring caches and recurrent states keep the
    slice."""

    def blocks(xc, aux, pparams, pcache):
        xc = hints.constrain(xc, "batch", "seq", None)   # seq-parallel
        new_caches = []
        for i, kind in enumerate(pattern):
            blk_cache = None if pcache is None else pcache[i]
            xc, nc, a = apply_block(pparams[i], xc, kind, cfg,
                                    positions=positions, mem=mem,
                                    cache=blk_cache, mode=mode,
                                    lengths=lengths, live=live,
                                    q_lens=q_lens)
            new_caches.append(nc)
            aux = aux + a
        return xc, aux, tuple(new_caches)

    # scan_unroll: full unroll (scan semantics preserved) — used by the
    # dry-run so XLA cost analysis sees every layer (HloCostAnalysis does
    # not scale while-loop bodies by trip count) and by real TPU runs for
    # cross-layer collective pipelining.
    n_periods = jax.tree.leaves(params)[0].shape[0]
    unroll = n_periods if getattr(cfg, "scan_unroll", False) else 1
    aux0 = jnp.zeros((), jnp.float32)

    if caches is None:
        def body(carry, pparams):
            xc, aux, _ = blocks(*carry, pparams, None)
            return (xc, aux), None

        if cfg.remat and mode == "train":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else None)
            body = jax.checkpoint(body, prevent_cse=False, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, aux0), params, unroll=unroll)
        return x, None, aux

    def is_pool(c):
        return isinstance(c, PagedKVState)

    def body_cached(carry, xs):
        xc, aux, all_caches = carry
        pparams, i = xs
        with jax.named_scope("layer_carry"):
            pcache = jax.tree.map(
                lambda c: c.at_layer(i) if is_pool(c) else c[i],
                all_caches, is_leaf=is_pool)
        xc, aux, new = blocks(xc, aux, pparams, pcache)
        with jax.named_scope("layer_carry"):
            all_caches = jax.tree.map(
                lambda c, n: c.put_layer(n, i) if is_pool(c)
                else jax.lax.dynamic_update_index_in_dim(c, n, i, 0),
                all_caches, new, is_leaf=is_pool)
        return (xc, aux, all_caches), None

    (x, aux, new_caches), _ = jax.lax.scan(
        body_cached, (x, aux0, caches),
        (params, jnp.arange(n_periods, dtype=jnp.int32)), unroll=unroll)
    return x, new_caches, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def init_model(key, cfg):
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model,
                                cfg.tie_embeddings),
        "groups": tuple(init_group(jax.random.fold_in(ks[1], i), cfg, pat, n)
                        for i, (pat, n) in enumerate(cfg.layer_groups)),
        "final_norm": init_norm(ks[2], cfg.d_model, cfg.norm_type),
    }
    if cfg.n_encoder_layers:
        enc_cfg = cfg
        p["encoder"] = {
            "groups": (init_group(ks[3], enc_cfg, ("enc",),
                                  cfg.n_encoder_layers),),
            "final_norm": init_norm(ks[4], cfg.d_model, cfg.norm_type),
        }
    if cfg.frontend_dim and cfg.frontend_dim != cfg.d_model:
        p["frontend_proj"] = jax.random.normal(
            ks[5], (cfg.frontend_dim, cfg.d_model), jnp.float32) \
            * cfg.frontend_dim ** -0.5
    if cfg.param_dtype == "bfloat16":
        p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    return p


def init_serving_params(key, cfg):
    """``init_model``'s weights for inference, created directly in the
    config's compute dtype under one ``jit``: the float32 tree is never
    materialised (phi3-mini-3.8b: 7.6 GB of bf16 on a 16 GB chip, where
    the eager f32 tree alone would take 15.3 GB). Same values as casting
    ``init_model(key, cfg)`` — the PRNG draws are identical under jit."""
    dt = cfg.compute_dtype()
    return jax.jit(lambda k: jax.tree.map(lambda a: a.astype(dt),
                                          init_model(k, cfg)))(key)


def _encode(params, cfg, frontend, mode):
    """Whisper encoder (frontend stub embeddings -> memory) or VLM
    projection of patch embeddings."""
    dt = cfg.compute_dtype()
    if frontend is None:
        return None
    mem = frontend.astype(dt)
    if "frontend_proj" in params:
        mem = mem @ params["frontend_proj"].astype(dt)
    if cfg.n_encoder_layers:
        import dataclasses
        if cfg.sinusoidal_pos:
            pos = sinusoidal_positions(mem.shape[1], cfg.d_model)
            mem = mem + jnp.asarray(pos, dt)
        enc_cfg = dataclasses.replace(cfg, causal=False)  # bidirectional
        x = mem
        for pat_params in params["encoder"]["groups"]:
            x, _, _ = apply_group(pat_params, x, enc_cfg, ("enc",),
                                  positions=jnp.arange(x.shape[1]),
                                  mem=None, caches=None, mode="train")
        mem = apply_norm(params["encoder"]["final_norm"], x, cfg.norm_type)
    return mem


def forward(params, tokens, cfg, *, mode="train", frontend=None, caches=None,
            pos0=None, lengths=None, live=None, q_lens=None,
            skip_unembed=False):
    """tokens (B, S) int32. Returns (logits, new_caches, aux).

    ``pos0``: first token's position — a scalar (lockstep decode) or a
    (B,) per-sequence vector (ragged batch decode). ``lengths`` (B,)
    marks a ragged *prefill* of right-padded prompts: the KV caches
    record per-sequence stream lengths so decode continues each row at
    its own position (pad columns are causally invisible to valid rows).
    ``live`` (B,) bool marks which batch slots are real sequences during
    decode (continuous batching): dead slots neither write their caches
    nor advance positions, so released pages are never touched.
    ``q_lens`` (B,) int32 marks a *mixed* decode step over paged caches
    (chunked prefill): row ``b`` holds ``q_lens[b]`` real tokens of the
    presented width — prompt chunks write pool pages directly and attend
    through the ragged-q kernel alongside 1-token decode rows.
    """
    dt = cfg.compute_dtype()
    with jax.named_scope("embed"):
        x = embed(params["embed"], tokens, dt)
        if cfg.embed_scale:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), dt)
        s = tokens.shape[1]
        if pos0 is None:
            positions = jnp.arange(s, dtype=jnp.int32)
        else:
            pos0 = jnp.asarray(pos0, jnp.int32)
            # (s,) lockstep, or (B, s) per-sequence (ragged decode)
            positions = pos0[..., None] + jnp.arange(s, dtype=jnp.int32) \
                if pos0.ndim else pos0 + jnp.arange(s, dtype=jnp.int32)
        if cfg.sinusoidal_pos:
            # computed from (possibly dynamic, possibly batched) positions
            # so decode works
            d = cfg.d_model
            dim = jnp.arange(0, d, 2, dtype=jnp.float32) / d
            ang = positions[..., None].astype(jnp.float32) / (10000.0 ** dim)
            pe = jnp.zeros(ang.shape[:-1] + (d,), jnp.float32) \
                .at[..., 0::2].set(jnp.sin(ang)) \
                .at[..., 1::2].set(jnp.cos(ang))
            x = x + (pe if pe.ndim == 3 else pe[None]).astype(dt)

    mem = _encode(params, cfg, frontend, mode)

    aux_total = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    for gi, (pattern, n) in enumerate(cfg.layer_groups):
        g_cache = None if caches is None else caches[gi]
        x, nc, aux = apply_group(params["groups"][gi], x, cfg, pattern,
                                 positions=positions, mem=mem,
                                 caches=g_cache, mode=mode, lengths=lengths,
                                 live=live, q_lens=q_lens)
        aux_total = aux_total + aux
        if new_caches is not None:
            new_caches.append(nc)
    with jax.named_scope("head"):
        x = apply_norm(params["final_norm"], x, cfg.norm_type)
        x = hints.constrain(x, "batch", None, None)
        if skip_unembed:
            return x, (tuple(new_caches) if new_caches is not None
                       else None), aux_total
        logits = unembed(params["embed"], x, cfg.logit_softcap)
        logits = hints.constrain(logits, "batch", None, "vocab")
    return logits, (tuple(new_caches) if new_caches is not None else None), \
        aux_total


def init_caches(cfg, batch: int, max_len: int, *, paged: bool = False,
                page_size: int = 128, num_pages: int | None = None):
    """Per-group cache pytrees. ``paged=True`` swaps the per-sequence KV
    rings for shared paged pools (continuous-batching layout; one arena
    per layer, sized by ``num_pages`` — None fully provisions)."""
    return tuple(init_group_cache(cfg, pat, n, batch, max_len, paged=paged,
                                  page_size=page_size, num_pages=num_pages)
                 for pat, n in cfg.layer_groups)


def _ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    vidx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    gold = jnp.sum(jnp.where(vidx == targets[..., None], logits, 0.0),
                   axis=-1)
    return (logz - gold).sum()


def loss_fn(params, batch, cfg, aux_weight: float = 0.01):
    """Causal-LM cross entropy (tokens shifted inside); MoE aux added.

    The gold-logit pick uses an iota-compare-reduce (not take_along_axis)
    so it fuses under GSPMD with a model-axis-sharded vocab — a gather
    across the sharded vocab would all-gather the full logits per device
    (hundreds of GiB at 256k vocab).

    ``cfg.ce_chunks > 1`` evaluates the unembed+CE in sequence chunks
    (lax.scan) so the (B,S,V) f32 logits never fully materialize — the
    §Perf lever for 256k-vocab temp-memory (gemma2 at train_4k).
    """
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    if cfg.ce_chunks <= 1:
        logits, _, aux = forward(params, tokens[:, :-1], cfg, mode="train",
                                 frontend=batch.get("frontend"))
        nll = _ce(logits, targets) / targets.size
        return nll + aux_weight * aux, {"nll": nll, "aux": aux}

    x, _, aux = forward(params, tokens[:, :-1], cfg, mode="train",
                        frontend=batch.get("frontend"), skip_unembed=True)
    b, s, d = x.shape
    nc = cfg.ce_chunks
    while s % nc:
        nc -= 1
    xc = jnp.moveaxis(x.reshape(b, nc, s // nc, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, nc, s // nc), 1, 0)

    def body(tot, inp):
        xcc, tcc = inp
        logits = unembed(params["embed"], xcc, cfg.logit_softcap)
        logits = hints.constrain(logits, "batch", None, "vocab")
        return tot + _ce(logits, tcc), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, tc))
    nll = tot / targets.size
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}
