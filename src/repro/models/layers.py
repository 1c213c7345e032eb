"""Shared neural-net layers (pure JAX, no flax): norms, RoPE, MLPs,
embeddings. Parameters are plain dict pytrees created by ``init_*``
functions driven by a threaded PRNG key."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _normal(key, shape, scale, dtype=jnp.float32):
    return scale * jax.random.normal(key, shape, dtype)


def dense(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` whose every row comes out the same however many rows
    share the call. XLA's TPU backend lowers a one-row matmul to a vector
    multiply-reduce instead of the MXU, which sums in another order, so a
    lone row is padded to one sublane tile (8 rows). Without this, greedy
    serving (a batch of slots) drifts from solo ``generate()`` (one row
    per decode step) in bf16."""
    rows = math.prod(x.shape[:-1])
    if rows != 1:
        return x @ w
    y = jnp.pad(x.reshape(1, -1), ((0, 7), (0, 0))) @ w
    return y[:1].reshape(*x.shape[:-1], w.shape[-1])


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def init_norm(key, d, norm_type="rmsnorm"):
    if norm_type == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}       # gemma-style 1+s
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def _row_mean(x: jax.Array) -> jax.Array:
    """Mean over the last axis, summed in one fixed order: halve the row
    while its width is even, then add the odd remainder left to right.
    XLA's TPU backend picks a reduction's tiling, and so its summation
    order, from the row count, so ``jnp.mean`` of the same row can round
    differently in a 1-row decode step, a 32-token chunk and a whole
    prompt. Elementwise adds are never reassociated, so this order holds
    in every program — what keeps served tokens bit-identical to solo
    ``generate()`` in bf16."""
    n = x.shape[-1]
    d = n
    while d % 2 == 0 and d > 1:
        d //= 2
        x = x[..., :d] + x[..., d:]
    total = x[..., 0]
    for i in range(1, d):
        total = total + x[..., i]
    return total[..., None] / n


def apply_norm(p, x, norm_type="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        var = _row_mean(xf * xf)
        y = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    else:
        mu = _row_mean(xf)
        var = _row_mean((xf - mu) ** 2)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int) -> np.ndarray:
    """Whisper-style sinusoidal embeddings (frontend stub positions)."""
    pos = np.arange(seq)[:, None]
    dim = np.arange(0, d, 2)[None, :] / d
    ang = pos / (10000.0 ** dim)
    out = np.zeros((seq, d), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d, f, mlp_type="swiglu"):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = d ** -0.5, f ** -0.5
    if mlp_type in ("swiglu", "geglu"):
        return {"w_gate": _normal(k1, (d, f), s_in),
                "w_up": _normal(k2, (d, f), s_in),
                "w_down": _normal(k3, (f, d), s_out)}
    return {"w_up": _normal(k1, (d, f), s_in),
            "b_up": jnp.zeros((f,), jnp.float32),
            "w_down": _normal(k2, (f, d), s_out),
            "b_down": jnp.zeros((d,), jnp.float32)}


def apply_mlp(p, x, mlp_type="swiglu"):
    dt = x.dtype
    if mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if mlp_type == "swiglu" else \
            lambda v: jax.nn.gelu(v, approximate=True)
        h = act(dense(x, p["w_gate"].astype(dt))) \
            * dense(x, p["w_up"].astype(dt))
        return dense(h, p["w_down"].astype(dt))
    h = jax.nn.gelu(dense(x, p["w_up"].astype(dt)) + p["b_up"].astype(dt),
                    approximate=True)
    return dense(h, p["w_down"].astype(dt)) + p["b_down"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab, d, tie=True):
    p = {"table": _normal(key, (vocab, d), d ** -0.5)}
    if not tie:
        p["unembed"] = _normal(jax.random.fold_in(key, 1), (d, vocab),
                               d ** -0.5)
    return p


def embed(p, ids, dtype):
    return p["table"].astype(dtype)[ids]


def unembed(p, x, softcap=0.0):
    if "unembed" in p:
        logits = dense(x, p["unembed"].astype(x.dtype))
    else:
        logits = dense(x, p["table"].T.astype(x.dtype))
    logits = logits.astype(jnp.float32)
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


def softcap(x, cap):
    return jnp.tanh(x / cap) * cap if cap > 0 else x
