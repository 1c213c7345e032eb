"""Reference ``attn`` block: pre-RMSNorm causal self-attention (GQA,
optional q/k/v bias, half-split RoPE, softmax scaled by head_dim**-0.5)
and a pre-RMSNorm SwiGLU MLP, each added to the residual. The layer
equations of Phi-3 (arXiv:2404.14219) and Qwen2 (arXiv:2407.10671);
norm gains are the identity (the benchmark's weights set them so)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import int4, matmul, rmsnorm

Q_BLOCK = 256
ROW_BLOCK = 2048


def rope(x, positions, theta: float):
    """x (S, H, D): rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def float_softmax(scores, mask):
    return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)


def int_softmax(scores, mask, bits: int = 4):
    """The integer softmax of the ITA dataflow at ``bits`` bits: logits
    on the grid ``bits / (2**bits * log2 e)``, clipped to the signed
    range; each key's numerator ``2**(bits-1) >> k`` with ``k`` the
    row-max distance shifted by ``bits - log2(bits)``; normalised at the
    end."""
    eps = bits / (2.0 ** bits * math.log2(math.e))
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    x = jnp.clip(jnp.round(scores / eps), lo, hi)
    x = jnp.where(mask, x, lo - 1)
    shift = bits - int(math.log2(bits))
    k = jnp.floor((jnp.max(x, -1, keepdims=True) - x) / 2 ** shift)
    u = jnp.where(mask, 2.0 ** (bits - 1) * 2.0 ** -k, 0.0)
    u = jnp.floor(u)
    return u / jnp.sum(u, -1, keepdims=True)


def attention(q, k, v, softmax=float_softmax):
    """Causal attention, computed one block of queries at a time so that
    the score matrix fits. q (S, H, D); k, v (S, G, D)."""
    s, h, d = q.shape
    g = k.shape[1]
    pad = (-s) % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(-1, Q_BLOCK, g, h // g, d)
    keys = jnp.arange(s)

    def one(args):
        i, blk = args
        scores = jnp.einsum("qgrd,kgd->grqk", blk, k,
                            precision=jax.lax.Precision.HIGHEST) * d ** -0.5
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        p = softmax(scores, keys[None, :] <= rows[:, None])
        return jnp.einsum("grqk,kgd->qgrd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(one, (jnp.arange(qb.shape[0]), qb))
    return out.reshape(-1, h, d)[:s]


def block(w, x, conf, prec):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    g, hd = conf["num_key_value_heads"], conf["head_dim"]
    clip = dict(conf["clip"])
    s = x.shape[0]
    pos = jnp.arange(s)
    y = rmsnorm(x, conf["rms_norm_eps"])
    q, k, v = (matmul(y, w[n], prec) for n in ("wq", "wk", "wv"))
    if conf["attention_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(s, h, hd), pos, conf["rope_theta"])
    k = rope(k.reshape(s, g, hd), pos, conf["rope_theta"])
    v = v.reshape(s, g, hd)
    o = attention(int4(q, clip["q"], prec), int4(k, clip["k"], prec),
                  int4(v, clip["v"], prec),
                  int_softmax if prec == "low" else float_softmax)
    o = int4(o, clip["out"], prec).reshape(s, h * hd)
    x = x + matmul(o, w["wo"], prec)
    return x + by_rows(lambda r: mlp(w, rmsnorm(r, conf["rms_norm_eps"]),
                                     prec), x)


def mlp(w, y, prec):
    act = jax.nn.silu(matmul(y, w["w_gate"], prec)) \
        * matmul(y, w["w_up"], prec)
    return matmul(act, w["w_down"], prec)


def by_rows(fn, x):
    """``fn`` over blocks of rows, so that wide intermediates fit."""
    s = x.shape[0]
    pad = (-s) % ROW_BLOCK
    blocks = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK,
                                                     x.shape[1])
    return jax.lax.map(fn, blocks).reshape(-1, x.shape[1])[:s]
