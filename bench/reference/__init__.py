"""Plain reference of the served decoder: float32 ``jax.numpy`` with the
highest matmul precision, no kernels, no cache, no batching. One module
per block kind (``reference/<kind>.py``, each with ``block``); this one
assembles them: embed, the blocks in the configuration's pattern, final
RMSNorm, LM head. It imports nothing of the program.

``prec`` selects the arithmetic: ``"f32"`` is the reference; ``"low"``
is the control, the same mathematics one step below what the
configuration states: fp8 (e4m3) operands for every bf16 matmul, and
for the int8 integer-softmax attention its 4-bit counterpart (int4 q, k,
v and output on the configuration's clips, logits on the 4-bit softmax
grid, powers-of-two numerators; ``attn.int_attention``).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b, prec: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "low":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def int4(x, clip: float, prec: str):
    """The control's int4 attention operand (identity for the reference)."""
    if prec != "low":
        return x
    step = clip / 7.0
    return jnp.clip(jnp.round(x / step), -8, 7) * step


def rmsnorm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("kind", "conf", "prec"))
def _layer(x, weights, index, *, kind, conf, prec):
    mod = importlib.import_module(f"reference.{kind}")
    w = {k: jax.lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
         for k, v in weights.items()}
    return mod.block(w, x, dict(conf), prec)


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _head(x, rows, unembed, *, eps, prec):
    h = rmsnorm(jnp.take(x, rows, axis=0), eps)
    return matmul(h, unembed, prec)


def logits(weights, tokens, rows, conf: dict, clip: dict,
           prec: str = "f32"):
    """Logits (len(rows), vocab) at positions ``rows`` of the token
    sequence ``tokens`` (S,), computed layer by layer.

    ``weights``: ``embed`` (V, d), ``unembed`` (d, V) and ``layers``, a
    dict of arrays stacked over layers, as ``block`` of each kind reads
    them. ``conf`` is the configuration file's dict; ``clip`` holds the
    attention operands' clips (``q``, ``k``, ``v``, ``out``), which the
    control's 4-bit attention quantises on."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "rms_norm_eps", "attention_bias")
    frozen = tuple((k, conf[k]) for k in keys) + (
        ("clip", tuple(sorted(clip.items()))),)
    pattern = conf["block_pattern"]
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(int(conf["num_hidden_layers"])):
        x = _layer(x, weights["layers"], i // len(pattern),
                   kind=pattern[i % len(pattern)], conf=frozen, prec=prec)
    return _head(x, rows, weights["unembed"], eps=conf["rms_norm_eps"],
                 prec=prec)
