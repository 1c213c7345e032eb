"""Seeded weights, made by the benchmark on the device in one jitted call.

The program's parameter tree gives the layout (names, shapes, stacking
over layers); every value comes from here, keyed by the leaf's path, so
the program makes none of the numbers the reference reads. The scales
are chosen so that attention matters to the output (queries and keys of
unit variance) and so that the int8 quantisation steps fit the values
the weights produce; ``INIT`` holds them.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

_QUANT = {"s_q": "q", "s_k": "k", "s_v": "v", "s_out": "out"}

# Standard deviations of the embedding, of the query and key projections
# (in units of 1/sqrt(d)) and of the QKV biases; the int8 clips of the
# attention operands, whose steps are the program's quantisation scales.
INIT = {"embed_std": 1.0, "qk_std": 1.0, "bias_std": 0.1,
        "clip": {"q": 4.0, "k": 4.0, "v": 4.0, "out": 0.5}}


def _leaf(key, path: str, shape, dtype, conf: dict):
    name = path.rsplit("/", 1)[-1]
    d = conf["hidden_size"]
    hd, h = conf["head_dim"], conf["num_attention_heads"]
    std = {
        "table": INIT["embed_std"],
        "unembed": d ** -0.5,
        "wq": INIT["qk_std"] * d ** -0.5,
        "wk": INIT["qk_std"] * d ** -0.5,
        "wv": d ** -0.5,
        "wo": (h * hd) ** -0.5,
        "bq": INIT["bias_std"], "bk": INIT["bias_std"], "bv": INIT["bias_std"],
        "w_gate": d ** -0.5, "w_up": d ** -0.5,
        "w_down": conf["intermediate_size"] ** -0.5,
    }
    if name in std:
        return (std[name] * jax.random.normal(key, shape, jnp.float32)) \
            .astype(dtype)
    if name == "scale":                     # norm gains: the identity
        return jnp.zeros(shape, dtype)
    if name in _QUANT:                      # int8 step: clip / 127
        return jnp.full(shape, INIT["clip"][_QUANT[name]] / 127.0, dtype)
    raise ValueError(f"no rule for weight {path!r}; the program's parameter "
                     f"tree has a leaf the benchmark does not know")


def _path(kp) -> str:
    parts = []
    for k in kp:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def base_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def make(layout, conf: dict, seed: int):
    """Weights shaped like ``layout`` (a pytree of ShapeDtypeStruct),
    drawn from ``seed``, on the default device, in one jitted call."""

    def build(key):
        def one(kp, s):
            path = _path(kp)
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            return _leaf(k, path, s.shape, s.dtype, conf)
        return jax.tree_util.tree_map_with_path(one, layout)

    return jax.jit(build)(base_key(seed))
