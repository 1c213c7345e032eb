"""Jitted wrapper for the standalone ITA softmax kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.ita_softmax.kernel import ita_softmax_pallas


def ita_softmax(x_q: jax.Array, mask: jax.Array | None = None, *,
                block_r: int = 128, block_c: int = 128,
                adaptive: bool = False,
                interpret: bool | None = None) -> jax.Array:
    """Streaming integer softmax over the last axis of int8 logits.

    Accepts any leading shape; pads rows/cols to block multiples (padded
    columns are masked out and return probability 0). ``interpret=None``
    resolves through ``kernels.common.resolve_interpret``.
    """
    return _ita_softmax(x_q, mask, block_r=block_r, block_c=block_c,
                        adaptive=adaptive,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "adaptive",
                                             "interpret"))
def _ita_softmax(x_q, mask, *, block_r, block_c, adaptive, interpret):
    *lead, n = x_q.shape
    x2 = x_q.reshape(-1, n)
    r = x2.shape[0]
    if mask is None:
        m2 = jnp.ones((r, n), jnp.int8)
    else:
        m2 = mask.reshape(-1, n).astype(jnp.int8)
    br = min(block_r, max(8, r))
    pad_r = (-r) % br
    pad_c = (-n) % block_c
    if pad_r or pad_c:
        x2 = jnp.pad(x2, ((0, pad_r), (0, pad_c)))
        m2 = jnp.pad(m2, ((0, pad_r), (0, pad_c)))
    out = ita_softmax_pallas(x2, m2, block_r=br, block_c=min(block_c, n + pad_c),
                             adaptive=adaptive, interpret=interpret)
    return out[:r, :n].reshape(*lead, n)
