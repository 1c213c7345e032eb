"""Jitted public wrapper for the int8 matmul kernel (handles batching,
padding to block multiples, and backend selection)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_matmul_blocks, resolve_interpret
from repro.kernels.int8_matmul.kernel import int8_matmul_pallas
from repro.kernels.int8_matmul.ref import int8_matmul_ref


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def int8_matmul(x_q: jax.Array, w_q: jax.Array, bias: jax.Array | None = None,
                mult: jax.Array | float = 1.0, *, block_m: int | None = None,
                block_n: int | None = None, block_k: int | None = None,
                schedule: str = "tpu", use_pallas: bool = True,
                interpret: bool | None = None) -> jax.Array:
    """Quantized linear: int8 x int8 -> int32 -> requant int8.

    ``x_q``: (..., K) int8; ``w_q``: (K, N) int8; ``bias``: (N,) int32 in
    accumulator units; ``mult``: per-channel (N,) or scalar f32 requant
    multiplier. Leading dims are flattened for the kernel. Block sizes
    default to ``kernels.common.BLOCK_DEFAULTS["int8_matmul"]`` — the
    grid the ``bench_kernels.py --sweep`` run records; explicit
    ``block_*=`` arguments override per call. ``interpret=None`` resolves
    through ``kernels.common.resolve_interpret``.
    """
    return _int8_matmul(x_q, w_q, bias, mult, block_m=block_m,
                        block_n=block_n, block_k=block_k, schedule=schedule,
                        use_pallas=use_pallas,
                        interpret=resolve_interpret(interpret))


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "schedule", "use_pallas",
                     "interpret"))
def _int8_matmul(x_q, w_q, bias, mult, *, block_m, block_n, block_k,
                 schedule, use_pallas, interpret):
    dm, dn, dk = default_matmul_blocks()
    block_m = dm if block_m is None else block_m
    block_n = dn if block_n is None else block_n
    block_k = dk if block_k is None else block_k
    *lead, kdim = x_q.shape
    n = w_q.shape[1]
    if bias is None:
        bias = jnp.zeros((n,), jnp.int32)
    mult = jnp.broadcast_to(jnp.asarray(mult, jnp.float32), (n,))

    x2 = x_q.reshape(-1, kdim)
    if not use_pallas:
        out = int8_matmul_ref(x2, w_q, bias, mult)
        return out.reshape(*lead, n)

    m = x2.shape[0]
    bm = min(block_m, max(8, m))
    x2p = _pad_to(x2, bm, 0)
    x2p = _pad_to(x2p, block_k, 1)
    w_p = _pad_to(_pad_to(w_q, block_k, 0), block_n, 1)
    bias_p = _pad_to(bias, block_n, 0)
    mult_p = _pad_to(mult, block_n, 0)
    out = int8_matmul_pallas(x2p, w_p, bias_p, mult_p, block_m=bm,
                             block_n=block_n, block_k=block_k,
                             schedule=schedule, interpret=interpret)
    return out[:m, :n].reshape(*lead, n)
