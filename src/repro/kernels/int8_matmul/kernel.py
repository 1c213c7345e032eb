"""Pallas TPU kernels for ITA's quantized linear layer (the PE array).

Two schedules:

- ``matmul_kernel`` — TPU-native: grid ``(m, n, k)`` with ``k`` innermost and
  an int32 VMEM accumulator; bias-add + requantization fused on the final
  ``k`` step. The paper's *weight reuse* (each weight fetched once per M
  input rows) maps to the ``block_m`` extent: weight-tile HBM traffic is
  ``K*N * ceil(M/block_m)`` bytes, so large ``block_m`` ≙ ITA's M-fold reuse.

- ``matmul_ws_kernel`` — paper-faithful *weight-stationary* schedule: grid
  ``(n, k, m)`` with ``m`` innermost, so each weight tile stays resident in
  VMEM while all input rows stream past it (the W1/W2 double buffer is
  Pallas's automatic pipelining of the streamed x blocks). Partial sums
  stream to/from HBM (aliased in/out), exactly the ``2·N·D`` bits/cycle
  partial-sum term in the paper's bandwidth equation. Used by the dataflow
  benchmark to reproduce the paper's §III bandwidth comparison.

All matmuls are int8 x int8 -> int32 (MXU-native on TPU; v5e runs int8 at
2x bf16 throughput).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import INT8_MAX, INT8_MIN


def _dot_i32(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def matmul_kernel(x_ref, w_ref, bias_ref, mult_ref, o_ref, acc_ref):
    """grid = (m, n, k); k innermost (reduction in VMEM scratch)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot_i32(x_ref[...], w_ref[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _finalize():
        acc = acc_ref[...] + bias_ref[...].astype(jnp.int32)
        y = jnp.round(acc.astype(jnp.float32) * mult_ref[...])
        o_ref[...] = jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)


def matmul_ws_kernel(x_ref, w_ref, bias_ref, mult_ref, psum_ref,
                     psum_out_ref, o_ref, *, final: bool):
    """grid = (n, m); one call per k tile — weight tile stationary in VMEM
    while all input rows stream past it (m is the inner grid axis).

    Partial sums stream HBM->VMEM->HBM between calls (aliased buffers),
    matching ITA's ``2·N·D`` partial-sum bits/cycle bandwidth term.
    """
    acc = psum_ref[...] + _dot_i32(x_ref[...], w_ref[...])
    psum_out_ref[...] = acc
    if final:
        full = acc + bias_ref[...].astype(jnp.int32)
        y = jnp.round(full.astype(jnp.float32) * mult_ref[...])
        o_ref[...] = jnp.clip(y, INT8_MIN, INT8_MAX).astype(jnp.int8)
    else:
        o_ref[...] = jnp.zeros_like(o_ref)


def int8_matmul_pallas(x_q: jax.Array, w_q: jax.Array, bias: jax.Array,
                       mult: jax.Array, *, block_m: int = 256,
                       block_n: int = 128, block_k: int = 128,
                       schedule: str = "tpu", interpret: bool = True):
    """Launch the quantized matmul. Shapes: x (M,K) int8, w (K,N) int8,
    bias (N,) int32 (pre-scaled to accumulator units), mult (N,) f32
    (per-channel requant multipliers; broadcast a scalar for per-tensor).
    Returns int8 (M,N)."""
    m, kdim = x_q.shape
    _, n = w_q.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, kdim)
    assert m % bm == 0 and n % bn == 0 and kdim % bk == 0, (m, n, kdim)
    bias2 = jnp.broadcast_to(bias.astype(jnp.int32), (1, n))
    mult2 = jnp.broadcast_to(mult.astype(jnp.float32), (1, n))

    if schedule == "tpu":
        return pl.pallas_call(
            matmul_kernel,
            grid=(m // bm, n // bn, kdim // bk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            interpret=interpret,
            name="int8_matmul",
        )(x_q, w_q, bias2, mult2)

    assert schedule == "weight_stationary", schedule
    import functools
    psum = jnp.zeros((m, n), jnp.int32)
    out_q = None
    n_k = kdim // bk
    for kt in range(n_k):                       # k outer: weights stationary
        x_sl = jax.lax.slice_in_dim(x_q, kt * bk, (kt + 1) * bk, axis=1)
        w_sl = jax.lax.slice_in_dim(w_q, kt * bk, (kt + 1) * bk, axis=0)
        kern = functools.partial(matmul_ws_kernel, final=kt == n_k - 1)
        psum, out_q = pl.pallas_call(
            kern,
            grid=(n // bn, m // bm),            # m innermost: W tile reused
            in_specs=[
                pl.BlockSpec((bm, bk), lambda j, i: (i, 0)),
                pl.BlockSpec((bk, bn), lambda j, i: (0, j)),  # const in m
                pl.BlockSpec((1, bn), lambda j, i: (0, j)),
                pl.BlockSpec((1, bn), lambda j, i: (0, j)),
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),  # psum stream
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
                pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            ],
            out_shape=[jax.ShapeDtypeStruct((m, n), jnp.int32),
                       jax.ShapeDtypeStruct((m, n), jnp.int8)],
            input_output_aliases={4: 0},
            interpret=interpret,
            name="int8_matmul_ws",
        )(x_sl, w_sl, bias2, mult2, psum)
    return out_q
