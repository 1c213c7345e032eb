"""Standalone ITA streaming softmax Pallas kernel.

Mirrors the silicon module (paper Fig. 4) on a TPU grid: the row dimension
is tiled like ITA's M-row tiles (MAX/Σ buffers hold one entry per row of the
tile), and the column dimension streams in parts. The grid's middle axis is
the *pass*: pass 0 performs DA (+DI on the last part), pass 1 re-streams the
logits and performs EN — exactly the paper's dataflow where the attention
row is seen twice (once from Q·Kᵀ, once as the A·V operand) and never more.

VMEM footprint per grid step: one (block_r, block_c) int8 logits tile +
3 × (block_r, 1) int32 stat buffers (the paper's MAX/Σ buffers + Σ_inv).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import SOFTMAX_SHIFT
from repro.kernels.common import (MASK_K, NEG_SENTINEL, adaptive_inverse,
                                  da_update, paper_inverse)


def softmax_kernel(x_ref, mask_ref, o_ref, m_ref, sigma_ref, inv_ref, er_ref,
                   *, adaptive: bool):
    pass_ax, c = pl.program_id(1), pl.program_id(2)
    last_c = pl.num_programs(2) - 1

    @pl.when((pass_ax == 0) & (c == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_SENTINEL)
        sigma_ref[...] = jnp.zeros_like(sigma_ref)

    @pl.when(pass_ax == 0)
    def _da():
        x = x_ref[...].astype(jnp.int32)
        valid = mask_ref[...] != 0
        da_update(m_ref, sigma_ref, x, valid)
        o_ref[...] = jnp.zeros_like(o_ref)          # overwritten in pass 1

        @pl.when(c == last_c)
        def _di():
            if adaptive:
                inv, e_r = adaptive_inverse(sigma_ref[...])
            else:
                inv, e_r = paper_inverse(sigma_ref[...]), \
                    jnp.full_like(sigma_ref[...], 8)
            inv_ref[...] = inv
            er_ref[...] = e_r

    @pl.when(pass_ax == 1)
    def _en():
        x = x_ref[...].astype(jnp.int32)
        valid = mask_ref[...] != 0
        k = jax.lax.shift_right_logical(m_ref[...] - x, SOFTMAX_SHIFT)
        k = jnp.where(valid, jnp.minimum(k, 31), MASK_K)
        p = jax.lax.shift_right_logical(inv_ref[...], k)
        # Probabilities as f32 * 2^-e_r (paper mode: e_r == 8, p/256).
        o_ref[...] = p.astype(jnp.float32) * jnp.exp2(-er_ref[...].astype(jnp.float32))


def ita_softmax_pallas(x_q: jax.Array, mask: jax.Array, *, block_r: int = 128,
                       block_c: int = 128, adaptive: bool = False,
                       interpret: bool = True) -> jax.Array:
    """x_q (R, C) int8 logits, mask (R, C) int8 (0 = masked). Returns f32
    probabilities (R, C)."""
    r, c = x_q.shape
    br, bc = min(block_r, r), min(block_c, c)
    assert r % br == 0 and c % bc == 0, (r, c, br, bc)
    import functools
    kern = functools.partial(softmax_kernel, adaptive=adaptive)
    return pl.pallas_call(
        kern,
        grid=(r // br, 2, c // bc),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, p, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, p, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, p, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.int32),
                        pltpu.VMEM((br, 1), jnp.int32),
                        pltpu.VMEM((br, 1), jnp.int32),
                        pltpu.VMEM((br, 1), jnp.int32)],
        interpret=interpret,
        name="ita_softmax",
    )(x_q, mask)
