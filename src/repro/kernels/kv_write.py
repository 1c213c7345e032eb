"""In-place writes into the int8 paged KV pool (``ita_kv_write``).

The pool is the paged attention kernels' own row-major layout, stacked
over layers: ``(L, P, G, page, hd)`` int8, each kv head's page one
contiguous ``(page, hd)`` block. A serve step writes a few token rows
per layer; these kernels land them in place (the pools are aliased to
the outputs and only the blocks a grid step names move), so no op
outside the attention kernels reads or writes a whole layer's pool.

One ``ita_kv_write`` call writes one layer of K and V, in two passes:

1. **Copy-on-write pages** (``ita_kv_cow``): entry ``c`` copies page
   ``cow_src[c]`` to ``cow_dst[c]``, one whole page per grid step.
2. **Token rows** (``ita_kv_write``): job ``j`` rewrites one
   ``(G, tile, hd)`` tile, ``pool[layer, pages[j], :, tiles[j] * tile +
   r, :]``, taking row ``r`` from ``new[j]`` where bit ``r`` of
   ``bits[j]`` is set and keeping the old row elsewhere. An int8 HBM
   tile is 32 rows deep (four rows share a 32-bit word), so a token row
   cannot be DMA'd alone: the tile is read, merged and written back.

An entry or a job with nothing to do names the parking page (0) as both
source and destination: its bytes are zero and stay zero, so the
round trip is a no-op, and consecutive no-ops fetch it once. Callers
guarantee that no two live jobs write one tile and that no job reads a
tile another writes (one job per written tile of a row, and rows own
the pages they write after copy-on-write), which is what lets the
pipeline read job ``j + 1``'s tile while job ``j``'s is written back.
The copies finish before the token pass starts, so a token written into
a page copied in the same call lands on the copy.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

TILE = 32            # int8 rows per (32, 128) HBM tile


def write_tile(page_size: int) -> int:
    """Rows per read-modify-write tile: the int8 HBM tile, or a divisor
    of it where a page is shallower than one."""
    return math.gcd(page_size, TILE)


def _cow_kernel(layer_ref, src_ref, dst_ref, k_in, v_in, k_out, v_out):
    del layer_ref, src_ref, dst_ref          # read by the index maps
    k_out[...] = k_in[...]
    v_out[...] = v_in[...]


def _write_kernel(layer_ref, page_ref, tile_ref, bits_ref, new_k, new_v,
                  k_in, v_in, k_out, v_out, *, tile):
    del layer_ref, page_ref, tile_ref        # read by the index maps
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    bits = jnp.full((tile, 1), bits_ref[pl.program_id(0)], jnp.int32)
    take = (jax.lax.shift_right_logical(bits, rows) & 1) != 0
    for new, old, out in ((new_k, k_in, k_out), (new_v, v_in, v_out)):
        out[0, 0] = jnp.where(take[None], new[0].astype(jnp.int32),
                              old[0, 0].astype(jnp.int32)).astype(jnp.int8)


def _aliased_call(kern, name, grid, n_prefetch, block, in_map, out_map,
                  extra_specs, interpret):
    """pallas_call over the two pools, aliased to its two outputs; the
    pools follow the scalar-prefetch operands and ``extra_specs``."""
    pool_in = pl.BlockSpec(block, in_map)
    pool_out = pl.BlockSpec(block, out_map)
    first_pool = n_prefetch + len(extra_specs)

    def call(*operands):
        k_pool, v_pool = operands[first_pool:]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch, grid=grid,
            in_specs=[*extra_specs, pool_in, pool_in],
            out_specs=[pool_out, pool_out])
        return pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                       jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
            input_output_aliases={first_pool: 0, first_pool + 1: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=name,
        )(*operands)
    return call


def ita_kv_write(k_pool, v_pool, layer, cow_src, cow_dst, pages, tiles,
                 bits, new_k, new_v, *, interpret: bool | None = None):
    """Write one layer's copy-on-write pages, then its token tiles, in
    place.

    ``k_pool``/``v_pool`` ``(L, P, G, page, hd)`` int8; ``layer`` ()
    int32; ``cow_src``/``cow_dst`` ``(n_cow,)`` int32 page ids
    (``0``/``0``: no copy; ``None``: no copy-on-write pass);
    ``pages``/``tiles`` ``(n_jobs,)`` int32, the page (``0``: no job)
    and the tile within it of each job; ``bits``
    ``(n_jobs,)`` int32, bit ``r`` set where the job writes row ``r``;
    ``new_k``/``new_v`` ``(n_jobs, G, tile, hd)`` int8, the rows laid
    out as the tile. Returns the updated ``(k_pool, v_pool)`` — the same
    buffers where the caller donates them."""
    return _kv_write(k_pool, v_pool, layer, cow_src, cow_dst, pages, tiles,
                     bits, new_k, new_v,
                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_write(k_pool, v_pool, layer, cow_src, cow_dst, pages, tiles, bits,
              new_k, new_v, *, interpret):
    _, _, g, page, hd = k_pool.shape
    n_jobs, _, tile, _ = new_k.shape
    assert page % tile == 0 and tile <= 32, (page, tile)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    layer = i32(layer).reshape(1)

    if cow_src is not None:
        copy = _aliased_call(
            _cow_kernel, "ita_kv_cow", (cow_src.shape[0],), 3,
            (1, 1, g, page, hd),
            lambda c, lr, sr, dr: (lr[0], sr[c], 0, 0, 0),
            lambda c, lr, sr, dr: (lr[0], dr[c], 0, 0, 0), [], interpret)
        k_pool, v_pool = copy(layer, i32(cow_src), i32(cow_dst), k_pool,
                              v_pool)

    def tile_of(j, lr, pr, tr, br):
        return (lr[0], pr[j], 0, tr[j], 0)

    row_spec = pl.BlockSpec((1, g, tile, hd), lambda j, *_: (j, 0, 0, 0))
    write = _aliased_call(
        functools.partial(_write_kernel, tile=tile), "ita_kv_write",
        (n_jobs,), 4, (1, 1, g, tile, hd), tile_of, tile_of,
        [row_spec, row_spec], interpret)
    return write(layer, i32(pages), i32(tiles), i32(bits), new_k, new_v,
                 k_pool, v_pool)
