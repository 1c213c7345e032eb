"""TPU v5e compile rehearsals of the serve path's attention kernels.

Each case lowers and compiles one fused kernel call for a *described*
v5e chip (no chip attached): the compiler refuses what the Pallas TPU
lowering cannot tile — blocks off the (8, 128) grid, unsupported matmul
operand types, too much VMEM — all of which interpret mode accepts.
Shapes are phi3-mini-3.8b's (32 heads of 96, page 128, 8 slots of 2048
tokens) plus one GQA shape at head_dim 128 (qwen2-7b's 28/4 heads).

The topology is described only inside a fixture: libtpu may be loaded by
one process at a time, so nothing here touches it while modules are
imported or tests collected.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ita_attention.ops import fused_attention

SLOTS, PAGE, PAGES_PER_SEQ, CHUNK = 8, 128, 16, 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one — keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,hq,hkv,d,sq,ragged", [
    pytest.param("decode", 32, 32, 96, 1, False, id="decode-paged-phi3"),
    pytest.param("onepass", 32, 32, 96, CHUNK, True,
                 id="ragged-onepass-paged-phi3"),
    pytest.param("decode", 28, 4, 128, 1, False, id="decode-paged-gqa-hd128"),
])
def test_paged_kernel_compiles_for_v5e(one_chip, no_persistent_cache, kind,
                                       hq, hkv, d, sq, ragged):
    pages = SLOTS * PAGES_PER_SEQ + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, k, v, page_table, kv_len, q_offset, q_lens):
        return fused_attention(q, k, v, 0.05, 0.05, 0.05, 0.02,
                               q_offset=q_offset, kv_len=kv_len,
                               q_lens=q_lens if ragged else None, kind=kind,
                               page_table=page_table, interpret=False)

    pool = sds((pages, hkv, PAGE, d), jnp.int8)
    rows = sds((SLOTS,), jnp.int32)
    compiled = jax.jit(call).lower(
        sds((SLOTS, hq, sq, d), jnp.int8), pool, pool,
        sds((SLOTS, PAGES_PER_SEQ), jnp.int32), rows, rows, rows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the device layout pads head_dim to the 128-lane tile
    out_bytes = compiled.memory_analysis().output_size_in_bytes
    assert out_bytes == SLOTS * hq * sq * max(d, 128)


def test_paged_kernels_carry_their_names(one_chip, no_persistent_cache):
    """The serve path's two kernels keep their ``pallas_call`` names in
    the compiled program, where a profiler trace shows them, next to the
    ``tpu_custom_call`` target and the int8 result shape the benchmark's
    kernel readers match."""
    pages = SLOTS * PAGES_PER_SEQ + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q_dec, q_chunk, k, v, page_table, kv_len, q_offset, q_lens):
        common = dict(kv_len=kv_len, page_table=page_table, interpret=False)
        dec = fused_attention(q_dec, k, v, 0.05, 0.05, 0.05, 0.02,
                              q_offset=q_offset, kind="decode", **common)
        chunk = fused_attention(q_chunk, k, v, 0.05, 0.05, 0.05, 0.02,
                                q_offset=q_offset, q_lens=q_lens,
                                kind="onepass", **common)
        return dec, chunk

    pool = sds((pages, 32, PAGE, 96), jnp.int8)
    rows = sds((SLOTS,), jnp.int32)
    text = jax.jit(call).lower(
        sds((SLOTS, 32, 1, 96), jnp.int8), sds((SLOTS, 32, CHUNK, 96),
                                               jnp.int8),
        pool, pool, sds((SLOTS, PAGES_PER_SEQ), jnp.int32), rows, rows,
        rows).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name, q in (("ita_decode_paged", 8), ("ita_onepass_paged", CHUNK)):
        line, = [c for c in calls if f"%{name}" in c.split("=", 1)[0]]
        assert f"s8[{SLOTS * 32},{q},96]" in line, line[:200]


def test_one_row_dense_runs_on_the_mxu(one_chip, no_persistent_cache):
    """A one-row ``x @ w`` lowers to a vector multiply-reduce on the TPU,
    which sums in another order than the MXU path every larger row count
    takes; ``models.layers.dense`` pads it so decode at batch 1 (solo
    ``generate()``) rounds like decode at batch 8 (serving)."""
    from repro.models.layers import dense

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    x, w = sds((1, 1, 3072)), sds((3072, 8192))
    bare = jax.jit(lambda a, b: a @ b).lower(x, w).compile().as_text()
    padded = jax.jit(dense).lower(x, w).compile().as_text()
    assert " convolution(" not in bare
    assert " convolution(" in padded


def test_norm_sums_its_row_without_a_reduce(one_chip, no_persistent_cache):
    """RMSNorm's sum of squares is elementwise adds in one order, so the
    v5e compiler has no lane reduction whose tiling (and so summation
    order) it could choose per row count: the norm rounds alike in a
    decode step, a prompt chunk and a whole prompt."""
    from repro.models.layers import apply_norm

    p = {"scale": jax.ShapeDtypeStruct((3072,), jnp.float32,
                                       sharding=one_chip)}
    for rows in (1, 32, 909):
        x = jax.ShapeDtypeStruct((rows, 3072), jnp.bfloat16,
                                 sharding=one_chip)
        text = jax.jit(apply_norm).lower(p, x).compile().as_text()
        sums = [line for line in text.splitlines()
                if " reduce(" in line and "reduce_sum" in line]
        assert not sums, sums


@pytest.mark.parametrize("head_dim", [96, 128])
def test_serve_segments_copy_no_pool(one_chip, no_persistent_cache,
                                     monkeypatch, head_dim):
    """In the compiled decode and mixed serve segments, no op but the
    kernels touches a whole layer's KV pool: no copy, dynamic slice or
    dynamic-update-slice (fused or not) has the pool's shape, one layer's
    or the stack's. The pool write and both attention kernels are there,
    compiled. Two layers of four heads, page 128, 16 slots of one page
    (17 pages with the parking page)."""
    import re

    from repro.configs.base import ModelConfig
    from repro.launch.steps import ServeSlotState
    from repro.models import init_caches, init_model
    from repro.runtime.generate import _serve_segment_fn

    monkeypatch.setenv("ITA_PALLAS_INTERPRET", "0")
    cfg = ModelConfig(name=f"poolcopy-hd{head_dim}", family="dense",
                      d_model=256, n_heads=4, n_kv_heads=4,
                      head_dim=head_dim, d_ff=512, vocab_size=256,
                      layer_groups=((("attn",), 2),), dtype="bfloat16",
                      attention_impl="ita")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    state = on_chip(jax.eval_shape(lambda: ServeSlotState.init(16, 128)))
    caches = on_chip(jax.eval_shape(lambda: init_caches(
        cfg, 16, max_len=128, paged=True, page_size=128)))
    pool = caches[0][0]["mix"].k.shape
    assert pool[:4] == (2, 17, 4, 128), pool
    dims = ",".join(map(str, pool[1:]))
    shaped = re.compile(rf"= s8\[(2,)?{dims}\]\S* (copy|copy-start|"
                        rf"dynamic-slice|dynamic-update-slice|fusion)\(")
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    for chunk, kernel in ((None, "ita_decode_paged"),
                          (32, "ita_onepass_paged")):
        fn = _serve_segment_fn(cfg, 4, False, None, 0, chunk,
                               None if chunk is None else 512,
                               None if chunk is None else 4)
        text = fn.lower(params, state, caches, temp).compile().as_text()
        whole = [line.strip()[:160] for line in text.splitlines()
                 if shaped.search(line)]
        assert not whole, "\n".join(whole)
        calls = " ".join(line.split("=", 1)[0] for line in text.splitlines()
                         if 'custom_call_target="tpu_custom_call"' in line)
        for name in ("ita_kv_write", kernel):
            assert f"%{name}" in calls, (name, calls)
