"""Sharding rules + multi-axis lower/compile smoke (the dry-run proper
runs via repro.launch.dryrun on 512 host devices; here: a tiny mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import sharding as SH
from repro.launch.steps import input_specs, lower_cell, params_shape

N_DEV = len(jax.devices())


def _mesh():
    if N_DEV >= 8:
        return jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    return jax.make_mesh((1, 1, 1), ("pod", "data", "model"))


def test_param_specs_divisibility():
    """Every assigned spec must divide the dim it shards."""
    mesh = _mesh()
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        ps = params_shape(cfg)
        shardings = SH.param_shardings(ps, mesh)

        def check(leaf, sh):
            spec = sh.spec
            for dim, ax in zip(leaf.shape, spec, strict=False):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % size == 0, (arch, leaf.shape, spec)

        jax.tree.map(check, ps, shardings)


@pytest.mark.skipif(N_DEV < 8, reason="needs 8 host devices")
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_lower_compile_all_kinds(arch):
    mesh = _mesh()
    cfg = get_config(arch, smoke=True)
    for shape in (ShapeConfig("t", 64, 8, "train"),
                  ShapeConfig("p", 64, 8, "prefill"),
                  ShapeConfig("d", 64, 8, "decode"),
                  ShapeConfig("d1", 128, 1, "decode")):
        lower_cell(cfg, shape, mesh).compile()


def test_hints_noop_without_mesh():
    from repro.launch.hints import constrain, heads_shardable
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x
    assert not heads_shardable(8)


def test_input_specs_shapes():
    cfg = get_config("qwen2-7b")
    from repro.configs.base import SHAPES
    sp = input_specs(cfg, SHAPES["train_4k"])
    assert sp["batch"]["tokens"].shape == (256, 4097)
    sp = input_specs(cfg, SHAPES["decode_32k"])
    assert sp["tokens"].shape == (128, 1)
    # KV cache leaves sized to the 32k context
    kv = [l for l in jax.tree.leaves(sp["caches"]) if l.ndim == 5]
    assert all(l.shape[2] == 32768 for l in kv)


def test_host_mesh_spans_the_devices_present():
    """Serving builds its mesh from the devices present (one chip serves
    phi3-mini whole), with Auto axes so the layers' sharding hints
    apply."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    assert mesh.devices.size == N_DEV
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_compile_cache_dir_from_env_else_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(compile_cache.CHECKOUT / ".jax_cache")
        assert compile_cache.use_compile_cache() == fixed
        assert (compile_cache.CHECKOUT / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
