"""Compile a cell's serve segments for a described v5e, without a chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py --workload qwen2-4l-longdoc

Lowers the pure-decode segment and the all-mixed segment of the cell's
serving geometry at the configuration's widths and pool, for one chip of a
described ``v5e:2x2``, and prints each program's ``memory_analysis()``
(arguments, outputs, temporaries) and the weights' bytes: what the
compiler would refuse and what the pool leaves free, before any chip
time is spent.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchlib import serve, spec, traffic
    from repro.launch.steps import ServeSlotState
    from repro.models import init_caches
    from repro.runtime.generate import _serve_segment_fn
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    conf, mix = cell.config, cell.traffic
    geo = mix["geometry"]
    pages = conf["serving"]["pool_pages"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    cfg = serve.program_config(conf)
    params = on_chip(serve.param_layout(cfg))
    longest = int(traffic.schedule(mix).plens.max())
    state = on_chip(jax.eval_shape(
        lambda: ServeSlotState.init(geo["slots"], longest)))
    caches = on_chip(jax.eval_shape(lambda: init_caches(
        cfg, geo["slots"], max_len=geo["max_len"], paged=True,
        page_size=geo["page_size"], num_pages=pages)))
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    gb = 1e9
    w = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    kv = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    print(f"{cell.name}: weights {w / gb:.3f} GB, pool of {pages} pages + "
          f"tables {kv / gb:.3f} GB")
    for mixed in (None, geo["segment"]):
        fn = _serve_segment_fn(cfg, geo["segment"], False, None, 0,
                               None if mixed is None else geo["chunk_size"],
                               None if mixed is None else geo["token_budget"],
                               mixed)
        mem = fn.lower(params, state, caches, temp).compile() \
            .memory_analysis()
        print(f"segment mixed_steps={mixed}: args "
              f"{mem.argument_size_in_bytes / gb:.3f} GB, out "
              f"{mem.output_size_in_bytes / gb:.3f} GB, alias "
              f"{mem.alias_size_in_bytes / gb:.3f} GB, temp "
              f"{mem.temp_size_in_bytes / gb:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
