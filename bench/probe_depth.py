"""Served tokens against the reference at other depths of a cell's
configuration: the depth at which the program can no longer be told
apart from its control.

    python3 bench/probe_depth.py --workload phi3-4l-reasoning \
        --layers 8,32 --seeds 11,12 --requests 8 --max-gen 256 \
        [--pool-pages 225] [--repeat 2] [--path serve|xla]

For each depth, seed and repeat: the first ``--requests`` requests of the
cell's mix for the seed, all due at once and their outputs cut to
``--max-gen``, are served at the cell's geometry and widths with the
configuration's layer count replaced (and its pool, with
``--pool-pages``). ``--path serve`` serves them through
``serve_continuous`` with the registry's fused ITA kernels, as the cells
do; ``--path xla`` generates the sampled ones one at a time through
``generate()`` on ring caches with the program's XLA ITA attention
(``ita_direct_xla``), a second witness of what the arithmetic gives
without the fused kernels. Then the sampled requests (the longest and
two drawn from the seed) go through the reference and its control, as
``calibrate.py`` does, and both readings through ``check.compare`` with
the cell's limits. Prints one JSON line per run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
import types

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from run import use_compile_cache  # noqa: E402

from benchlib import spec  # noqa: E402


def requests_for(mix: dict, vocab: int, seed: int, n: int, max_gen: int):
    from benchlib import traffic
    out = dict(mix["output_len"], max=min(mix["output_len"]["max"], max_gen))
    small = dict(mix, n_requests=n, warm_requests=n, output_len=out)
    return [dataclasses.replace(r, arrival=0)
            for r in traffic.generate(small, vocab, seed)]


def stub(r, tokens):
    return types.SimpleNamespace(rid=r.rid, prompt=r.prompt,
                                 plen=int(r.prompt.size), gen=r.gen,
                                 tokens=tokens)


def served(path: str, params, cfg, reqs, mix, pool_pages, seed):
    """The sampled requests with the tokens the chosen path gave them."""
    import jax.numpy as jnp
    import numpy as np

    from benchlib import check, serve
    if path == "serve":
        res = serve.serve(params, cfg, reqs, mix, pool_pages, None)
        done = [stub(reqs[c.index], np.asarray(c.tokens))
                for c in res.completed]
        return check.sample(done, check.SAMPLE_REQUESTS, seed)
    from repro.runtime.generate import generate
    xla = dataclasses.replace(cfg, attention_backend="ita_direct_xla")
    picked = check.sample([stub(r, None) for r in reqs],
                          check.SAMPLE_REQUESTS, seed)
    for s in picked:
        out = generate(params, xla, jnp.asarray(s.prompt)[None], s.gen,
                       max_len=s.plen + s.gen)
        s.tokens = np.asarray(out.tokens[0])
    return picked


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-gen", type=int, default=256)
    ap.add_argument("--pool-pages", type=int)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--path", choices=("serve", "xla"), default="serve")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    use_compile_cache(pathlib.Path(os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", spec.CACHE_DIR)))
    from benchlib import check, runner, serve, weights
    runner.require_chip(cell.chips)
    pool = args.pool_pages or cell.config["serving"]["pool_pages"]
    for layers in (int(x) for x in args.layers.split(",")):
        conf = dict(cell.config, num_hidden_layers=layers)
        cfg = serve.program_config(conf)
        for seed in (int(s) for s in args.seeds.split(",")):
            for rep in range(args.repeat):
                t0 = time.perf_counter()
                params = weights.make(serve.param_layout(cfg), conf, seed)
                reqs = requests_for(cell.traffic, conf["vocab_size"], seed,
                                    args.requests, args.max_gen)
                picked = served(args.path, params, cfg, reqs, cell.traffic,
                                pool, seed)
                t1 = time.perf_counter()
                r = check.read(check.reference_weights(params), conf,
                               picked, control=True)
                failed = sum(len(s.tokens) != s.gen for s in picked)
                print(json.dumps({
                    "layers": layers, "seed": seed, "repeat": rep,
                    "path": args.path, "pool_pages": pool,
                    "program": dataclasses.asdict(r.program),
                    "control": dataclasses.asdict(r.control),
                    "program_correct": check.is_correct(
                        check.compare(r.program, failed, conf)),
                    "control_correct": check.is_correct(
                        check.compare(r.control, 0, conf)),
                    "served": r.served,
                    "lengths": [[s.plen, s.gen] for s in picked],
                    "serve_s": t1 - t0,
                    "reference_s": time.perf_counter() - t1}), flush=True)
                del params, picked
    return 0


if __name__ == "__main__":
    sys.exit(main())
