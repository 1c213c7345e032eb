"""Step-time knee of a traffic mix, found on the CPU.

    python bench/knee.py --traffic reasoning --config phi3-mini-3.8b-4l \
        --loads 0.7,0.8,0.9,1.0,1.1 --steps 6000 [--write]

In decode steps the schedule of ``serve_continuous`` depends on the
lengths, the arrivals and the serving geometry (slots, page size and
pool, segment, chunk, budget), not on the weights or the chip. So a
smoke model (one layer, one head) served at the cell's exact geometry
on the CPU gives the schedule the chip will run. Each load is a share
of the slot capacity ``slots / E[steps a request holds a slot]``; for
each, the mix is served for ``--steps`` steps, and the requests it
admits, the admission delay of those due in the middle and last thirds
and the requests still waiting at the end are recorded. Past the
system's capacity more load admits no more requests: the slots are
saturated and the queue only grows. The knee is the sweep's highest
load below that plateau (the lowest load whose admissions reach 99% of
the sweep's most). The mix's own schedule is served
at each load, its arrivals stretched to the rate. ``--write`` stores
``LOAD`` times the knee as ``arrivals.rate_per_step`` in the mix's file;
the sweep's table belongs in PERF.md.

It also prints the step-count facts of the geometry from the segments'
own per-step outputs: the rows a mixed step computes (slots x chunk),
how many of them carry tokens, and how the budget is granted.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import multiprocessing as mp
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

LOAD = 0.8      # the cells run at this share of the knee


def hold_steps(mix: dict) -> float:
    """Mean steps a request holds a slot: its prefill chunks, its decode
    steps, and half a segment on each side for the boundaries it waits
    for."""
    import numpy as np

    from benchlib.traffic import schedule
    geo = mix["geometry"]
    s = schedule(mix, rate=1.0)
    return float(np.mean(np.ceil(s.plens / geo["chunk_size"]) + s.gens - 1
                         + geo["segment"]))


def capacity(mix: dict) -> float:
    """Requests per step that keep every slot busy."""
    return mix["geometry"]["slots"] / hold_steps(mix)


def serve_at(args):
    """One load, in a fresh process: returns the schedule's numbers."""
    traffic_name, config_name, load, steps = args
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    from benchlib import serve, traffic
    from benchlib.spec import BENCH_DIR
    from repro.configs.registry import get_config
    from repro.models import init_serving_params
    from repro.runtime.journal import ServeDrain
    mix = json.loads((BENCH_DIR / "traffic" / f"{traffic_name}.json")
                     .read_text())
    conf = json.loads((BENCH_DIR / "configs" / f"{config_name}.json")
                      .read_text())
    rate = load * capacity(mix)
    mix = dict(mix, n_requests=int(mix["warm_requests"])
               + int(rate * steps * 1.2) + 2)
    cfg = get_config("phi3-mini-3.8b", smoke=True, attention_impl="ita",
                     d_model=32, n_heads=1, n_kv_heads=1, head_dim=32,
                     d_ff=64, vocab_size=512,
                     layer_groups=((("attn",), 1),))
    params = init_serving_params(jax.random.PRNGKey(0), cfg)
    reqs = traffic.generate(mix, 512, 0, rate=rate)
    rec = serve.SegmentRecorder()
    rec.active = True
    undo = rec.install(mix["geometry"]["slots"])
    try:
        res = serve.serve(params, cfg, reqs, mix,
                          conf["serving"]["pool_pages"],
                          ServeDrain(after_steps=steps))
    finally:
        undo()
    start = traffic.warmup_steps(mix)
    span = steps - start
    done = {c.index: c for c in res.completed}
    waits = {1: [], 2: []}
    left = 0
    for i, r in enumerate(reqs):
        third = int(3 * (r.arrival - start) / span)
        if third not in waits or r.arrival > steps:
            continue
        if i in done:
            waits[third].append(done[i].admitted_step - r.arrival)
        else:
            left += 1
            waits[third].append(steps - r.arrival)
    geo = mix["geometry"]
    mixed = grants = live = emitted = 0
    for c in rec.host_calls():
        g = np.asarray(c.grants)
        mixed += c.mixed_steps
        grants += int(g[:, :c.mixed_steps].sum())
        live += int((g[:, :c.mixed_steps] > 0).sum())
        emitted += int(np.asarray(c.emits).sum())
    return {"load": load, "rate_per_step": rate, "steps": res.steps,
            "admitted": len(done), "left_waiting": left,
            "wait_mid": float(np.mean(waits[1])) if waits[1] else 0.0,
            "wait_late": float(np.mean(waits[2])) if waits[2] else 0.0,
            "wait_late_p95": float(np.percentile(waits[2], 95))
            if waits[2] else 0.0,
            "occupancy": emitted / (res.steps * geo["slots"]),
            "mixed_steps": mixed, "mixed_share": mixed / max(res.steps, 1),
            "rows_per_mixed_step": geo["slots"] * geo["chunk_size"],
            "granted_per_mixed_step": grants / max(mixed, 1),
            "live_rows_per_mixed_step": live / max(mixed, 1)}


def knee_row(rows: list) -> dict | None:
    """The sweep's highest load below the admissions' plateau, or None
    when the plateau starts at the sweep's lowest load or is never
    reached."""
    rows = sorted(rows, key=lambda r: r["load"])
    top = max(r["admitted"] for r in rows)
    first = next(i for i, r in enumerate(rows)
                 if r["admitted"] >= 0.99 * top)
    if first == 0 or first == len(rows) - 1:
        return None
    return rows[first - 1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--loads", default="0.7,0.8,0.9,1.0,1.1")
    ap.add_argument("--steps", type=int, default=6000)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    path = BENCH / "traffic" / f"{args.traffic}.json"
    mix = json.loads(path.read_text())
    loads = [float(x) for x in args.loads.split(",")]
    jobs = [(args.traffic, args.config, x, args.steps)
            for x in loads]
    ctx = mp.get_context("spawn")
    with cf.ProcessPoolExecutor(args.workers, mp_context=ctx) as pool:
        rows = list(pool.map(serve_at, jobs))
    cap = capacity(mix)
    print(f"capacity estimate {cap:.6f} requests/step")
    for r in rows:
        print(json.dumps(r))
    row = knee_row(rows)
    if row is None:
        print("the admissions' plateau starts at an end of the sweep; "
              "widen it")
        return 1
    knee = row["rate_per_step"]
    rate = LOAD * knee
    print(f"knee {knee:.6f} requests/step, rate {rate:.6f}")
    if args.write:
        mix["arrivals"]["rate_per_step"] = round(rate, 6)
        path.write_text(json.dumps(mix, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
