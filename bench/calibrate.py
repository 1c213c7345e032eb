"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--fault token]

For each seed: one run of the cell as ``run.py`` makes it (weights,
traffic, warm-up, ramp, a window of ``--seconds``), then the sampled
requests through the reference and through the control (the reference
one precision step down: ``reference.__init__``). Both readings go
through ``check.compare`` with the cell's configuration, so each line
says whether the program and the control would read ``correct``; the
control has to read false. ``--fault`` plants one of
``benchlib.faults`` under the served path first, whose program reading
then has to read false too. Prints one JSON line per seed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from run import use_compile_cache  # noqa: E402

from benchlib import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--fault", choices=("token", "kv"))
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    # a cache directory the machine provides outlives this process's
    # checkout, so later calibration calls find their programs there
    use_compile_cache(pathlib.Path(os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", spec.CACHE_DIR)))
    import jax

    from benchlib import check, faults, runner
    runner.require_chip(cell.chips)
    peaks = spec.load_peaks(jax.devices()[0].device_kind)
    if args.fault:
        faults.plant(args.fault)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter() if i else T_START
        out = runner.run(cell, seed, args.seconds, False, t0, peaks,
                         control=True)
        r = out["reading"]
        program = check.compare(r.program, out["failed"], cell.config)
        control = check.compare(r.control, 0, cell.config)
        print(json.dumps({
            "seed": seed, "fault": args.fault,
            "program": dataclasses.asdict(r.program),
            "control": dataclasses.asdict(r.control),
            "program_correct": check.is_correct(program),
            "control_correct": check.is_correct(control),
            "served": r.served, "attempted": out["attempted"],
            "failed": out["failed"],
            "window_steps": [out["window"].step0, out["window"].step1],
            "metrics": out["metrics"], "device": out["device"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
