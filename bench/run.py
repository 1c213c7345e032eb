"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, ``breakdown`` (traced runs) and, last,
``checks``: each number compared for ``correct`` with its limit. The
same numbers end standard error. Without a TPU, with fewer chips than
the cell asks for, or with Pallas in interpret mode it exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(path: pathlib.Path = spec.CACHE_DIR):
    """JAX's persistent compilation cache at one fixed path (inside the
    checkout unless told otherwise), for the benchmark and the program
    alike, holding every program and evicting none; the TPU runtime's
    own log files off (they would go to a fixed path under /tmp)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    use_compile_cache()
    import jax

    from benchlib import check, runner
    try:
        runner.require_chip(cell.chips)
        peaks = spec.load_peaks(jax.devices()[0].device_kind)
    except spec.SpecError as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    out = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                     T_START, peaks)
    chk = check.compare(out["reading"].program, out["failed"], cell.config)
    correct = check.is_correct(chk)
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": out["device"]}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = chk
    for name, c in chk.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    shutil.rmtree(runner.TRACE_DIR, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
