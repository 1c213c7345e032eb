"""CPU self-tests of the benchmark harness (``python -m pytest bench/tests``).
They import the harness the way ``bench/run.py`` does."""

import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
