"""``correct`` on a tiny model on the CPU: sound runs pass, the control
(the reference one precision step down) and each planted fault fail.

Each case is one run of the harness in a fresh process (faults patch the
program), with the tiny cell of ``data/`` and the chip check skipped."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
SCRIPT = """
import json, sys, time
sys.path[:0] = {paths!r}
from benchlib import check, faults, runner, spec, weights
# at the tiny widths the attention outputs reach past the cells' clip
weights.INIT["clip"]["out"] = 2.0
conf = json.loads(open({conf!r}).read())
mix = json.loads(open({mix!r}).read())
cell = spec.Cell("tiny", 1, conf, mix, (), ())
if {fault!r}:
    faults.plant({fault!r})
out = runner.run(cell, {seed}, 2.0, False, time.perf_counter(), {{}},
                 control=True)
r = out["reading"]
program = check.compare(r.program, out["failed"], conf)
control = check.compare(r.control, 0, conf)
print("RESULT " + json.dumps({{"program": check.is_correct(program),
                              "control": check.is_correct(control),
                              "reading": [r.program, r.control]}},
                             default=vars))
"""


def run_tiny(seed, fault=None):
    code = SCRIPT.format(
        paths=[str(HERE.parent), str(HERE.parents[1] / "src")],
        conf=str(HERE / "data" / "tiny.json"),
        mix=str(HERE / "data" / "tiny-mix.json"), fault=fault, seed=seed)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][7:])


@pytest.mark.parametrize("seed", [1, 4])
def test_sound_run_passes_and_control_fails(seed):
    got = run_tiny(seed)
    assert got["program"], got
    assert not got["control"], got


@pytest.mark.parametrize("fault", ["token", "kv"])
def test_planted_fault_fails(fault):
    got = run_tiny(1, fault)
    assert not got["program"], got


def test_request_gap_sees_what_the_mean_dilutes():
    """Two wrong tokens in a 300-token answer lift that request's own
    mean gap past the phi3 cell's mean-gap limit, while the sample's
    mean stays under it."""
    from benchlib import check
    conf = json.loads((HERE.parent / "configs" /
                       "phi3-mini-3.8b-4l.json").read_text())
    limit = conf["check"]["mean_logit_gap_limit"]
    sound = [np.full(n, 0.004, np.float32) for n in (3000, 900, 300)]
    bad = [g.copy() for g in sound]
    bad[2][[10, 200]] = 4.0
    for gaps, ok in ((sound, True), (bad, False)):
        g = check._gaps(gaps)
        assert g.mean < limit
        assert (g.request <= limit) == ok
