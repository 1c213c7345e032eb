"""A deep model served end to end stays close to float32: the served
path's error must not grow with depth faster than the control's.

An 8-layer model at toy widths (phi3's block: MHA, no biases), seeded
random weights and quantisation scales from the benchmark's own
generator (the cells' clips), serves three long
prompts through ``serve_continuous`` over 16-token pages, so each row's
attention streams 10-18 KV tiles whose maxima arrive in any order. The
benchmark's plain float32 reference (``bench/reference``) then scores
every served token by how far it lies below the reference's best token,
and so does its control: the same reference one precision step down
(fp8 matmuls, 4-bit integer attention), choosing its own greedy tokens.
"""

import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"

# The control's mean gap over the program's. On the chip the 4-layer
# phi3 cell reads the control at 4-13x the program (PERF.md); this
# model reads 5.5x and 9.0x on seeds 2 and 3 on the CPU, where a served
# path that streams the paper's DA and clips the attention output onto
# the int8 s_out grid reads 1.3x. 3 keeps the program clearly inside the
# control with room for the seed's noise.
CONTROL_FACTOR = 3.0


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.syspath_prepend(str(BENCH))
    from benchlib import weights
    return weights


def test_deep_served_model_stays_inside_the_control(harness):
    import reference
    from benchlib import check
    from benchlib import serve as bserve

    from repro.runtime.generate import ServeRequest, serve_continuous
    weights = harness
    conf = json.loads((BENCH / "tests" / "data" / "tiny.json").read_text())
    conf.update(registry="phi3-mini-3.8b", num_hidden_layers=8,
                num_key_value_heads=4, attention_bias=False)
    cfg = bserve.program_config(conf)
    params = weights.make(bserve.param_layout(cfg), conf, 2)
    prng = np.random.default_rng(2)
    reqs = [ServeRequest(prompt=prng.integers(0, 512, n).astype(np.int32),
                         gen=g, arrival=0)
            for n, g in ((200, 24), (150, 32), (260, 16))]
    res = serve_continuous(params, cfg, reqs, slots=4, segment=8,
                           max_len=512, page_size=16, chunk_size=32)
    assert len(res.completed) == len(reqs)

    w = check.reference_weights(params)
    clip = weights.INIT["clip"]
    got, ctl = [], []
    for c in res.completed:
        prompt, toks = reqs[c.index].prompt, np.asarray(c.tokens)
        seq = np.concatenate([prompt, toks]).astype(np.int32)
        rows = jnp.arange(prompt.size - 1, seq.size - 1)
        seq = jnp.asarray(np.pad(seq, (0, 512 - seq.size)))
        ref = np.asarray(reference.logits(w, seq, rows, conf, clip))
        low = np.asarray(reference.logits(w, seq, rows, conf, clip, "low"))
        at = np.arange(toks.size)
        best = ref.max(-1)
        got.append(best - ref[at, toks])
        ctl.append(best - ref[at, low.argmax(-1)])
    program, control = np.concatenate(got).mean(), np.concatenate(ctl).mean()
    assert program * CONTROL_FACTOR < control, (program, control)
