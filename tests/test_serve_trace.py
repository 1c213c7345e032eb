"""The serve path's own names in a profiler trace (CPU).

``serve_continuous`` writes host spans (``serve.round`` and its children)
into the profiler's trace, and the segment program carries device scopes
in its ops' ``op_name``: what the benchmark's per-layer readers look for.
Tracing must not change what is served.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.runtime.generate as gen
from repro.configs.base import ModelConfig
from repro.launch.steps import ServeSlotState
from repro.models import init_caches, init_model
from repro.runtime.generate import ServeRequest, serve_continuous

CFG = ModelConfig(name="servetrace-smoke", family="dense", d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=128, layer_groups=((("attn",), 2),),
                  dtype="float32", attention_impl="ita",
                  attention_backend="ita_onepass_pallas")
SERVE = dict(slots=2, segment=4, max_len=128, page_size=128, chunk_size=8)
ROUND = ["serve.schedule", "serve.pool", "serve.dispatch", "serve.wait",
         "serve.readback"]


def _requests(seed=0, n=5):
    """Every request due at step 0, so every round dispatches a segment;
    prompts of several chunks, so mixed phases of several widths."""
    prng = np.random.default_rng(seed)
    return [ServeRequest(
        prompt=prng.integers(0, CFG.vocab_size,
                             int(prng.integers(3, 40))).astype(np.int32),
        gen=int(prng.integers(2, 10)), arrival=0) for _ in range(n)]


def _serve_spans(trace_dir):
    """The ``serve.*`` host spans of the newest trace under ``trace_dir``:
    ``(name, start_ns, end_ns, args)`` sorted by start."""
    files = sorted(trace_dir.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    data = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _rounds(spans):
    """Each ``serve.round`` with the spans that lie inside it."""
    rounds = [s for s in spans if s[0] == "serve.round"]
    return [(r, [s for s in spans if s[0] != "serve.round"
                 and r[1] <= s[1] and s[2] <= r[2]]) for r in rounds]


def _recording_builder(ks):
    """Wraps the segment builder so that each dispatched segment appends
    the ``k`` (mixed steps) the builder gave it."""
    orig = gen._serve_segment_fn

    def builder(cfg, segment, sample, eos_id, pad_id, chunk=None,
                budget=None, mixed_steps=None):
        fn = orig(cfg, segment, sample, eos_id, pad_id, chunk, budget,
                  mixed_steps)
        k = 0 if chunk is None else \
            (segment if mixed_steps is None else min(mixed_steps, segment))

        def call(*args, **kw):
            ks.append(k)
            return fn(*args, **kw)
        return call
    return builder


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    params = init_model(jax.random.PRNGKey(0), CFG)
    reqs = _requests()
    plain = serve_continuous(params, CFG, reqs, **SERVE)
    ks = []
    trace_dir = tmp_path_factory.mktemp("trace")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gen, "_serve_segment_fn", _recording_builder(ks))
        with jax.profiler.trace(str(trace_dir)):
            traced = serve_continuous(params, CFG, reqs, **SERVE)
    return plain, traced, _serve_spans(trace_dir), ks


def test_one_round_per_segment_with_its_children_in_order(served):
    _, res, spans, _ = served
    rounds = _rounds(spans)
    assert len(rounds) == res.segments > 1
    for r, children in rounds:
        assert [c[0] for c in children] == ROUND
        ends = [r[1]] + [t for c in children for t in (c[1], c[2])]
        assert ends == sorted(ends)
    assert [r[3]["segment"] for r, _ in rounds] == list(range(res.segments))


def test_dispatch_args_count_the_segments_steps(served):
    _, res, spans, ks = served
    dispatch = [s[3] for s in spans if s[0] == "serve.dispatch"]
    assert sum(a["steps"] for a in dispatch) == res.steps
    assert [a["mixed"] for a in dispatch] == ks
    assert 0 in ks and any(0 < k < SERVE["segment"] for k in ks)
    steps = np.cumsum([0] + [a["steps"] for a in dispatch[:-1]])
    assert [a["step"] for a in dispatch] == steps.tolist()
    rounds = [s[3] for s in spans if s[0] == "serve.round"]
    assert [(a["segment"], a["step"]) for a in rounds] \
        == [(a["segment"], a["step"]) for a in dispatch]


def test_tracing_serves_the_same_tokens(served):
    plain, traced, _, _ = served
    a = {c.index: c.tokens.tolist() for c in plain.completed}
    b = {c.index: c.tokens.tolist() for c in traced.completed}
    assert a == b and len(a) == len(_requests())


def test_register_and_journal_close_the_round(tmp_path):
    """With the prefix index and the journal on, their spans follow the
    readback inside each round."""
    params = init_model(jax.random.PRNGKey(0), CFG)
    shared = np.arange(1, 130, dtype=np.int32) % CFG.vocab_size
    reqs = [ServeRequest(prompt=np.concatenate([shared, r.prompt]),
                         gen=r.gen, arrival=0) for r in _requests(1, 3)]
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = serve_continuous(params, CFG, reqs, **dict(SERVE, max_len=256),
                               prefix_sharing=True,
                               journal_dir=str(tmp_path / "journal"))
    rounds = _rounds(_serve_spans(tmp_path / "trace"))
    assert len(rounds) == res.segments
    names = [[c[0] for c in children] for _, children in rounds]
    assert all(n == ROUND + ["serve.register", "serve.journal"]
               for n in names)


def test_segment_ops_carry_the_program_scopes():
    k, segment, budget = 2, 4, 16
    fn = gen._serve_segment_fn(CFG, segment, False, None, 0, 8, budget, k)
    lowered = fn.lower(init_model(jax.random.PRNGKey(0), CFG),
                       ServeSlotState.init(2, 40),
                       init_caches(CFG, 2, max_len=128, paged=True,
                                   page_size=128),
                       jnp.float32(1.0))
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    for phase in ("mixed_phase", "decode_phase"):
        assert any(n.endswith(phase + "/while") for n in names), phase
    for scope in ("embed", "attn_qkv", "kv_write", "attn_kernel",
                  "attn_out", "mlp", "layer_carry", "head", "sample"):
        assert any(scope in n.split("/") for n in names), scope
