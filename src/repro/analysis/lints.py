"""Jit-hygiene lints for the fused serve/generate loops.

Two perf invariants from PRs 3 and 5 that nothing else guards:

- **Recompilation is bounded.** ``serve_continuous`` keys its jitted
  segment on ``mixed_steps = min(segment, next_pow2(n_steps))`` so a
  trace with arbitrary per-segment step counts compiles at most
  ``floor(log2(segment)) + 2`` variants — the pow2-rounding contract.
  And each variant must compile exactly *once*: a python scalar or
  weak-typed leaf leaking into the jit boundary retraces the same
  variant per call, which shows up here as ``_cache_size() > 1``.

- **Donation is used.** The segment/generate carries are donated
  (``donate_argnums``) so the KV pools update in place; XLA emits a
  "Some donated buffers were not usable" warning at compile time when a
  donated buffer cannot be aliased — on this invariant that warning is
  a failure, not a note.

- **Preemption does not retrace.** The overload path's victim eviction
  (``launch.steps.preempt_rows``) runs once per round with a host-built
  bool mask; a dtype or weak-type leak there would recompile the
  dispatch every eviction under sustained overload — exactly when the
  scheduler can least afford it. The lint drives a deterministic
  preempt→release→re-admit trace twice and requires exactly one trace
  of the dispatch (and that preemption actually fired, so the check
  can't go vacuous).

All lints drive the *real* loops (a tiny config, a mixed
chunked-prefill trace) rather than re-deriving the contracts, so any
refactor that silently changes the cache keying or breaks aliasing
fails the gate.
"""

from __future__ import annotations

import math
import warnings

import numpy as np

LINT_CONFIG = dict(
    d_model=32, n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
    vocab_size=64, n_layers=1)

SERVE_SEGMENT = 8
_DONATION_WARNING = "donated buffers were not usable"


def expected_variant_bound(segment: int) -> int:
    """Max distinct ``mixed_steps`` values: the powers of two up to
    ``segment`` plus ``segment`` itself (when not a power of two) plus
    the initial prefill segment — the PR-5 pow2-rounding contract."""
    return int(math.floor(math.log2(segment))) + 2


def lint_pow2_contract(segment: int = SERVE_SEGMENT,
                       max_steps: int = 1024) -> dict:
    """Closed-form check: the variant key is bounded over *every*
    possible per-segment step count, not just the ones a sample trace
    happens to produce."""
    from repro.runtime.generate import _next_pow2
    variants = {min(segment, _next_pow2(n)) for n in range(1, max_steps + 1)}
    bound = expected_variant_bound(segment)
    ok = len(variants) <= bound
    return {
        "name": "pow2-variant-contract",
        "ok": ok,
        "detail": f"{len(variants)} distinct mixed_steps variants over "
                  f"n_steps in [1, {max_steps}] at segment={segment} "
                  f"(bound {bound}): {sorted(variants)}",
    }


def _tiny_cfg():
    from repro.configs.base import ModelConfig
    c = LINT_CONFIG
    return ModelConfig(
        name="analysis-lint", family="dense", d_model=c["d_model"],
        n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"],
        head_dim=c["head_dim"], d_ff=c["d_ff"],
        vocab_size=c["vocab_size"],
        layer_groups=((("attn",), c["n_layers"]),), dtype="float32",
        attention_impl="ita", attention_backend="ita_onepass_pallas")


def _lint_trace(n_requests: int, vocab: int, seed: int = 7):
    from repro.runtime.generate import ServeRequest
    prng = np.random.default_rng(seed)
    reqs, step = [], 0
    for _ in range(n_requests):
        plen = int(prng.integers(3, 14))
        reqs.append(ServeRequest(
            prompt=prng.integers(0, vocab, plen).astype(np.int32),
            gen=int(prng.integers(1, 10)), arrival=step))
        step += int(prng.integers(0, 4))
    return reqs


def _run_instrumented_serve(n_requests: int):
    """Run ``serve_continuous`` over a mixed chunked trace with the
    segment factory wrapped to record every (variant key -> jitted fn),
    capturing compile-time warnings. Returns (variants, warnings)."""
    import jax

    from repro.models import init_model
    from repro.runtime import generate as GEN

    cfg = _tiny_cfg()
    params = init_model(jax.random.PRNGKey(0), cfg)
    reqs = _lint_trace(n_requests, cfg.vocab_size)

    GEN._serve_segment_fn.cache_clear()
    seen = {}
    orig = GEN._serve_segment_fn

    def recording(*key):
        fn = orig(*key)
        seen[key] = fn
        return fn

    GEN._serve_segment_fn = recording
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            GEN.serve_continuous(params, cfg, reqs, slots=2,
                                 segment=SERVE_SEGMENT, max_len=128,
                                 page_size=128, chunk_size=5)
    finally:
        GEN._serve_segment_fn = orig
        GEN._serve_segment_fn.cache_clear()
    return seen, caught


def _run_overload_serve():
    """Drive the preemption recovery path twice on one deterministic
    overload trace: a low-class request holds 2 of the pool's 3
    allocatable pages when a high-class arrival needs 2 — victim
    eviction, page release, re-admission with the longer resumed prompt.
    Returns (per-run preemption counts, preempt_rows trace count)."""
    import jax

    from repro.launch import steps as STEPS
    from repro.models import init_model
    from repro.runtime import generate as GEN

    cfg = _tiny_cfg()
    params = init_model(jax.random.PRNGKey(0), cfg)
    prng = np.random.default_rng(11)

    def req(arrival, priority):
        return GEN.ServeRequest(
            prompt=prng.integers(0, cfg.vocab_size, 130).astype(np.int32),
            gen=20, arrival=arrival, priority=priority)

    reqs = [req(0, 0), req(2, 1)]
    STEPS.preempt_rows.clear_cache()
    preempts = []
    for _ in range(2):
        res = GEN.serve_continuous(
            params, cfg, reqs, slots=2, segment=SERVE_SEGMENT,
            max_len=256, page_size=128, num_pages=4,
            chunk_size=64, preemption=True)
        preempts.append(res.preemptions)
    return preempts, STEPS.preempt_rows._cache_size()


def _run_instrumented_generate():
    """Run the fused ``generate()`` loop (donated caches carry),
    capturing compile-time warnings."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_model
    from repro.runtime import generate as GEN

    cfg = _tiny_cfg()
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompts = jnp.asarray(
        np.arange(2 * 6, dtype=np.int32).reshape(2, 6) % cfg.vocab_size)
    GEN._gen_loop.cache_clear()
    seen = {}
    orig = GEN._gen_loop

    def recording(*key):
        fn = orig(*key)
        seen[key] = fn
        return fn

    GEN._gen_loop = recording
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            GEN.generate(params, cfg, prompts, 5, max_len=64)
    finally:
        GEN._gen_loop = orig
        GEN._gen_loop.cache_clear()
    return seen, caught


def run_lints(*, smoke: bool = False) -> dict:
    """Run every lint; returns {"ok": bool, "lints": [...]}.

    ``smoke`` shortens the serve trace (CI gate); the contracts checked
    are identical.
    """
    results = [lint_pow2_contract()]

    seg_variants, serve_warnings = _run_instrumented_serve(
        6 if smoke else 12)
    gen_variants, gen_warnings = _run_instrumented_generate()

    bound = expected_variant_bound(SERVE_SEGMENT)
    n_var = len(seg_variants)
    results.append({
        "name": "serve-recompile-bound",
        "ok": n_var <= bound,
        "detail": f"{n_var} serve-segment variants compiled over the "
                  f"trace (bound {bound} at segment={SERVE_SEGMENT}): "
                  f"mixed_steps={sorted(k[-1] for k in seg_variants)}",
    })

    retraced = {
        f"segment{tuple(k[1:])}": fn._cache_size()
        for k, fn in seg_variants.items() if fn._cache_size() != 1}
    retraced.update({
        f"gen_loop{tuple(k[1:])}": fn._cache_size()
        for k, fn in gen_variants.items() if fn._cache_size() != 1})
    results.append({
        "name": "no-retrace-per-variant",
        "ok": not retraced,
        "detail": "every jitted variant compiled exactly once"
        if not retraced else
        f"variants retraced (python-scalar/weak-type leak into the jit "
        f"boundary?): {retraced}",
    })

    preempts, preempt_traces = _run_overload_serve()
    results.append({
        "name": "preemption-no-retrace",
        "ok": min(preempts) >= 1 and preempt_traces == 1,
        "detail": f"victim eviction fired {preempts} times over two "
                  f"identical overload runs; preempt_rows compiled "
                  f"{preempt_traces}x (must be exactly 1)",
    })

    donation_msgs = sorted({
        str(w.message).splitlines()[0]
        for w in (*serve_warnings, *gen_warnings)
        if _DONATION_WARNING in str(w.message)})
    results.append({
        "name": "donation-used",
        "ok": not donation_msgs,
        "detail": "every donated carry buffer was aliased by XLA"
        if not donation_msgs else
        f"XLA could not use donated buffers: {donation_msgs[:3]}",
    })

    return {"ok": all(r["ok"] for r in results), "lints": results}
