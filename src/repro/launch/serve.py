"""Serving launcher: batched prefill + one-dispatch fused decode with the
ITA integer path.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --attention-impl ita --batch 4 --prompt-len 32 --gen 16

Demonstrates the production serving loop via ``repro.runtime.generate``:
quantized (int8) KV caches (``repro.runtime.kv_cache``), integer
streaming-softmax attention at prefill, then **one** jitted ``lax.scan``
over every decode step — sampling on device, no host round-trip per
token. ``--ragged`` serves a mixed-length batch (right-padded prompts,
per-sequence positions through the kernel meta); ``--paged`` swaps the
per-sequence rings for the shared paged KV pool (bit-identical tokens);
``--loop stepwise`` runs the legacy per-token host loop for comparison.

``--continuous`` is the full continuous-batching server: a Poisson
arrival trace (``--requests``/``--rate``) served through fixed decode
slots over the paged pool — finished sequences release their pages
between fused ``--segment``-step scan segments, the admission scheduler
prefills queued requests into the freed slots, and throughput is
reported as *sustained* tok/s over the whole trace.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import attention as ATT
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.hints import use_hints
from repro.launch.mesh import make_host_mesh
from repro.models import init_serving_params
from repro.models.attention import make_spec
from repro.runtime.generate import ServeRequest, generate, serve_continuous


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--attention-impl", default="ita",
                    choices=["float", "ita", "ibert"])
    ap.add_argument("--attention-backend", default="",
                    choices=[""] + ATT.list_backends(),
                    help="prefer a registry backend at every call site it "
                         "can serve (no backend covers all of prefill+"
                         "decode); capability dispatch fills the rest")
    ap.add_argument("--list-backends", action="store_true",
                    help="print every backend's verdict for this "
                         "arch/impl's decode spec, then exit")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loop", default="fused", choices=["fused", "stepwise"],
                    help="fused = one scan dispatch for all decode steps; "
                         "stepwise = legacy per-token host loop")
    ap.add_argument("--ragged", action="store_true",
                    help="serve a mixed-length batch: random per-sequence "
                         "prompt lengths in [prompt_len/2, prompt_len]")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="mask sequences after this token, stop counting "
                         "them toward tok/s, and exit early once all "
                         "finished (fused: while_loop; stepwise: a host "
                         "check that adds a per-step device sync)")
    ap.add_argument("--paged", action="store_true",
                    help="allocate the KV caches as shared paged pools "
                         "(PagedKVState) instead of per-sequence rings — "
                         "bit-identical tokens, O(live tokens) memory")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a Poisson arrival "
                         "trace: --batch slots, paged pool, admission "
                         "between --segment-step fused scan segments")
    ap.add_argument("--requests", type=int, default=16,
                    help="trace length for --continuous")
    ap.add_argument("--rate", type=float, default=0.25,
                    help="mean arrivals per decode step for --continuous")
    ap.add_argument("--segment", type=int, default=16,
                    help="decode steps per fused segment (admission "
                         "granularity) for --continuous")
    ap.add_argument("--page-size", type=int, default=128,
                    help="KV pool page size (tokens per page)")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="prompt tokens prefilling per slot per step "
                         "for --continuous")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget of the decode-maximal "
                         "scheduler (default slots - 1 + chunk_size)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="share identical prompt prefixes across requests "
                         "through the paged pool (copy-on-write): "
                         "matching page-aligned prefix chunks adopt "
                         "existing pages instead of re-prefilling")
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    help="prepend a common system prefix of this many "
                         "tokens to every --continuous request (makes "
                         "--prefix-sharing observable: >= page-size "
                         "tokens shared per request)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="number of SLO classes for --continuous: each "
                         "request draws a random class in [0, N) (higher "
                         "= more urgent; orders admission, the chunked "
                         "token budget and victim selection); per-class "
                         "p95 TTFT/latency are reported")
    ap.add_argument("--preemption", action="store_true",
                    help="page-pressure preemption for --continuous: "
                         "under pool/slot exhaustion, lower-class victims "
                         "release their pages and re-enqueue carrying "
                         "their generated prefix (bit-identical outputs)")
    ap.add_argument("--overload", type=float, default=1.0,
                    help="multiply --rate by this factor (arrival rate > "
                         "service rate exercises --preemption; 1 = off)")
    ap.add_argument("--journal-dir", default=None,
                    help="write-ahead request journal + snapshots here "
                         "(--continuous): admissions, per-segment token "
                         "high-water marks and completions are journaled "
                         "at every segment boundary (group commit, "
                         "bounded fsync lag), so a crashed serve can be "
                         "resumed bit-identically with --resume")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot the paged pool + prefix index every N "
                         "segments into <journal-dir>/snapshots (0 = off); "
                         "a usable snapshot warm-starts --resume, a "
                         "corrupt one degrades to cold-start from the "
                         "journal")
    ap.add_argument("--resume", action="store_true",
                    help="replay <journal-dir>/journal.jsonl before "
                         "serving: finished requests return without being "
                         "served twice, unfinished ones resume from their "
                         "last journaled boundary (bit-identical tokens)")
    ap.add_argument("--drain-timeout", type=float, default=None,
                    help="on SIGTERM (or Ctrl-C posing as one), stop "
                         "admitting and let in-flight requests finish; "
                         "after this many seconds stop at the next segment "
                         "boundary with progress journaled for --resume")
    ap.add_argument("--aging-steps", type=int, default=None,
                    help="starvation aging for --priority-classes: a "
                         "waiting request's effective class grows by one "
                         "every N virtual steps (bounded worst-case "
                         "admission delay for the low class)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke,
                     attention_impl=args.attention_impl,
                     attention_backend=args.attention_backend)

    if args.list_backends:
        spec = make_spec(cfg, mode="decode", causal=cfg.causal,
                         window=cfg.window, q_len=1)
        print(f"[serve] decode spec for {cfg.name}: {spec}")
        for name, verdict in ATT.backend_reasons(spec).items():
            mark = "eligible" if verdict is True else f"no — {verdict}"
            print(f"[serve]   {name:20s} {mark}")
        return
    use_compile_cache()
    # serving runs on the devices present (one v5e chip serves phi3-mini
    # whole); weights are created in the compute dtype (bf16), never f32
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(args.seed)

    if args.continuous:
        rng = np.random.default_rng(args.seed)
        with mesh, use_hints(mesh):
            params = init_serving_params(key, cfg)
            rate = max(args.rate, 1e-6) * max(args.overload, 1e-6)
            arrivals = np.cumsum(rng.exponential(1.0 / rate,
                                                 args.requests)).astype(int)
            system = rng.integers(0, cfg.vocab_size, args.system_prompt_len
                                  ).astype(np.int32)
            reqs = [ServeRequest(
                prompt=np.concatenate([system, rng.integers(
                    0, cfg.vocab_size, int(rng.integers(
                        max(1, args.prompt_len // 2), args.prompt_len + 1))
                    ).astype(np.int32)]),
                gen=int(rng.integers(max(2, args.gen // 4), args.gen + 1)),
                arrival=int(t),
                priority=int(rng.integers(0, max(1, args.priority_classes)))
            ) for t in arrivals]
            drain = None
            if args.journal_dir is not None:
                import signal

                from repro.runtime.journal import ServeDrain
                drain = ServeDrain()
                signal.signal(signal.SIGTERM,
                              lambda *_: drain.request())
            res = serve_continuous(
                params, cfg, reqs, slots=args.batch, segment=args.segment,
                max_len=args.system_prompt_len + args.prompt_len + args.gen,
                page_size=args.page_size, temperature=args.temperature,
                key=key if args.temperature > 0 else None,
                eos_id=args.eos_id, chunk_size=args.chunk_size, token_budget=args.token_budget,
                prefix_sharing=args.prefix_sharing,
                preemption=args.preemption,
                journal_dir=args.journal_dir,
                snapshot_every=args.snapshot_every, resume=args.resume,
                drain=drain, drain_timeout=args.drain_timeout,
                aging_steps=args.aging_steps)
        util = max((u for _, u in res.page_util), default=0.0)
        print(f"[serve] arch={cfg.name} continuous slots={args.batch} "
              f"segment={args.segment} page_size={args.page_size} "
              f"chunk={args.chunk_size}")
        print(f"[serve] {len(res.completed)}/{args.requests} requests, "
              f"{res.steps} steps / {res.segments} segments / "
              f"{res.admission_rounds} admission rounds")
        print(f"[serve] {res.total_tokens} tokens in {res.wall_s:.2f} s "
              f"-> sustained {res.tok_s:.1f} tok/s; latency p50 "
              f"{res.latency_quantile(0.5)*1e3:.0f} ms p95 "
              f"{res.latency_quantile(0.95)*1e3:.0f} ms; TTFT p50 "
              f"{res.ttft_quantile(0.5)*1e3:.0f} ms p95 "
              f"{res.ttft_quantile(0.95)*1e3:.0f} ms; peak page util "
              f"{util:.0%}")
        if args.prefix_sharing:
            print(f"[serve] prefix sharing: {res.prefix_hits}/"
                  f"{len(res.completed)} hits "
                  f"({res.prefix_hit_rate:.0%}), "
                  f"{res.shared_prefix_tokens} prompt tokens adopted "
                  f"from shared pages ({res.prefill_tokens} prefilled)")
        if args.journal_dir is not None:
            n_rep = sum(1 for c in res.completed if c.replayed)
            print(f"[serve] journal: dir={args.journal_dir} "
                  f"recovered={res.recovered} "
                  f"snapshot_restore={res.restored_from_snapshot} "
                  f"replayed {n_rep} requests / {res.replayed_tokens} "
                  f"tokens, recovery {res.recovery_s*1e3:.0f} ms, "
                  f"snapshot {res.snapshot_bytes/2**20:.1f} MiB"
                  + (" [drained]" if res.drained else ""))
        if args.preemption or args.priority_classes > 1:
            print(f"[serve] preemptions: {res.preemptions}")
            for prio in sorted(res.class_summary(), reverse=True):
                d = res.class_summary()[prio]
                aging = (f", aging bound {d['aging_bound_steps']} steps"
                         if "aging_bound_steps" in d else "")
                print(f"[serve]   class {prio}: {d['n']} requests, "
                      f"{d['preemptions']} preemptions, p95 TTFT "
                      f"{d['p95_ttft_s']*1e3:.0f} ms, p95 latency "
                      f"{d['p95_latency_s']*1e3:.0f} ms, p95 admission "
                      f"delay {d['p95_admit_delay_steps']} steps, max "
                      f"{d['max_admit_delay_steps']}{aging}")
        return

    with mesh, use_hints(mesh):
        params = init_serving_params(key, cfg)
        prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        lengths = None
        if args.ragged:
            key, lk = jax.random.split(key)
            lengths = jax.random.randint(
                lk, (args.batch,), max(1, args.prompt_len // 2),
                args.prompt_len + 1)
        frontend = None
        if cfg.frontend_dim:
            frontend = jax.random.normal(
                key, (args.batch, cfg.n_frontend_tokens, cfg.frontend_dim),
                jnp.float32)
        key, sample_key = jax.random.split(key)
        res = generate(params, cfg, prompts, args.gen, frontend=frontend,
                       temperature=args.temperature, key=sample_key,
                       prompt_lengths=lengths, eos_id=args.eos_id,
                       paged=args.paged, page_size=args.page_size,
                       early_exit=args.eos_id is not None, loop=args.loop)

    print(f"[serve] arch={cfg.name} impl={cfg.attention_impl} "
          f"loop={args.loop}" + (" ragged" if args.ragged else "")
          + (" paged" if args.paged else ""))
    if lengths is not None:
        print(f"[serve] prompt lengths: {lengths.tolist()}")
    print(f"[serve] prefill {args.batch}x{args.prompt_len} tokens in "
          f"{res.prefill_s*1e3:.1f} ms")
    dispatches = 1 if args.loop == "fused" else res.decode_steps
    print(f"[serve] decoded {res.decode_steps} steps x{args.batch} "
          f"({res.n_decode_tokens} live tokens, {dispatches} device "
          f"dispatch{'es' if dispatches != 1 else ''}) in "
          f"{res.decode_s*1e3:.1f} ms ({res.decode_tok_s:.1f} tok/s)")
    print("[serve] sample:", res.tokens[0, :12].tolist())


if __name__ == "__main__":
    main()
