"""One-chip smoke run of the serve path at phi3-mini-3.8b's full widths.

    python chip_smoke.py

Runs on one TPU in one process (a chip belongs to one process at a
time), through the entry points a user calls:

1. device: refuse to run unless JAX's first device is a TPU and the
   Pallas kernels resolve to compiled mode (so neither a CPU fallback
   nor ``ITA_PALLAS_INTERPRET=1`` can pass);
2. kernels: the compiled decode-paged and ragged onepass-paged kernels
   against the same calls with ``interpret=True``, int8 output bit for
   bit, at phi3's widths (32 heads of 96, page 128, 8 slots);
3. serve: phi3-mini-3.8b, all 32 layers, seeded random bf16 weights,
   ``attention_impl="ita"``; 16 seeded requests (prompts 128-1024
   tokens, gen 32-128) through ``serve_continuous`` with chunked
   admission, 8 slots and a page-128 pool — every request must complete;
4. parity: two of those requests generated alone with ``generate()``;
   the greedy tokens must match the served ones bit for bit (prefill is
   pinned to the fused family, which is what keeps the two paths on one
   KV tile schedule).

Any failure raises, so the script exits non-zero before its last line.
Compile seconds and peak device memory are printed as smoke figures, not
benchmark metrics. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.kernels.common import resolve_interpret  # noqa: E402
from repro.kernels.ita_attention.ops import fused_attention  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import init_serving_params  # noqa: E402
from repro.runtime.generate import (ServeRequest, generate,  # noqa: E402
                                    serve_continuous)

ARCH = "phi3-mini-3.8b"
SLOTS, PAGE, CHUNK, SEGMENT = 8, 128, 32, 16
N_REQUESTS, PROMPT_LEN, GEN = 16, (128, 1024), (32, 128)
MAX_LEN = PROMPT_LEN[1] + GEN[1]          # 1152 = 9 pages per slot
N_PARITY = 2
SEED = 0


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums XLA backend-compile seconds (JAX's own monitoring event)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def device_phase():
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"JAX's first device is {dev.platform!r}, not a TPU")
    check(not resolve_interpret(),
          "Pallas kernels resolve to interpret mode on the chip "
          "(is ITA_PALLAS_INTERPRET set?)")
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {use_compile_cache()}")
    return dev


def kernel_phase(cfg, rng):
    """Compiled vs interpret-mode paged kernels at the served geometry."""
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    per_seq = MAX_LEN // PAGE
    pages = SLOTS * per_seq + 1                      # + the parking page

    def i8(*shape):
        return jnp.asarray(rng.integers(-128, 128, shape, dtype=np.int8))

    k_pool, v_pool = i8(pages, hkv, PAGE, d), i8(pages, hkv, PAGE, d)
    table = jnp.asarray(rng.permutation(np.arange(1, pages, dtype=np.int32))
                        .reshape(SLOTS, per_seq))
    for kind, sq in (("decode", 1), ("onepass", CHUNK)):
        q_lens = (rng.choice(np.array([0, 1, CHUNK], np.int32), SLOTS)
                  if kind == "onepass" else np.ones(SLOTS, np.int32))
        kv_len = rng.integers(CHUNK, MAX_LEN + 1, SLOTS).astype(np.int32)
        q = i8(SLOTS, hq, sq, d)

        def run(interpret):
            return np.asarray(fused_attention(
                q, k_pool, v_pool, 0.05, 0.05, 0.05, 0.02,
                q_offset=jnp.asarray(kv_len - q_lens),
                kv_len=jnp.asarray(kv_len),
                q_lens=jnp.asarray(q_lens) if kind == "onepass" else None,
                kind=kind, page_table=table, interpret=interpret))

        compiled, interpreted = run(False), run(True)
        check(np.unique(compiled).size > 2,
              f"{kind}-paged kernel output is degenerate")
        mismatch = int(np.sum(compiled != interpreted))
        check(mismatch == 0, f"{kind}-paged kernel: {mismatch} int8 outputs "
                             f"differ between compiled and interpret mode")
        log(f"kernel {kind}-paged (B {SLOTS}, H {hq}, sq {sq}, hd {d}, "
            f"page {PAGE}): compiled == interpret on {compiled.size} int8 "
            f"outputs")


def trace(cfg, rng):
    reqs, step = [], 0
    for i in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
        reqs.append(ServeRequest(
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            gen=int(rng.integers(GEN[0], GEN[1] + 1)), arrival=step,
            request_id=f"smoke-{i}"))
        step += int(rng.integers(0, SEGMENT))
    return reqs


def serve_phase(params, cfg, reqs):
    res = serve_continuous(params, cfg, reqs, slots=SLOTS, segment=SEGMENT,
                           max_len=MAX_LEN, page_size=PAGE,
                           chunk_size=CHUNK)
    check(len(res.completed) == len(reqs),
          f"{len(res.completed)}/{len(reqs)} requests completed")
    served = {c.index: np.asarray(c.tokens) for c in res.completed}
    for i, r in enumerate(reqs):
        check(served[i].shape == (r.gen,),
              f"request {i} returned {served[i].shape[0]} of {r.gen} tokens")
        check(bool(np.all((served[i] >= 0) & (served[i] < cfg.vocab_size))),
              f"request {i} returned token ids outside the vocabulary")
    log(f"serve: {len(res.completed)}/{len(reqs)} requests, "
        f"{res.total_tokens} tokens, {res.steps} steps / {res.segments} "
        f"segments, {res.prefill_tokens} prompt tokens prefilled")
    return served


def parity_phase(params, cfg, reqs, served):
    for i in range(N_PARITY):
        r = reqs[i]
        solo = np.asarray(generate(params, cfg, jnp.asarray(r.prompt)[None],
                                   r.gen, max_len=MAX_LEN).tokens)[0]
        diff = np.flatnonzero(solo != served[i])
        check(diff.size == 0,
              f"request {i} (prompt {r.prompt.size}, gen {r.gen}) diverges "
              f"from solo generate() at token {diff[:1].tolist()}")
        log(f"parity: request {i} (prompt {r.prompt.size}, gen {r.gen}) "
            f"served == solo generate()")


def main():
    dev = device_phase()
    clock = CompileClock()
    rng = np.random.default_rng(SEED)
    cfg = get_config(ARCH, attention_impl="ita",
                     attention_backend="ita_onepass_pallas")

    t0 = time.perf_counter()
    kernel_phase(cfg, rng)
    params = init_serving_params(jax.random.PRNGKey(SEED), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    check(all(x.dtype == cfg.compute_dtype() for x in jax.tree.leaves(params)),
          "weights are not all in the compute dtype")
    log(f"{cfg.name}: {n_params / 1e9:.3f} B parameters in {cfg.dtype}, "
        f"{sum(n for _, n in cfg.layer_groups)} layers")
    reqs = trace(cfg, rng)
    served = serve_phase(params, cfg, reqs)
    parity_phase(params, cfg, reqs, served)

    stats = dev.memory_stats() or {}
    log(f"smoke figures, not benchmark metrics: wall "
        f"{time.perf_counter() - t0:.1f} s, XLA compile {clock.seconds:.1f} s, "
        f"peak device memory "
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
        f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
