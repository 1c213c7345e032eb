"""Batched autoregressive generation: quantized prefill → fused on-device
decode through the int8 KV caches.

The serving loop the launchers and examples share: one jitted prefill
over the whole prompt batch (streaming ITA attention, caches written
once), then **one** jitted ``lax.scan`` over all decode steps — the
carry ``(caches, tok, pos, key, done)`` lives on device, sampling
(greedy or temperature) happens on device with a threaded PRNG, and the
whole ``(B, gen)`` token block returns in a single dispatch. No host
round-trip per generated token: ITA's streaming softmax minimizes data
movement inside the kernel, and the fused loop extends that to the
serving dataflow around it.

    from repro.runtime.generate import generate
    res = generate(params, cfg, prompts, gen=32)
    res.tokens          # (B, gen) int32
    res.decode_tok_s    # decode throughput (live sequences only)

Ragged batches: pass ``prompt_lengths`` (B,) for right-padded prompts —
each sequence prefills, positions and decodes at its own length through
the per-row kernel meta (no padding to the longest prompt's position).
``loop="stepwise"`` keeps the legacy per-step host loop (one dispatch
per token) as the parity/benchmark reference. ``paged=True`` swaps the
per-sequence rings for shared paged KV pools (bit-identical tokens).

``serve_continuous`` is the continuous-batching server on top: a fixed-
slot batch over the paged pool, fused ``lax.scan`` segments with host
admission between them — finished sequences release their pages, and
arrived prompts enter via **chunked prefill** (default): admission only
enqueues token ids, the segments prefill them chunk-by-chunk straight
into pool pages, interleaved with decode under a decode-maximal token
budget. The stop-the-world PR-4 path survives as ``admission="stall"``.
``prefix_sharing=True`` adds copy-on-write KV prefix sharing: a host
``PrefixIndex`` maps page-aligned prompt chunks to the physical pages
already holding their bytes, admission adopts matching pages (+1
refcount, zero prefill) and chunked prefill starts at the first unshared
token. Throughput is sustained tok/s over the whole arrival trace
(DESIGN.md §Paged KV + continuous-batching dataflow, §Chunked-prefill
dataflow, §Prefix sharing + copy-on-write dataflow).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

LOOPS = ("fused", "stepwise")

# Every jit that runs the model. XLA may keep a bf16 value at f32 inside
# one fusion and round it in another ("excess precision"); which it does
# depends on the program, so a served token could round unlike the same
# token generated alone. Off, every bf16 rounding the model states
# happens in every program.
MODEL_JIT_OPTIONS = {"xla_allow_excess_precision": False}


@functools.lru_cache(maxsize=32)
def _steps(cfg):
    """Jitted prefill/decode steps, cached per (hashable, frozen) config so
    repeated generate() calls reuse compilations."""
    from repro.launch.steps import make_decode_step, make_prefill_step
    prefill = jax.jit(make_prefill_step(cfg),
                      compiler_options=MODEL_JIT_OPTIONS)
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,),
                     compiler_options=MODEL_JIT_OPTIONS)
    return prefill, decode


@functools.lru_cache(maxsize=32)
def _gen_loop(cfg, gen, sample, eos_id, pad_id, early_exit):
    """Jitted fused generation loop, cached per static shape of the loop.
    The caches carry is donated — the ring buffers update in place across
    the whole scan."""
    from repro.launch.steps import make_generate_loop
    loop = make_generate_loop(cfg, gen=gen, sample=sample, eos_id=eos_id,
                              pad_id=pad_id, early_exit=early_exit)
    return jax.jit(loop, donate_argnums=(2,),
                   compiler_options=MODEL_JIT_OPTIONS)


@dataclasses.dataclass
class GenerateResult:
    tokens: jax.Array            # (B, gen) generated token ids
    prefill_s: float             # wall-clock of the prefill step
    decode_s: float              # wall-clock of all decode steps
    decode_steps: int            # steps actually run (< gen-1 on early exit)
    n_decode_tokens: int         # decode tokens from *live* sequences

    @property
    def decode_tok_s(self) -> float:
        return self.n_decode_tokens / max(self.decode_s, 1e-9)


def _first_paged(caches):
    """First PagedKVState node in a cache pytree (period-stacked leaves),
    or None — how the serving stack sniffs the cache layout."""
    from repro.attention import PagedKVState
    for node in jax.tree.leaves(
            caches, is_leaf=lambda x: isinstance(x, PagedKVState)):
        if isinstance(node, PagedKVState):
            return node
    return None


def _paged_geometry(paged):
    """(batch, num_pages, page_size) of a period-stacked PagedKVState."""
    return (paged.page_table.shape[1], paged.k.shape[1], paged.k.shape[3])


def _validate_pool_provision(caches, batch: int, tokens_per_seq: int):
    """Lockstep generate() has no admission scheduler rationing pages, so
    an undersized pool would overdraw the on-device allocator mid-scan
    and silently double-book pages — refuse statically instead. The
    worst case is exact: every sequence grows to min(tokens, window)."""
    from repro.attention import PagedKVState
    for node in jax.tree.leaves(
            caches, is_leaf=lambda x: isinstance(x, PagedKVState)):
        if not isinstance(node, PagedKVState):
            continue
        num_pages, page = node.k.shape[1], node.k.shape[3]
        npps = node.page_table.shape[2]
        per_seq = min(-(-min(tokens_per_seq, npps * page) // page), npps)
        if batch * per_seq > num_pages - 1:
            raise ValueError(
                f"paged pool undersized for lockstep generate: {batch} "
                f"sequences x {per_seq} pages each > {num_pages - 1} "
                f"allocatable pages (num_pages={num_pages}, page_size="
                f"{page}) — raise num_pages, or serve through "
                f"serve_continuous, whose admission scheduler rations an "
                f"oversubscribed pool")


def _validate_caches(caches, cfg, batch: int, max_len: int):
    """A reused ``caches=`` pytree must match what this call would have
    allocated — silently decoding into wrong-capacity rings (or
    wrong-geometry page tables) corrupts positions/eviction/allocation.
    Paged caches are validated against the paged allocation of the same
    batch/max_len, with the mismatched field named (batch / pool size /
    page size / page-table width)."""
    from repro.models import init_caches
    paged = _first_paged(caches)
    kwargs = {}
    detail = f"batch ({batch}) and max_len ({max_len})"
    if paged is not None:
        pt_batch, num_pages, page_size = _paged_geometry(paged)
        if pt_batch != batch:
            raise ValueError(
                f"caches= batch mismatch: page tables hold {pt_batch} "
                f"slots but this call decodes batch={batch}")
        # pool size and page size are free choices (oversubscription /
        # granularity) — validate the rest of the tree against them
        kwargs = dict(paged=True, page_size=page_size, num_pages=num_pages)
        detail += (f", pool size ({num_pages} pages) and page size "
                   f"({page_size})")
    expected = jax.eval_shape(functools.partial(init_caches, cfg, batch,
                                                max_len, **kwargs))
    exp_leaves, exp_tree = jax.tree_util.tree_flatten(expected)
    got = jax.tree_util.tree_flatten_with_path(caches)[0]
    got_tree = jax.tree_util.tree_structure(caches)
    if exp_tree != got_tree:
        raise ValueError(
            f"caches= structure does not match init_caches(cfg, batch="
            f"{batch}, max_len={max_len}"
            + (", paged=True" if paged is not None else "") +
            f") for {cfg.name!r} — pass the max_len the caches were "
            f"allocated with")
    for e, (path, g) in zip(exp_leaves, got, strict=True):
        if e.shape != g.shape or e.dtype != g.dtype:
            field = jax.tree_util.keystr(path)
            raise ValueError(
                f"caches= leaf {field} mismatch: expected "
                f"{e.shape}/{e.dtype}, got {g.shape}/{g.dtype} — reused "
                f"caches must match this call's {detail}")


def _validate_ragged(cfg, prompt_lengths, prompt_len: int):
    if not cfg.causal:
        raise ValueError("ragged prompts need causal attention (pad "
                         "columns must be invisible to valid rows)")
    kinds = {k for pat, _ in cfg.layer_groups for k in pat}
    recurrent = kinds - {"attn", "local", "swa", "enc", "cross",
                         "attn_cross"}
    if recurrent:
        raise ValueError(
            f"ragged prompts are attention-only (recurrent blocks "
            f"{sorted(recurrent)} would roll pad tokens into their state)")
    # every ring must hold the whole padded prompt (per-row eviction of a
    # padded prefill would need per-row rolls); window kinds cap capacity
    for kind, cap in (("swa", cfg.window), ("local", cfg.local_window)):
        if kind in kinds and cap < prompt_len:
            raise ValueError(
                f"ragged prompts need ring capacity >= the padded prompt "
                f"length; {kind!r} blocks cap it at {kind}-window {cap} < "
                f"prompt_len {prompt_len} — shorten/split the prompts")
    lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if lengths.ndim != 1:
        raise ValueError("prompt_lengths must be a (B,) vector")
    lnp = np.asarray(lengths)
    if lnp.min() < 1 or lnp.max() > prompt_len:
        raise ValueError(f"prompt_lengths must lie in [1, {prompt_len}] "
                         f"(the padded prompt width); got {lnp.tolist()}")
    return lengths


def generate(params, cfg, prompts, gen: int, *, frontend=None,
             temperature: float = 0.0, key=None, max_len: int | None = None,
             caches=None, paged: bool = False, page_size: int = 128,
             num_pages: int | None = None, prompt_lengths=None,
             eos_id: int | None = None, pad_id: int = 0,
             loop: str = "fused", early_exit: bool = False) -> GenerateResult:
    """Prefill the prompt batch, then decode ``gen`` tokens on-device.

    ``prompts`` (B, S) int32, right-padded when ``prompt_lengths`` (B,)
    declares a ragged batch. ``max_len`` sizes the KV caches (default
    S + gen; smaller values window-evict; ``KVCacheState.init``
    block-aligns capacities above one KV block, so the decode kernels'
    per-step ring pad is statically a no-op). ``paged=True`` allocates
    the KV as shared paged pools (``PagedKVState``; bit-identical tokens
    to the ring layout at ``page_size`` = the ring's KV block) — the
    continuous-batching layout, also accepted via ``caches=``. Pass
    ``caches`` to reuse pre-allocated buffers across calls (validated
    against batch/max_len and, for paged caches, the pool geometry).
    ``eos_id``: sequences that emit it are masked to
    ``pad_id`` and stop counting toward ``decode_tok_s``; with
    ``early_exit=True`` decoding stops once every sequence finished
    (fused: a ``lax.while_loop`` instead of the scan; stepwise: a host
    check per step). ``loop="stepwise"`` runs the per-token host loop
    instead (parity/benchmark reference — bit-identical tokens to the
    fused loop).
    """
    from repro.launch.steps import advance_step, sample_token
    from repro.models import init_caches

    if loop not in LOOPS:
        raise ValueError(f"loop={loop!r} not in {LOOPS}")
    if early_exit and eos_id is None:
        raise ValueError("early_exit needs an eos_id to exit on")
    b, prompt_len = prompts.shape
    if gen <= 0:
        return GenerateResult(tokens=jnp.zeros((b, 0), jnp.int32),
                              prefill_s=0.0, decode_s=0.0, decode_steps=0,
                              n_decode_tokens=0)
    max_len = max_len or prompt_len + gen
    prefill, decode = _steps(cfg)
    if caches is None:
        caches = init_caches(cfg, b, max_len=max_len, paged=paged,
                             page_size=page_size, num_pages=num_pages)
    else:
        _validate_caches(caches, cfg, b, max_len)
    _validate_pool_provision(caches, b, prompt_len + gen)
    lengths = None
    if prompt_lengths is not None:
        lengths = _validate_ragged(cfg, prompt_lengths, prompt_len)

    sample = temperature > 0.0 and key is not None
    temperature = jnp.asarray(temperature if sample else 1.0, jnp.float32)

    t0 = time.perf_counter()
    logits, caches = prefill(params, prompts, caches, frontend, lengths)
    tok, key = sample_token(logits, key, temperature, sample=sample)
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    # decode starts each sequence at its own stream position
    pos0 = lengths if lengths is not None \
        else jnp.full((b,), prompt_len, jnp.int32)

    t0 = time.perf_counter()
    if loop == "fused":
        run = _gen_loop(cfg, gen, sample, eos_id, pad_id, early_exit)
        rest, n_dec, steps_run, caches = run(params, tok, caches, pos0, key,
                                             temperature, frontend)
        tokens = jnp.concatenate([tok, rest], axis=1)
        jax.block_until_ready(tokens)
        n_decode, steps_run = int(n_dec), int(steps_run)
    else:                                   # stepwise host-loop reference
        done = (tok[:, 0] == eos_id) if eos_id is not None \
            else jnp.zeros((b,), jnp.bool_)
        out, pos, steps_run = [tok], pos0, 0
        n_dec = jnp.zeros((), jnp.int32)    # device-side (no per-step sync)
        for _ in range(gen - 1):
            if early_exit and bool(jnp.all(done)):   # opt-in per-step sync
                break
            steps_run += 1
            logits, caches = decode(params, tok, caches, pos, frontend)
            tok, key, done, n_dec = advance_step(
                logits, key, temperature, done, n_dec, sample=sample,
                eos_id=eos_id, pad_id=pad_id)
            out.append(tok)
            pos = pos + 1
        if len(out) < gen:                  # early exit: the rest is pad
            out.append(jnp.full((b, gen - len(out)), pad_id, jnp.int32))
        tokens = jnp.concatenate(out, axis=1)
        jax.block_until_ready(tokens)
        n_decode = int(n_dec)
    t_decode = time.perf_counter() - t0

    return GenerateResult(tokens=tokens, prefill_s=t_prefill,
                          decode_s=t_decode, decode_steps=steps_run,
                          n_decode_tokens=n_decode)


# ---------------------------------------------------------------------------
# Continuous batching: paged pool + admission scheduler + fused segments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One serving request of an arrival trace. ``arrival`` is in virtual
    time units = decode steps (the scheduler's clock), ``gen`` counts all
    generated tokens including the one sampled from prefill.
    ``priority`` is the request's SLO class (higher = more urgent): it
    orders admission, steers the mixed segments' prompt-chunk budget and
    selects preemption victims (strictly lower classes only).
    ``request_id`` is a stable identity for journaling: re-submitting
    the same id after a crash recovery dedupes against the journal (a
    completed request replays instead of serving twice). Defaults to
    ``req-<trace index>`` when unset; ids must be unique per trace."""
    prompt: Any                      # (S,) int32 token ids
    gen: int
    arrival: int = 0
    priority: int = 0
    request_id: str | None = None


@dataclasses.dataclass
class CompletedRequest:
    index: int                       # position in the submitted trace
    arrival: int                     # virtual (step) arrival time
    admitted_step: int               # step count when FIRST admitted
    finished_step: int               # step count when the slot freed
    arrived_s: float                 # wall-clock when first admittable
    finished_s: float                # wall-clock at the freeing boundary
    tokens: Any                      # (gen,) int32 generated ids
    first_token_s: float = 0.0       # wall-clock of the first emitted token
    priority: int = 0                # the request's SLO class
    preemptions: int = 0             # times this request was evicted
    replayed: bool = False           # rebuilt from the journal, not served

    @property
    def latency_s(self) -> float:
        return self.finished_s - self.arrived_s

    @property
    def ttft_s(self) -> float:
        """Time to first token: queue wait + prompt processing."""
        return self.first_token_s - self.arrived_s


@dataclasses.dataclass
class ServeResult:
    completed: list                  # CompletedRequest, completion order
    wall_s: float                    # whole-trace wall clock
    steps: int                       # decode steps executed
    segments: int                    # fused segments dispatched
    admission_rounds: int            # admission dispatches
    page_util: list                  # (step, fraction of pool pages held)
    prefill_stall_s: float = 0.0     # wall spent in stop-the-world prefill
                                     # dispatches (0 under chunked admission)
    prefill_tokens: int = 0          # prompt tokens actually prefilled
    shared_prefix_tokens: int = 0    # prompt tokens skipped via adoption
    prefix_hits: int = 0             # admissions that adopted >= 1 page
    preemptions: int = 0             # victim evictions (incl. fault kills)
    straggler_segments: int = 0      # segments the watchdog flagged slow
    drained: bool = False            # graceful drain cut the serve short
    recovered: bool = False          # this serve resumed from a journal
    restored_from_snapshot: bool = False   # warm pool/index restore hit
    replayed_tokens: int = 0         # tokens recovered from the journal
    snapshot_bytes: int = 0          # last snapshot's on-disk leaf bytes
    recovery_s: float = 0.0          # wall spent in replay + restore
    aging_steps: int | None = None   # starvation-aging period (None = off)
    max_class: int = 0               # highest SLO class in the trace

    @property
    def total_tokens(self) -> int:
        return sum(int(np.asarray(c.tokens).size) for c in self.completed)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of completed requests admitted with a shared prefix."""
        return self.prefix_hits / max(len(self.completed), 1)

    @property
    def tok_s(self) -> float:
        return self.total_tokens / max(self.wall_s, 1e-9)

    @property
    def prefill_stall_frac(self) -> float:
        return self.prefill_stall_s / max(self.wall_s, 1e-9)

    def _quantile(self, values, q: float) -> float:
        vals = sorted(values)
        if not vals:
            return 0.0
        return vals[min(int(q * len(vals)), len(vals) - 1)]

    def _of_class(self, priority):
        return (c for c in self.completed
                if priority is None or c.priority == priority)

    def latency_quantile(self, q: float, priority: int | None = None):
        return self._quantile(
            (c.latency_s for c in self._of_class(priority)), q)

    def ttft_quantile(self, q: float, priority: int | None = None):
        return self._quantile(
            (c.ttft_s for c in self._of_class(priority)), q)

    def admission_delay_quantile(self, q: float,
                                 priority: int | None = None):
        """Virtual-time TTFT proxy: decode steps from arrival to first
        admission. Deterministic (no wall clock), so SLO assertions on it
        are machine-independent — the bench smoke gate."""
        return self._quantile(
            (c.admitted_step - c.arrival for c in self._of_class(priority)),
            q)

    def class_summary(self) -> dict:
        """Per-SLO-class accounting: count, total preemptions suffered,
        p95 TTFT / latency / admission delay, the worst admission delay
        actually suffered, and — when starvation aging is on — the
        class's ``aging_bound_steps``: the virtual-step horizon at which
        a waiting request of this class reaches the priority cap and can
        no longer be overtaken by any newly arrived class (the aging
        guarantee property-tested in tests)."""
        out = {}
        for c in self.completed:
            d = out.setdefault(c.priority, {"n": 0, "preemptions": 0})
            d["n"] += 1
            d["preemptions"] += c.preemptions
        for prio, d in out.items():
            d["p95_ttft_s"] = self.ttft_quantile(0.95, priority=prio)
            d["p95_latency_s"] = self.latency_quantile(0.95, priority=prio)
            d["p95_admit_delay_steps"] = self.admission_delay_quantile(
                0.95, priority=prio)
            d["max_admit_delay_steps"] = max(
                (c.admitted_step - c.arrival for c in self._of_class(prio)),
                default=0)
            if self.aging_steps is not None:
                d["aging_bound_steps"] = self.aging_steps * (
                    self.max_class + 1 - prio)
        return out


@functools.lru_cache(maxsize=32)
def _serve_segment_fn(cfg, segment, sample, eos_id, pad_id, chunk=None,
                      budget=None, mixed_steps=None):
    from repro.launch.steps import make_serve_segment
    seg = make_serve_segment(cfg, segment=segment, sample=sample,
                             eos_id=eos_id, pad_id=pad_id, chunk=chunk,
                             budget=budget, mixed_steps=mixed_steps)
    return jax.jit(seg, donate_argnums=(1, 2),
                   compiler_options=MODEL_JIT_OPTIONS)


def _is_kv_state(x):
    from repro.attention import KVCacheState, PagedKVState
    return isinstance(x, (KVCacheState, PagedKVState))


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def _release_slots(caches, finished):
    """Return every finished slot's pages (all layers) to the free
    stacks."""
    from repro.attention import PagedKVState

    def rel(node):
        if isinstance(node, PagedKVState):
            return jax.vmap(lambda p: p.release(finished))(node)
        return node

    return jax.tree.map(rel, caches, is_leaf=_is_kv_state)


def _admit_chunked(state, slot_ids, prompts, lengths, gens, req_keys,
                   shared=None, prios=None):
    """Chunked admission state write — lives in ``launch.steps`` next to
    ``ServeSlotState``; kept callable from here for the serve loop and
    its tests."""
    from repro.launch.steps import admit_chunked
    return admit_chunked(state, slot_ids, prompts, lengths, gens, req_keys,
                         shared, prios)


def _preempt_rows(state, mask):
    """One-dispatch victim eviction of every slot in ``mask`` — see
    ``launch.steps.preempt_rows``."""
    from repro.launch.steps import preempt_rows
    return preempt_rows(state, mask)


def _admit_stall(state, slot_ids, lengths, tok0, new_done, new_rem,
                 req_keys, prios=None):
    from repro.launch.steps import admit_stall
    return admit_stall(state, slot_ids, lengths, tok0, new_done, new_rem,
                       req_keys, prios)


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def _adopt_prefix_slots(caches, slot_ids, pages, n_pages, n_tokens):
    """Point freshly admitted slots' leading page-table entries at the
    shared prefix pages (every layer's pool — the allocators run in
    lockstep, so one page id is valid for all of them). Rows with
    ``slot_ids[i] < 0`` or ``n_pages[i] == 0`` are no-ops."""
    from repro.attention import PagedKVState

    def one(node):
        if isinstance(node, PagedKVState):
            return jax.vmap(lambda p: p.adopt_prefix(slot_ids, pages,
                                                     n_pages, n_tokens))(node)
        return node

    return jax.tree.map(one, caches, is_leaf=_is_kv_state)


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def _pin_pages(caches, pages):
    """+1 refcount on ``pages`` (flat, -1 padded) in every layer's pool —
    the prefix index's registration pin."""
    from repro.attention import PagedKVState

    def one(node):
        if isinstance(node, PagedKVState):
            return jax.vmap(lambda p: p.incref_pages(pages))(node)
        return node

    return jax.tree.map(one, caches, is_leaf=_is_kv_state)


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def _unpin_pages(caches, pages):
    """Drop the index pin on ``pages`` (flat, -1 padded); pages reaching
    refcount zero return to every layer's free stack."""
    from repro.attention import PagedKVState

    def one(node):
        if isinstance(node, PagedKVState):
            return jax.vmap(lambda p: p.decref_pages(pages))(node)
        return node

    return jax.tree.map(one, caches, is_leaf=_is_kv_state)


def _check_paged_invariants(caches, pins=None):
    """Debug-mode host check: run ``PagedKVState.check_invariants`` on
    every layer of every paged pool in the cache tree (``pins``: the
    host-side {page: count} pin ledger). Slow — device_get of the full
    bookkeeping state — gated behind ``debug_invariants`` / the
    ``ITA_PAGED_DEBUG`` env var in ``serve_continuous``."""
    import dataclasses as dc

    from repro.attention import PagedKVState
    for node in jax.tree.leaves(caches, is_leaf=_is_kv_state):
        if not isinstance(node, PagedKVState):
            continue
        layers = node.k.shape[0]
        for i in range(layers):
            layer = PagedKVState(**{
                f.name: (None if getattr(node, f.name) is None
                         else getattr(node, f.name)[i])
                for f in dc.fields(node)})
            layer.check_invariants(pins=pins)


@functools.partial(jax.jit, donate_argnums=(0,))
def _adopt_prompts(pool, temp, slot_ids, lengths):
    """Copy freshly prefilled (ring) K/V bytes into pool pages at the
    assigned slots — the admission hand-off. ``slot_ids`` (n,) int32,
    negative entries are padding rows of the fixed-width admission batch
    and are dropped. The ring holds exactly the quantized bytes decode
    will read, so adopted pages are bit-identical to having prefilled
    into the pool directly."""
    from repro.attention import PagedKVState

    def one(p, t):
        if isinstance(p, PagedKVState):
            for i in range(p.k.shape[0]):
                p = p.put_layer(p.at_layer(i).write_prompts(
                    t.k[i], t.v[i], lengths=lengths, slots=slot_ids), i)
        return p

    return jax.tree.map(one, pool, temp, is_leaf=_is_kv_state)


def _validate_serve_cfg(cfg, admission: str = "stall", chunk: int = 1):
    from repro import attention as ATT
    from repro.models.attention import make_spec
    kinds = {k for pat, _ in cfg.layer_groups for k in pat}
    if not kinds <= {"attn", "local", "swa"}:
        raise ValueError(
            f"continuous batching serves decoder-only attention stacks "
            f"(got block kinds {sorted(kinds)})")
    if not cfg.causal:
        raise ValueError("continuous batching needs causal attention")
    specs = [("paged decode", dict(q_len=1))]
    if admission == "chunked":
        # the mixed segment's ragged chunked-prefill call must be servable
        specs.append(("ragged chunked-prefill paged decode",
                      dict(q_len=chunk, ragged_q=True)))
    for kind in kinds:
        window = {"attn": 0, "local": cfg.local_window,
                  "swa": cfg.window}[kind]
        for what, kw in specs:
            spec = make_spec(cfg, mode="decode", causal=True, window=window,
                             layout="bhsd_paged", **kw)
            if not ATT.list_backends(spec):
                reasons = "; ".join(f"{n}: {r}" for n, r in
                                    ATT.backend_reasons(spec).items())
                raise ValueError(
                    f"no attention backend serves the {what} spec for "
                    f"{kind!r} blocks of {cfg.name!r} — {reasons}")


ADMISSIONS = ("chunked", "stall")


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def serve_continuous(params, cfg, requests, *, slots: int,
                     segment: int = 16, max_len: int | None = None,
                     page_size: int = 128, num_pages: int | None = None,
                     temperature: float = 0.0, key=None,
                     eos_id: int | None = None, pad_id: int = 0,
                     admission: str = "chunked", chunk_size: int = 32,
                     token_budget: int | None = None,
                     prefix_sharing: bool = False,
                     preemption: bool = False, faults=None,
                     straggler_factor: float = 2.0,
                     debug_invariants: bool | None = None,
                     audit=None, journal_dir: str | None = None,
                     snapshot_every: int = 0, resume: bool = False,
                     drain=None, drain_timeout: float | None = None,
                     aging_steps: int | None = None) -> ServeResult:
    """Serve an arrival trace with continuous batching over a paged pool.

    A fixed-slot batch (``slots`` wide) runs fused ``lax.scan`` segments
    of ``segment`` steps; between segments the host scheduler (1)
    releases the pages of every finished sequence back to the shared
    pool, (2) admits arrived requests into freed slots, and (3) reads
    back the segment's tokens. Virtual time = decode steps (request
    ``arrival`` is in steps); throughput is **sustained**: total
    generated tokens over the whole trace wall clock.

    ``admission`` selects how prompts enter the batch:

    - ``"chunked"`` (default) — admission only *enqueues* the prompt's
      token ids into the slot's ``ServeSlotState`` (one tiny state
      dispatch) and reserves pages; the prompt is then prefilled in
      ``chunk_size``-token chunks *inside* the fused segments, written
      page-native via ``append_chunk``, interleaved with decode steps
      under a decode-maximal per-step ``token_budget`` (default
      ``slots - 1 + chunk_size``: every decoding slot advances every
      step, the leftover budget feeds prompt chunks). Decode throughput
      never stops for a long prompt and the ring scratch + bytes-copy
      adoption of the stall path never runs.
    - ``"stall"`` — the PR-4 stop-the-world path, kept for A/B parity:
      admission runs one fixed-shape ragged prefill over a ring scratch,
      bytes-copies the K/V into pool pages (``_adopt_prompts``), and all
      decode slots wait. Its stop time is reported as
      ``ServeResult.prefill_stall_s``.

    Admission reserves each request's worst-case page need
    (``ceil((len + gen) / page_size)``, capped at the per-slot window) up
    front, so the on-device allocator can never be overdrawn mid-segment
    — the invariant ``tests/test_paged.py`` property-checks. ``audit``
    (testing hook) is called after every admission round with the live
    cache pytree, the slot→request map and the host pin ledger.

    ``prefix_sharing=True`` (chunked admission only) shares identical
    prompt prefixes across requests through the paged pool: as prompts
    prefill, every *full* page of prompt tokens is registered in a host
    ``PrefixIndex`` (chain hash of page-aligned token chunks → physical
    page) and pinned (+1 refcount) so it outlives its request; a later
    admission whose prompt walks the same chain *adopts* those pages
    (``PagedKVState.adopt_prefix``) instead of reserving and prefilling
    them — near-zero prefill cost for the shared tokens and a smaller
    reservation, so more concurrent requests fit the same arena. At
    least one prompt token always prefills (the sampled first token
    needs live logits), and only requests that cannot wrap their window
    (``len + gen <= capacity``) share or donate, so adopted pages are
    never overwritten in serving — copy-on-write in the append paths
    still guards the general case at the state level. Under page
    pressure the index evicts idle pinned pages (LRU, active adopters
    protected) before stalling the head of the queue.

    **Overload survival** (DESIGN.md §Overload survival):
    ``preemption=True`` (chunked admission only) lets admission make
    room for a higher-priority arrival when the pool or the slots are
    exhausted: victim slots — strictly lower ``ServeRequest.priority``
    only, lowest class first, then most reserved pages — are evicted in
    one ``preempt_rows`` dispatch, their pages return to the pool
    (pinned prefix pages decref, never free), and their requests
    re-enqueue carrying the prompt *plus every token generated so far*.
    The resumed request re-prefills that stream through ordinary chunked
    admission (near-free when its pages are still registered in the
    prefix index), its slot PRNG stream is restored from a snapshot
    taken at eviction, and its remaining budget shrinks by what it
    already emitted — so greedy *and* sampled outputs are bit-identical
    to never having been preempted. Only requests whose full stream fits
    the per-slot window (``len + gen <= capacity``) are preemptable.
    ``faults`` (a ``runtime.fault_tolerance.ServeFaultPlan``) injects
    seeded overload: forced slot kills (the same eviction/resume path,
    regardless of ``preemption``), phantom page-pressure spikes
    subtracted from the admission budget, and sleeps before segment
    dispatches that the segment watchdog (``StragglerWatchdog`` at
    ``straggler_factor`` x median, shared with the train driver) must
    flag — counted in ``ServeResult.straggler_segments``.

    Bit-exactness:
    a page's K/V bytes are a pure function of its tokens and
    page-aligned position, and chunk boundaries don't change the fused
    kernels' arithmetic, so shared-path tokens are bit-identical to the
    unshared path (same conditions as chunked ≡ solo parity:
    ``page_size`` = fused ``block_kv`` 128 + fused-family prefill).
    ``debug_invariants`` (or env ``ITA_PAGED_DEBUG=1``) host-checks the
    allocator partition + refcount invariants after every admission
    round.

    **Crash safety** (DESIGN.md §Crash recovery): ``journal_dir``
    enables a write-ahead request journal (``runtime.journal``) —
    admissions, per-request emitted-token high-water marks and PRNG key
    snapshots flushed at every segment boundary, completions — plus,
    with ``snapshot_every=N``, a ``Checkpointer`` snapshot of the paged
    pool + prefix index every N segments. ``resume=True`` replays the
    journal first: completed requests (matched by
    ``ServeRequest.request_id``) return as replayed
    ``CompletedRequest``s without serving twice, and every unfinished
    request is rebuilt as a pending ``prompt ++ emitted`` stream with
    its journaled key snapshot and re-admitted through the ordinary
    preemption-resume path — greedy *and* sampled tokens bit-identical
    to a never-crashed serve. A usable snapshot (checksums, version and
    geometry verified; post-restore allocator invariants checked) warm-
    starts the prefix index so shared prompts skip re-prefilling; any
    snapshot problem degrades to a cold start from the journal alone —
    never to wrong tokens. ``drain`` (a ``journal.ServeDrain``) stops
    admission and finishes in-flight work — or, past ``drain_timeout``
    seconds, stops at the next boundary with progress journaled — then
    takes a final snapshot. ``aging_steps`` turns on starvation aging:
    a waiting request's effective class grows by one every
    ``aging_steps`` virtual steps, capped one above the trace's highest
    class, giving the low class a *bounded* worst-case admission delay
    (``class_summary()['aging_bound_steps']``).

    Requests decode greedily (or with temperature sampling when ``key``
    is given) until ``gen`` tokens or ``eos_id``. Greedy serving is
    bit-identical to generating each request alone under **both**
    admission modes (chunked-prefill bit-exactness needs the solo prefill
    on the same KV tile schedule: ``page_size`` equal to the fused
    prefill ``block_kv``, 128, and a fused-kernel prefill backend).
    Sampled serving draws each request's tokens from its own PRNG stream
    (``fold_in(key, request_index)``), so outputs are independent of
    admission interleaving and co-scheduled traffic.
    Returns ``ServeResult`` with per-request latency/TTFT and page-pool
    utilization samples.

    **Tracing**: the loop writes host spans into the JAX profiler's own
    trace (``jax.profiler.TraceAnnotation``), on the clock it puts the
    device's ops on. Each admission round is one ``serve.round`` (args
    ``segment``, the index of the segment it dispatches, and ``step``);
    a round that dispatches a segment holds, in order:

    - ``serve.schedule``: arrivals, candidate order, prefix lookup, LRU
      eviction, victim choice (host only);
    - ``serve.pool``: the round's preempt, release, unpin, adopt and
      admit dispatches;
    - ``serve.dispatch`` (args ``segment``, ``step``, ``mixed``: the
      segment's chunk-wide steps, ``steps``: its length): the segment
      call;
    - ``serve.wait``: the host waiting for the device to finish it;
    - ``serve.readback``: the one ``device_get`` and the per-slot token
      bookkeeping;
    - ``serve.register`` (with ``prefix_sharing``): page registration
      and pins; ``serve.journal`` (with ``journal_dir``): progress,
      flush and snapshot.

    A round that finds nothing to run holds ``serve.schedule`` and
    ``serve.pool`` only. On the device, ``make_serve_segment``'s scopes
    and the kernels' ``pallas_call`` names mark the work in each op's
    ``op_name``. To trace a serve, run it under
    ``with jax.profiler.trace(trace_dir):`` and read the ``.xplane.pb``
    written under ``trace_dir`` with ``jax.profiler.ProfileData``: the
    spans are events of the ``/host:`` planes, their args the events'
    stats; the ops are the ``XLA Ops`` line of each device plane.
    """
    from repro.launch.steps import ServeSlotState, aged_priority, \
        fold_keys, sample_token_rows
    from repro.models import init_caches

    if admission not in ADMISSIONS:
        raise ValueError(f"admission={admission!r} not in {ADMISSIONS}")
    if (preemption or faults is not None) and admission != "chunked":
        raise ValueError(
            "preemption / fault injection require admission='chunked' "
            "(victims resume through chunked re-prefill of their "
            "prompt + generated prefix)")
    _validate_serve_cfg(cfg, admission=admission,
                        chunk=max(1, chunk_size))
    requests = list(requests)
    if not requests:
        return ServeResult([], 0.0, 0, 0, 0, [])
    injector = None
    if faults is not None:
        from repro.runtime.fault_tolerance import (ServeFaultInjector,
                                                   SimulatedCrash)
        injector = ServeFaultInjector(faults)
    from repro.runtime.watchdog import StragglerWatchdog
    watchdog = StragglerWatchdog(factor=straggler_factor)
    may_preempt = preemption or (injector is not None
                                 and injector.plan.may_kill)
    prompt_pad = max(int(np.asarray(r.prompt).size) for r in requests)
    longest = max(int(np.asarray(r.prompt).size) + r.gen for r in requests)
    max_len = max_len or longest
    sample = temperature > 0.0 and key is not None
    temp_arr = jnp.asarray(temperature if sample else 1.0, jnp.float32)
    base_key = jax.random.PRNGKey(0) if key is None else key

    caches = init_caches(cfg, slots, max_len=max_len, paged=True,
                         page_size=page_size, num_pages=num_pages)
    geo = _first_paged(caches)
    pool_pages = geo.k.shape[1] - 1                # minus parking
    pages_per_seq = geo.page_table.shape[2]
    capacity = pages_per_seq * page_size

    # pending streams: what admission will actually prefill per request —
    # the original prompt, or (after a preemption) prompt + generated
    # prefix with the remaining token budget. Page need is invariant
    # across resumes (plen' + gen' == plen + gen), so only requests whose
    # whole stream fits the per-slot window are resumable, and the prompt
    # buffer must hold up to plen + gen - 1 tokens for them.
    pending = {i: (np.asarray(r.prompt, np.int32).reshape(-1), int(r.gen))
               for i, r in enumerate(requests)}
    prio_req = [int(getattr(r, "priority", 0)) for r in requests]
    resumable = [int(np.asarray(r.prompt).size) + r.gen <= capacity
                 for r in requests]
    max_class = max(prio_req, default=0)
    if aging_steps is not None and aging_steps <= 0:
        raise ValueError(f"aging_steps={aging_steps} must be positive")

    def eff_prio(i, at_step):
        return aged_priority(prio_req[i],
                             at_step - requests[i].arrival,
                             aging_steps, max_class)

    # -- write-ahead journal + replay (DESIGN.md §Crash recovery) --------
    journal = None
    fingerprint = None
    seed_emitted = {}                  # index -> journaled emitted tokens
    seed_keys = {}                     # index -> journaled PRNG snapshot
    replayed_completed = []            # CompletedRequest rebuilt, not served
    done_replayed = set()
    replayed_tokens = 0
    recovered = False
    recovery_s = 0.0
    rids = [r.request_id if r.request_id is not None else f"req-{i:06d}"
            for i, r in enumerate(requests)]
    if len(set(rids)) != len(rids):
        dup = sorted({r for r in rids if rids.count(r) > 1})
        raise ValueError(f"duplicate request_id(s): {dup} — journal "
                         f"dedupe needs ids unique per trace")
    if journal_dir is not None:
        from repro.runtime.journal import (ServeJournal, check_fingerprint,
                                           prompt_digest)
        t_rec = time.perf_counter()
        os.makedirs(journal_dir, exist_ok=True)
        jpath = os.path.join(journal_dir, "journal.jsonl")
        fingerprint = {
            "journal_version": 1, "arch": cfg.name,
            "page_size": int(page_size), "max_len": int(max_len),
            "temperature": float(temperature), "sample": bool(sample),
            "eos_id": eos_id, "pad_id": int(pad_id),
            "key": ([int(x) for x in
                     np.asarray(base_key).reshape(-1).tolist()]
                    if sample else None),
        }
        jreplay = None
        if resume and os.path.exists(jpath) and os.path.getsize(jpath):
            jreplay = ServeJournal.replay(jpath)
            if jreplay.header is None:
                raise ValueError(
                    f"{jpath}: no intact header record — not a serve "
                    f"journal (or its very first write was torn)")
            check_fingerprint(jreplay.header["fingerprint"], fingerprint)
            recovered = True
        journal = ServeJournal(jpath, fingerprint=fingerprint,
                               fresh=jreplay is None)
        for i, r in enumerate(requests):
            digest = prompt_digest(r.prompt)
            sub = jreplay.submits.get(rids[i]) if jreplay else None
            if sub is not None:
                # id dedupe: same id must mean the same request — a
                # digest/shape mismatch is id reuse, not a resume
                if (sub["digest"] != digest or sub["gen"] != int(r.gen)
                        or sub["i"] != i):
                    raise ValueError(
                        f"request_id {rids[i]!r} reused for a different "
                        f"request (journal has index {sub['i']}, gen "
                        f"{sub['gen']}, digest {sub['digest']})")
            else:
                journal.append({"t": "submit", "rid": rids[i], "i": i,
                                "digest": digest, "gen": int(r.gen),
                                "arrival": int(r.arrival),
                                "priority": prio_req[i]})
            if jreplay is None:
                continue
            toks = [int(x) for x in jreplay.emitted.get(rids[i], [])]
            comp = jreplay.completes.get(rids[i])
            # a torn flush can persist the complete record but lose the
            # same boundary's progress lines — so the journaled *token
            # count*, not the record's existence, decides: short streams
            # fall to the partial-resume path and regenerate the tail
            needed = int(comp["n"]) if comp is not None else int(r.gen)
            if len(toks) >= needed:
                # finished before the crash: replay, never serve twice
                comp = comp or {}
                replayed_tokens += needed
                replayed_completed.append(CompletedRequest(
                    index=i, arrival=int(r.arrival),
                    admitted_step=int(comp.get("admitted_step", 0)),
                    finished_step=int(comp.get("finished_step", 0)),
                    arrived_s=float(comp.get("arrived_s", 0.0)),
                    finished_s=float(comp.get("finished_s", 0.0)),
                    first_token_s=float(comp.get("first_token_s", 0.0)),
                    tokens=np.asarray(toks[:needed], np.int32),
                    priority=prio_req[i],
                    preemptions=int(comp.get("preemptions", 0)),
                    replayed=True))
                done_replayed.add(i)
            elif toks and resumable[i] \
                    and (not sample or rids[i] in jreplay.keys):
                # unfinished: resume exactly as if preempted at the last
                # journaled boundary — pending = prompt ++ emitted with
                # the leftover budget, PRNG stream from the snapshot
                prompt0 = np.asarray(r.prompt, np.int32).reshape(-1)
                pending[i] = (
                    np.concatenate([prompt0,
                                    np.asarray(toks, np.int32)]),
                    int(r.gen) - len(toks))
                seed_emitted[i] = toks
                replayed_tokens += len(toks)
                if sample:
                    seed_keys[i] = np.asarray(jreplay.keys[rids[i]],
                                              np.uint32)
            # else: nothing journaled (or stream not resumable) — the
            # request restarts from its original prompt; its fold_in
            # PRNG stream restarts too, so tokens still come out
            # bit-identical, just re-generated
        journal.flush()
        recovery_s = time.perf_counter() - t_rec
    if may_preempt or seed_emitted:
        prompt_pad = max(
            int(np.asarray(r.prompt).size) + (r.gen - 1 if resumable[i]
                                              else 0)
            for i, r in enumerate(requests))

    index = None
    if prefix_sharing:
        from repro.attention import PagedKVState, PrefixIndex
        if admission != "chunked":
            raise ValueError(
                "prefix_sharing requires admission='chunked' (stall-mode "
                "prefill bypasses the page-native write path)")
        geos = {(n.k.shape[1], n.k.shape[3], n.page_table.shape[2])
                for n in jax.tree.leaves(caches, is_leaf=_is_kv_state)
                if isinstance(n, PagedKVState)}
        if len(geos) > 1:
            raise ValueError(
                f"prefix_sharing needs one uniform pool geometry across "
                f"all attention layers (one physical page id must mean "
                f"the same logical page everywhere), got {sorted(geos)} — "
                f"window-capped layer groups (local/swa mixed with full "
                f"attention) break the layer-lockstep guarantee")
        index = PrefixIndex(page_size)
    debug = debug_invariants if debug_invariants is not None \
        else bool(os.environ.get("ITA_PAGED_DEBUG"))
    chunk = max(1, min(chunk_size, capacity))
    budget = token_budget if token_budget is not None \
        else slots - 1 + chunk
    if admission == "chunked" and budget < slots:
        raise ValueError(
            f"token_budget={budget} < slots={slots}: a decode-maximal "
            f"step must cover every decoding slot plus at least one "
            f"prefill token")
    prefill, _ = _steps(cfg)
    seg_decode = _serve_segment_fn(cfg, segment, sample, eos_id, pad_id)

    def seg_mixed(k):
        # two-phase segment: `k` chunk-wide mixed steps, sized to the
        # prompt chunks actually outstanding, then 1-token decode steps
        # for the rest — one dispatch, one host round-trip per `segment`
        # steps, chunk-wide q width paid only where prefill happens
        return _serve_segment_fn(
            cfg, segment, sample, eos_id, pad_id, chunk, budget, k)

    def pages_for(req):
        n = int(np.asarray(req.prompt).size) + req.gen
        return min(-(-n // page_size), pages_per_seq)

    for idx, r in enumerate(requests):
        plen = int(np.asarray(r.prompt).size)
        if plen > capacity:
            raise ValueError(
                f"request {idx}: prompt length {plen} exceeds the per-slot "
                f"window {capacity}; raise max_len")
        if pages_for(r) > pool_pages:
            raise ValueError(
                f"request {idx} needs {pages_for(r)} pages but the pool "
                f"has {pool_pages}; raise num_pages")

    # stall mode: reusable ring scratch for admission prefills (fully
    # overwritten by every ragged prefill — allocated once, not per round)
    scratch = init_caches(cfg, slots, max_len=prompt_pad) \
        if admission == "stall" else None

    # scheduler state (host)
    order = sorted(range(len(requests)), key=lambda i: requests[i].arrival)
    queue = [i for i in order if i not in done_replayed]
    slot_req = [None] * slots                      # request index per slot
    reserved = [0] * slots                         # pages reserved per slot
    plen_host = [0] * slots                        # prompt length per slot
    cursor_host = [0] * slots                      # host mirror of cursor
    prefilling = [False] * slots                   # host mirror of phase
    slot_prompt = [None] * slots                   # admitted pending stream
    arrived_wall = {}
    first_tok = {}
    emitted = {i: list(seed_emitted.get(i, []))
               for i in range(len(requests))}
    jhw = {i: len(emitted[i]) for i in emitted}    # journaled high water
    admitted_step = {}
    preempt_count = {}                             # request -> evictions
    resume_keys = dict(seed_keys)                  # request -> PRNG snapshot
    n_preempts = 0
    completed = list(replayed_completed)
    page_util = []
    drain_since = None                             # wall time drain began
    snapshot_bytes = 0

    # prefix-sharing host state (all empty/zero when index is None)
    pins = {}                                      # page -> 1 (index pins)
    slot_shared = [[] for _ in range(slots)]       # adopted pages per slot
    slot_shareable = [False] * slots               # row may donate pages
    reg_done = [0] * slots                         # prompt pages registered
    prefill_tokens = 0
    shared_tokens = 0
    prefix_hits = 0

    # -- snapshot/restore of the pool + prefix index (§Crash recovery) ---
    restored_from_snapshot = False
    snap_ckpt = None
    snap_ord = 0
    snap_geo = {"arch": cfg.name, "slots": int(slots),
                "page_size": int(page_size),
                "num_pages": int(geo.k.shape[1]),
                "pages_per_seq": int(pages_per_seq)}
    if journal is not None and snapshot_every > 0:
        from repro.checkpoint.checkpointing import (Checkpointer,
                                                    CheckpointCorrupt)
        snap_ckpt = Checkpointer(os.path.join(journal_dir, "snapshots"),
                                 keep=2, prefix="serve")
        snap_ord = snap_ckpt.latest_step() or 0
    if recovered and snap_ckpt is not None and index is not None:
        t_rec = time.perf_counter()
        try:
            if snap_ckpt.latest_step() is None:
                raise FileNotFoundError("no serve snapshot on disk")
            loaded, snap_meta = snap_ckpt.restore(caches)
            extra = snap_meta["extra"]
            if extra.get("geometry") != snap_geo:
                raise CheckpointCorrupt(
                    f"snapshot geometry {extra.get('geometry')} != this "
                    f"serve's {snap_geo}")
            exp_shapes = [list(l.shape) for l in jax.tree.leaves(caches)]
            if snap_meta["shapes"] != exp_shapes:
                raise CheckpointCorrupt("snapshot leaf shapes changed")
            index.load_state_dict(extra["index"])
            new_pins = {int(p): int(c) for p, c in extra["pins"].items()}
            # the snapshot was taken mid-serve with rows holding pages;
            # none of those rows survive the crash, so release every row
            # — refcounts drop to exactly the index pins — then host-
            # check the allocator invariants before trusting any of it
            loaded = _release_slots(loaded, jnp.ones((slots,), bool))
            _check_paged_invariants(loaded, pins=dict(new_pins))
            caches = loaded
            pins = new_pins
            restored_from_snapshot = True
        except (CheckpointCorrupt, FileNotFoundError, AssertionError,
                KeyError, ValueError) as e:
            # graceful degradation: a missing/corrupt/mismatched
            # snapshot can cost re-prefill work, never correctness —
            # cold-start the pool and index, recover from the journal
            if not isinstance(e, FileNotFoundError):
                print(f"[serve] snapshot unusable ({e}); cold start "
                      f"from journal", flush=True)
            caches = init_caches(cfg, slots, max_len=max_len, paged=True,
                                 page_size=page_size, num_pages=num_pages)
            index = PrefixIndex(page_size)
            pins = {}
        recovery_s += time.perf_counter() - t_rec

    def save_snapshot():
        nonlocal snap_ord, snapshot_bytes
        snap_ord += 1
        snap_ckpt.save(snap_ord, caches, extra={
            "kind": "serve", "geometry": snap_geo,
            "fingerprint": fingerprint,
            "index": index.state_dict() if index is not None else None,
            "pins": {str(p): int(c) for p, c in pins.items()}})
        snapshot_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(caches))

    state = ServeSlotState.init(slots, prompt_pad, base_key)

    step = 0
    segments = 0
    rounds = 0
    stall_s = 0.0
    straggler_segs = 0
    t0 = time.perf_counter()

    def finish(slot, now_s):
        i = slot_req[slot]
        completed.append(CompletedRequest(
            index=i, arrival=requests[i].arrival,
            admitted_step=admitted_step[i], finished_step=step,
            arrived_s=arrived_wall[i], finished_s=now_s,
            first_token_s=first_tok.get(i, now_s),
            tokens=np.asarray(emitted[i][:requests[i].gen], np.int32),
            priority=prio_req[i],
            preemptions=preempt_count.get(i, 0)))
        if journal is not None:
            # "n" is the authoritative finished-token count: replay
            # trusts it over the record's mere existence (a torn flush
            # can drop this boundary's progress lines but keep this)
            journal.append({
                "t": "complete", "rid": rids[i],
                "n": len(emitted[i][:requests[i].gen]),
                "admitted_step": admitted_step[i], "finished_step": step,
                "arrival": int(requests[i].arrival),
                "arrived_s": arrived_wall[i], "finished_s": now_s,
                "first_token_s": first_tok.get(i, now_s),
                "priority": prio_req[i],
                "preemptions": preempt_count.get(i, 0)})
        slot_req[slot] = None
        reserved[slot] = 0
        prefilling[slot] = False
        slot_prompt[slot] = None
        slot_shared[slot] = []
        slot_shareable[slot] = False
        reg_done[slot] = 0

    to_release = []                                # slots freed, pages held

    def preempt_slot(slot):
        """Evict ``slot``'s request (host side): snapshot its PRNG
        stream, rebuild its pending entry as prompt + generated prefix
        with the leftover token budget, clear the slot's host mirrors and
        re-enqueue. The device-row clear (``preempt_rows``) and the page
        release are batched by the caller — one dispatch per round."""
        nonlocal n_preempts
        i = slot_req[slot]
        if sample:
            # the stream already advanced once per emitted token; resuming
            # from this snapshot is what keeps sampled outputs
            # bit-identical to an unpreempted serve (eager device_get:
            # a fault kill may re-admit this request in the same round)
            resume_keys[i] = np.asarray(jax.device_get(state.keys[slot]))
        g = len(emitted[i])
        prompt0 = np.asarray(requests[i].prompt, np.int32).reshape(-1)
        pending[i] = (
            np.concatenate([prompt0, np.asarray(emitted[i][:g], np.int32)]),
            requests[i].gen - g)
        preempt_count[i] = preempt_count.get(i, 0) + 1
        n_preempts += 1
        slot_req[slot] = None
        reserved[slot] = 0
        prefilling[slot] = False
        cursor_host[slot] = 0
        plen_host[slot] = 0
        slot_prompt[slot] = None
        slot_shared[slot] = []
        slot_shareable[slot] = False
        reg_done[slot] = 0
        queue.append(i)
        queue.sort(key=lambda j: (requests[j].arrival, j))
        to_release.append(slot)

    def _journal_progress(keys_np=None):
        """Journal every request's emitted-token delta since its last
        journaled high-water mark and, when sampling, its post-draw PRNG
        snapshot (from the segment readback for live slots, from the
        eviction snapshot for preempted ones) — all batched into ONE
        progress record per boundary, so the journal's per-record cost
        doesn't scale with slot count. The caller flushes — durability
        is per segment boundary, not per token."""
        slot_of = {slot_req[s]: s for s in range(slots)
                   if slot_req[s] is not None}
        deltas, keys = {}, {}
        for i, toks in emitted.items():
            if len(toks) <= jhw[i]:
                continue
            deltas[rids[i]] = [int(x) for x in toks[jhw[i]:]]
            if sample:
                if keys_np is not None and i in slot_of:
                    keys[rids[i]] = [int(x) for x in keys_np[slot_of[i]]]
                elif i in resume_keys:
                    keys[rids[i]] = [int(x) for x in
                                     np.asarray(resume_keys[i]).reshape(-1)]
            jhw[i] = len(toks)
        if deltas:
            rec = {"t": "progress", "d": deltas}
            if keys:
                rec["k"] = keys
            journal.append(rec)

    while queue or any(s is not None for s in slot_req):
        now_s = time.perf_counter() - t0
        if injector is not None and injector.want_crash(step):
            # process death at an admission-round boundary: everything
            # through the previous segment's flush is durable, all
            # in-memory state is abandoned (no flush, no cleanup).
            # In-flight async IO (journal group commit, snapshot write)
            # is settled first so the in-process simulation is
            # deterministic and the restarted serve never races a
            # "dead" writer thread — a real death mid-write leaves a
            # torn journal tail / a .tmp snapshot dir, both of which
            # replay and tmp+rename atomicity already make equivalent
            # to the write never starting
            if journal is not None:
                journal.wait()
            if snap_ckpt is not None:
                snap_ckpt.wait()
            raise SimulatedCrash(step, "round-boundary")
        draining = drain is not None and drain.poll(step)
        if draining:
            if drain_since is None:
                drain_since = time.perf_counter()
            if all(s is None for s in slot_req):
                break                  # nothing in flight: drain done
            if drain_timeout is not None and \
                    time.perf_counter() - drain_since >= drain_timeout:
                # timeout: stop here — in-flight progress is journaled
                # through the last boundary, a resume picks it up
                break
        with jax.profiler.TraceAnnotation("serve.round",
                                          segment=segments, step=step):
            with jax.profiler.TraceAnnotation("serve.schedule"):
                for i in queue:
                    if requests[i].arrival <= step:
                        arrived_wall.setdefault(i, now_s)
                victims_round = []
                if injector is not None and injector.want_kill(step):
                    # forced slot kill: seeded pick among live resumable slots,
                    # evicted through the exact preemption recovery path (and a
                    # candidate for re-admission this very round)
                    live = [s for s in range(slots)
                            if slot_req[s] is not None
                            and resumable[slot_req[s]]]
                    if live:
                        s = live[int(injector.rng.integers(len(live)))]
                        preempt_slot(s)
                        victims_round.append(s)
                # -- admission: arrived requests into free, page-backed
                # slots
                # budget: reservations + index pins both count against the
                # pool. A pinned page inside an active donor's reservation is
                # counted twice — conservative, never overdrawn; the win comes
                # from adopters reserving `need - shared` pages. Fault-injected
                # pressure spikes subtract phantom pages for one round.
                free_slots = [s for s in range(slots) if slot_req[s] is None]
                phantom = injector.phantom_pages(step) \
                    if injector is not None else 0
                page_budget = pool_pages - sum(reserved) - len(pins) - phantom
                adm = []
                adm_shared = {}                         # slot -> adopted pages
                evict_batch = []
                # candidate order = admission order: effective SLO class first
                # (aging-adjusted, so a starved low-class request eventually
                # outranks fresh high-class arrivals), then arrival, then trace
                # position (a snapshot — this round's victims re-enter the
                # queue but only become candidates next round, so preemption
                # can never livelock within a round). Draining: admit nothing.
                cand = [] if draining else sorted(
                    (i for i in queue if requests[i].arrival <= step),
                    key=lambda j: (-eff_prio(j, step), requests[j].arrival, j))
                for i in cand:
                    if not free_slots and not preemption:
                        break
                    prompt_i, gen_i = pending[i]
                    plen_i = int(prompt_i.size)
                    sh_pages = []
                    if index is not None and plen_i + gen_i <= capacity:
                        # cap at plen-1: >= 1 prompt token must prefill live
                        # (the first sampled token needs this request's
                        # last-position logits); no sharing for window-wrapping
                        # requests (their COW pops would need headroom the
                        # reservation lacks)
                        sh_pages = index.lookup(prompt_i,
                                                max_tokens=plen_i - 1)
                    need = min(-(-(plen_i + gen_i) // page_size),
                               pages_per_seq) - len(sh_pages)
                    if need > page_budget and index is not None and len(index):
                        # evict idle pinned prefixes (LRU) before preempting or
                        # stalling the head; pages adopted by active slots (or
                        # about to be, by this request) keep their pin
                        protected = {p for lst in slot_shared for p in lst}
                        protected |= set(sh_pages)
                        evicted = index.evict_lru(need - page_budget,
                                                  protected)
                        for p in evicted:
                            pins.pop(p, None)
                        evict_batch.extend(evicted)
                        page_budget += len(evicted)
                    if preemption and (need > page_budget or not free_slots):
                        # page-pressure preemption: evict strictly-lower-class
                        # victims — lowest class first, then most reserved
                        # pages — until this candidate fits. All-or-nothing: a
                        # candidate that still wouldn't fit evicts nobody.
                        cast = sorted(
                            (s for s in range(slots)
                             if slot_req[s] is not None
                             and eff_prio(slot_req[s], step)
                             < eff_prio(i, step)
                             and resumable[slot_req[s]]),
                            key=lambda s: (eff_prio(slot_req[s], step),
                                           -reserved[s], s))
                        gain, picked = 0, []
                        for s in cast:
                            if need <= page_budget + gain \
                                    and (free_slots or picked):
                                break
                            picked.append(s)
                            gain += reserved[s]
                        if need <= page_budget + gain \
                                and (free_slots or picked):
                            for s in picked:
                                preempt_slot(s)            # reserved[s] -> 0
                                victims_round.append(s)
                                free_slots.append(s)
                            page_budget += gain
                    if not free_slots or need > page_budget:
                        break                        # head-of-line: keep order
                    slot = free_slots.pop(0)
                    queue.remove(i)
                    slot_req[slot] = i
                    reserved[slot] = need
                    page_budget -= need
                    admitted_step.setdefault(i, step)   # first admission: TTFT
                    adm.append((slot, i))
                    adm_shared[slot] = sh_pages
                    slot_prompt[slot] = prompt_i
                    slot_shared[slot] = list(sh_pages)
                    slot_shareable[slot] = (index is not None
                                            and plen_i + gen_i <= capacity)
                    reg_done[slot] = len(sh_pages)  # adopted = already indexed
                    sh_toks = len(sh_pages) * page_size
                    prefill_tokens += plen_i - sh_toks
                    shared_tokens += sh_toks
                    prefix_hits += bool(sh_pages)
            with jax.profiler.TraceAnnotation("serve.pool"):
                if victims_round:
                    # one-dispatch device-row clear: the victims' done flag
                    # raises before any release/adopt/admit dispatch and before
                    # the next segment, so the scan never touches freed pages
                    vmask = np.zeros((slots,), bool)
                    vmask[victims_round] = True
                    state = _preempt_rows(state, jnp.asarray(vmask))
                if adm and to_release:
                    # deferred page hand-back: freed slots accumulate across
                    # segment boundaries and release in one dispatch right
                    # before the pages are actually needed (host `reserved`
                    # accounting keeps the budget exact in between)
                    mask = np.zeros((slots,), bool)
                    mask[to_release] = True
                    caches = _release_slots(caches, jnp.asarray(mask))
                    to_release = []
                if evict_batch:
                    # unpin evicted index entries (dispatched even when the
                    # head still didn't fit, so the host pin ledger and the
                    # device refcounts never diverge); pages reaching refcount
                    # zero are free the moment this lands
                    pad = np.full((slots * pages_per_seq,), -1, np.int32)
                    pad[:len(evict_batch)] = evict_batch
                    caches = _unpin_pages(caches, jnp.asarray(pad))
                if adm:
                    rounds += 1
                    prompts = np.zeros((slots, prompt_pad), np.int32)
                    lengths = np.ones((slots,), np.int32)
                    gens = np.zeros((slots,), np.int32)
                    prios = np.zeros((slots,), np.int32)
                    slot_ids = np.full((slots,), -1, np.int32)
                    row_req = np.zeros((slots,), np.int32)
                    for row, (slot, i) in enumerate(adm):
                        p, g = pending[i]
                        prompts[row, :p.size] = p
                        lengths[row] = p.size
                        gens[row] = g
                        prios[row] = eff_prio(i, step)
                        slot_ids[row] = slot
                        row_req[row] = i
                        plen_host[slot] = p.size
                    req_keys = fold_keys(base_key, jnp.asarray(row_req))
                    if resume_keys:
                        # resumed rows restore the PRNG snapshot taken at their
                        # eviction instead of restarting the fold_in stream —
                        # the draws continue exactly where the victim left off
                        rk = np.asarray(req_keys).copy()
                        for row, (slot, i) in enumerate(adm):
                            if i in resume_keys:
                                rk[row] = resume_keys.pop(i)
                        req_keys = jnp.asarray(rk)
                    lengths_d = jnp.asarray(lengths)
                    slot_ids_d = jnp.asarray(slot_ids)
                    if admission == "chunked":
                        shared_rows = np.zeros((slots,), np.int32)
                        if index is not None:
                            adopt_pages = np.zeros((slots, pages_per_seq),
                                                   np.int32)
                            adopt_n = np.zeros((slots,), np.int32)
                            for row, (slot, i) in enumerate(adm):
                                sh = adm_shared.get(slot, [])
                                adopt_pages[row, :len(sh)] = sh
                                adopt_n[row] = len(sh)
                                shared_rows[row] = len(sh) * page_size
                            if adopt_n.any():
                                # point the new slots' leading table entries at
                                # the shared pages (+1 refcount, every layer)
                                caches = _adopt_prefix_slots(
                                    caches, slot_ids_d,
                                    jnp.asarray(adopt_pages),
                                    jnp.asarray(adopt_n),
                                    jnp.asarray(shared_rows))
                        # enqueue-only admission: prompt ids + phase state; the
                        # segments do the prefill, page-native, starting at the
                        # first unshared token
                        state = _admit_chunked(state, slot_ids_d,
                                               jnp.asarray(prompts), lengths_d,
                                               jnp.asarray(gens), req_keys,
                                               jnp.asarray(shared_rows),
                                               jnp.asarray(prios))
                        for row, (slot, i) in enumerate(adm):
                            prefilling[slot] = True
                            cursor_host[slot] = int(shared_rows[row])
                    else:
                        # stall admission: stop-the-world ragged prefill over
                        # the ring scratch, bytes-copied into pool pages (no
                        # sharing: every prompt token forwards)
                        t_stall = time.perf_counter()
                        logits, scratch = prefill(params, jnp.asarray(prompts),
                                                  scratch, None, lengths_d)
                        tok0, req_keys = sample_token_rows(
                            logits, req_keys, temp_arr, sample=sample)
                        caches = _adopt_prompts(caches, scratch, slot_ids_d,
                                                lengths_d)
                        tok0_np = np.asarray(tok0)
                        new_done = np.zeros((slots,), bool)
                        new_rem = np.zeros((slots,), np.int32)
                        now_s = time.perf_counter() - t0
                        for row, (slot, i) in enumerate(adm):
                            t0_tok = int(tok0_np[row, 0])
                            emitted[i].append(t0_tok)
                            first_tok.setdefault(i, now_s)
                            new_rem[row] = requests[i].gen - 1
                            new_done[row] = (requests[i].gen <= 1
                                             or (eos_id is not None
                                                 and t0_tok == eos_id))
                        state = _admit_stall(
                            state, slot_ids_d, lengths_d, tok0,
                            jnp.asarray(new_done), jnp.asarray(new_rem),
                            req_keys, jnp.asarray(prios))
                        jax.block_until_ready(state.tok)
                        stall_s += time.perf_counter() - t_stall
                    if audit is not None:
                        audit(caches, list(slot_req), dict(pins))
                    if debug:
                        _check_paged_invariants(caches, pins=dict(pins))
                if admission == "stall" and adm:
                    # freshly admitted gen-1/EOS requests finish without
                    # decoding
                    just_done = np.asarray(state.done)
                    fin = [s for s in range(slots)
                           if slot_req[s] is not None and just_done[s]]
                    if fin:
                        now_s = time.perf_counter() - t0
                        for s in fin:
                            finish(s, now_s)
                        to_release.extend(fin)
                        continue
            if all(s is None for s in slot_req):
                if not queue:
                    break
                step += segment                      # idle: nothing admittable
                continue

            # -- fused segment: mixed while any slot is mid-prompt (sized to
            # the chunks actually left), pure decode otherwise — decode-only
            # phases never pay chunk-wide q width
            t_seg = time.perf_counter()
            if injector is not None:
                pause = injector.straggle(step)
                if pause > 0.0:
                    time.sleep(pause)                  # injected straggler
            if admission == "chunked" and any(prefilling):
                # steps of mixed phase: bounded below by the largest single
                # prompt (one chunk per slot per step) and by total prefill
                # work over the per-step prefill token capacity (budget minus
                # the decoding slots it must keep fed)
                left = [plen_host[s] - cursor_host[s]
                        for s in range(slots) if prefilling[s]]
                n_dec = sum(1 for s in range(slots)
                            if slot_req[s] is not None and not prefilling[s])
                per_step = max(budget - n_dec, 1)
                need = max(-(-max(left) // chunk),
                           -(-sum(left) // per_step))
                # rounded up to a power of two to bound compilation count
                k = min(segment, _next_pow2(max(need, 1)))
                fn = seg_mixed(k)
            else:
                k, fn = 0, seg_decode
            with jax.profiler.TraceAnnotation("serve.dispatch",
                                              segment=segments, step=step,
                                              mixed=k, steps=segment):
                toks, emits, _, state, caches, _ = fn(params, state, caches,
                                                      temp_arr)
            segments += 1
            step += segment
            # pool utilization from the host-side reservation ledger (exact
            # upper bound on device-held pages; no extra device sync),
            # sampled while the segment's occupants still hold their pages
            page_util.append((step, sum(reserved) / max(pool_pages, 1)))
            keys_np = None
            out = (toks, emits, state.done, state.cursor)
            if journal is not None and sample:
                out += (state.keys,)
            with jax.profiler.TraceAnnotation("serve.wait"):
                jax.block_until_ready(out)
            with jax.profiler.TraceAnnotation("serve.readback"):
                if journal is not None and sample:
                    toks_np, emits_np, done_np, cursor_np, keys_np = \
                        jax.device_get(out)                    # one sync
                else:
                    toks_np, emits_np, done_np, cursor_np = \
                        jax.device_get(out)                    # one sync
                if injector is not None and injector.want_crash_after(step):
                    # mid-segment death: the device produced this segment's
                    # tokens but the flush below never runs — the torn
                    # window. Recovery resumes from the *previous* boundary
                    # and must regenerate the lost tokens bit-identically
                    if journal is not None:
                        journal.wait()
                    if snap_ckpt is not None:
                        snap_ckpt.wait()
                    raise SimulatedCrash(step, "mid-segment")
                straggler_segs += watchdog.observe(
                    time.perf_counter() - t_seg).straggler
                now_s = time.perf_counter() - t0
                for s in range(slots):
                    if slot_req[s] is None:
                        continue
                    i = slot_req[s]
                    row = toks_np[s][emits_np[s]].tolist()
                    if row:
                        first_tok.setdefault(i, now_s)
                        emitted[i].extend(row)
                    cursor_host[s] = int(cursor_np[s])
                    prefilling[s] = cursor_host[s] < plen_host[s]
            if index is not None:
                with jax.profiler.TraceAnnotation("serve.register"):
                    # register every freshly completed *full* page of prompt
                    # tokens (bytes final: no-wrap donors never rewrite them)
                    # so later arrivals can adopt it; runs before the
                    # finish/release bookkeeping so a request that just
                    # completed still donates. One small device_get of layer
                    # 0's page tables serves every layer — the pools are in
                    # lockstep.
                    reg_rows = []
                    for s in range(slots):
                        if slot_req[s] is None or not slot_shareable[s]:
                            continue
                        full = min(cursor_host[s], plen_host[s]) // page_size
                        if full > reg_done[s]:
                            reg_rows.append((s, full))
                    if reg_rows:
                        table = np.asarray(jax.device_get(
                            _first_paged(caches).page_table[0]))
                        new_pins = []
                        for s, full in reg_rows:
                            # the slot's *pending* stream, not the original
                            # prompt: a resumed slot prefills prompt +
                            # generated prefix, and those pages hash under that
                            # stream — which is also what makes a
                            # re-preemption's re-admission adopt them back
                            # nearly for free
                            got = index.register(slot_prompt[s],
                                                 table[s, :full])
                            reg_done[s] = full
                            new_pins.extend(got)
                        if new_pins:
                            pins.update((p, 1) for p in new_pins)
                            pad = np.full((slots * pages_per_seq,), -1,
                                          np.int32)
                            pad[:len(new_pins)] = new_pins
                            caches = _pin_pages(caches, jnp.asarray(pad))
            fin = [s for s in range(slots)
                   if slot_req[s] is not None and done_np[s]]
            for s in fin:
                finish(s, now_s)
            to_release.extend(fin)
            if journal is not None:
                with jax.profiler.TraceAnnotation("serve.journal"):
                    # the boundary's group-commit point: progress deltas + key
                    # snapshots + any completes land in one written batch
                    # (fsynced on the journal's bounded cadence); a crash
                    # before the *next* flush loses at most a bounded suffix of
                    # regenerable work
                    _journal_progress(keys_np)
                    journal.flush()
                    if snap_ckpt is not None \
                            and segments % snapshot_every == 0:
                        save_snapshot()

    if journal is not None:
        _journal_progress(None)
        journal.flush()
        if snap_ckpt is not None:
            # final snapshot: a clean restart (drain + resume, or a new
            # trace over the same prompts) warm-starts the prefix index
            save_snapshot()
            snap_ckpt.wait()
        journal.close()
    if debug:
        _check_paged_invariants(caches, pins=dict(pins))
    wall = time.perf_counter() - t0
    return ServeResult(completed=completed, wall_s=wall, steps=step,
                       segments=segments, admission_rounds=rounds,
                       page_util=page_util, prefill_stall_s=stall_s,
                       prefill_tokens=prefill_tokens,
                       shared_prefix_tokens=shared_tokens,
                       prefix_hits=prefix_hits, preemptions=n_preempts,
                       straggler_segments=straggler_segs,
                       drained=drain_since is not None,
                       recovered=recovered,
                       restored_from_snapshot=restored_from_snapshot,
                       replayed_tokens=replayed_tokens,
                       snapshot_bytes=snapshot_bytes,
                       recovery_s=recovery_s, aging_steps=aging_steps,
                       max_class=max_class)
