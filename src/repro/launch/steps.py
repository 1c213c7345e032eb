"""Jitted train / prefill / decode steps with production shardings, plus
``input_specs`` (ShapeDtypeStruct stand-ins — weak-type-correct, shardable,
no device allocation) used by the dry-run and launchers."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.launch import sharding as SH
from repro.models import forward, init_caches, init_model, loss_fn
from repro.models.layers import unembed
from repro.optim.optimizer import AdamWConfig, adamw_update, init_opt_state


# ---------------------------------------------------------------------------
# Shape stand-ins
# ---------------------------------------------------------------------------

def params_shape(cfg):
    return jax.eval_shape(functools.partial(init_model, cfg=cfg),
                          jax.random.PRNGKey(0))


def opt_state_shape(cfg):
    return jax.eval_shape(
        lambda: init_opt_state(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         params_shape(cfg))))


def caches_shape(cfg, batch, max_len):
    return jax.eval_shape(
        functools.partial(init_caches, cfg, batch, max_len))


def input_specs(cfg, shape) -> dict[str, Any]:
    """ShapeDtypeStructs for every model input of an (arch × shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((b, s + 1), jnp.int32)}
        if cfg.frontend_dim:
            batch["frontend"] = sds(
                (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
        return {"batch": batch}
    if shape.kind == "prefill":
        out = {"tokens": sds((b, s), jnp.int32),
               "caches": caches_shape(cfg, b, s)}
        if cfg.frontend_dim:
            out["frontend"] = sds(
                (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
        return out
    # decode: one new token per sequence against a seq_len KV cache, at
    # per-sequence positions (ragged-capable — the production shape)
    out = {"tokens": sds((b, 1), jnp.int32),
           "caches": caches_shape(cfg, b, s),
           "pos0": sds((b,), jnp.int32)}
    if cfg.frontend_dim:
        out["frontend"] = sds(
            (b, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32)
    return out


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt_cfg: AdamWConfig):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, cfg)
        params, opt_state, stats = adamw_update(grads, opt_state, params,
                                                opt_cfg)
        return params, opt_state, {"loss": loss, **metrics, **stats}
    return train_step


def make_prefill_step(cfg):
    def prefill_step(params, tokens, caches, frontend=None, lengths=None):
        logits, caches, _ = forward(params, tokens, cfg, mode="prefill",
                                    frontend=frontend, caches=caches,
                                    lengths=lengths)
        if lengths is None:
            return logits[:, -1:], caches
        # ragged: each sequence's next-token logits sit at its own last
        # valid position of the right-padded prompt
        idx = (jnp.asarray(lengths, jnp.int32) - 1)[:, None, None]
        return jnp.take_along_axis(logits, idx, axis=1), caches
    return prefill_step


def make_decode_step(cfg):
    def decode_step(params, tokens, caches, pos0, frontend=None, live=None):
        logits, caches, _ = forward(params, tokens, cfg, mode="decode",
                                    frontend=frontend, caches=caches,
                                    pos0=pos0, live=live)
        return logits, caches
    return decode_step


# ---------------------------------------------------------------------------
# Fused generation loop (decode without per-token host dispatch)
# ---------------------------------------------------------------------------

def sample_token(logits, key, temperature, *, sample: bool):
    """Next token from (B, 1, V) logits: greedy argmax or temperature
    sampling. Returns ``(tok (B, 1) int32, new_key)`` — the key is split
    exactly once per sampled step so the fused scan loop and the per-step
    host loop consume identical PRNG streams (bit-identical outputs)."""
    if not sample:
        return jnp.argmax(logits, -1).astype(jnp.int32), key
    key, sub = jax.random.split(key)
    tok = jax.random.categorical(sub, logits / temperature, axis=-1)
    return tok.astype(jnp.int32), key


def advance_step(logits, key, temperature, done, n, *, sample: bool,
                 eos_id: int | None, pad_id: int):
    """Per-step tail shared by the fused scan body and the stepwise host
    loop: sample the next token, pin finished sequences to ``pad_id``,
    count live decode tokens into ``n`` and fold new EOS hits into
    ``done``. Both loops calling this one function is what makes their
    documented bit-parity structural rather than merely test-caught.
    Returns ``(tok (B, 1), new_key, done, n)``."""
    nxt, key = sample_token(logits, key, temperature, sample=sample)
    if eos_id is not None:
        nxt = jnp.where(done[:, None], pad_id, nxt)
        n = n + jnp.sum(~done).astype(jnp.int32)
        done = done | (nxt[:, 0] == eos_id)
    else:
        n = n + nxt.shape[0]
    return nxt, key, done, n


def make_generate_loop(cfg, *, gen: int, sample: bool, eos_id: int | None,
                       pad_id: int, early_exit: bool):
    """One jitted on-device generation loop: ``gen - 1`` decode steps as a
    single dispatch instead of ``gen - 1`` host round-trips.

    The carry ``(caches, tok, pos, key, done, n)`` is scanned over decode
    steps: each step runs the decode forward, samples on-device (PRNG key
    threaded through the carry), advances the per-sequence positions, and
    — when ``eos_id`` is set — pins finished sequences to ``pad_id``
    while counting only live ones into ``n`` (the honest tok/s
    denominator). ``early_exit`` swaps the scan for a ``lax.while_loop``
    that stops as soon as every sequence has emitted EOS (same outputs:
    the steps it skips would have produced only pads).

    Returns ``loop(params, tok0, caches, pos0, key, temperature,
    frontend) -> (tokens (B, gen-1), n_decode_tokens, steps_run,
    caches)`` — ``steps_run < gen-1`` when ``early_exit`` fired; jit
    with ``donate_argnums=(2,)`` so the caches update in place.
    """
    decode = make_decode_step(cfg)
    steps = gen - 1

    def loop(params, tok0, caches, pos0, key, temperature, frontend=None):
        b = tok0.shape[0]
        done0 = (tok0[:, 0] == eos_id) if eos_id is not None \
            else jnp.zeros((b,), jnp.bool_)
        key = jax.random.PRNGKey(0) if key is None else key
        carry0 = (caches, tok0, jnp.asarray(pos0, jnp.int32), key, done0,
                  jnp.zeros((), jnp.int32))

        def step(carry):
            caches, tok, pos, key, done, n = carry
            logits, caches = decode(params, tok, caches, pos, frontend)
            nxt, key, done, n = advance_step(
                logits, key, temperature, done, n, sample=sample,
                eos_id=eos_id, pad_id=pad_id)
            return (caches, nxt, pos + 1, key, done, n)

        if early_exit:
            out0 = jnp.full((b, steps), pad_id, jnp.int32)

            def cond(st):
                i, carry = st[0], st[1]
                return (i < steps) & ~jnp.all(carry[4])

            def body(st):
                i, carry, out = st
                carry = step(carry)
                return (i + 1, carry, out.at[:, i].set(carry[1][:, 0]))

            i, carry, out = jax.lax.while_loop(
                cond, body, (jnp.zeros((), jnp.int32), carry0, out0))
            return out, carry[5], i, carry[0]

        def body(carry, _):
            carry = step(carry)
            return carry, carry[1][:, 0]

        carry, toks = jax.lax.scan(body, carry0, None, length=steps)
        return toks.T, carry[5], jnp.asarray(steps, jnp.int32), carry[0]

    return loop


# ---------------------------------------------------------------------------
# Continuous-batching serve segments (pure decode + mixed chunked prefill)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeSlotState:
    """Per-slot device state of the continuous-batching serve loop.

    One fixed-width pytree the fused segments carry and the (tiny)
    admission dispatch updates — admission is *just* this state write
    plus the host's page reservation: prompt token ids are enqueued here
    and prefilled chunk-by-chunk inside the segments (``cursor`` <
    ``plen`` marks the prefill phase), so there is no stop-the-world
    prompt dispatch and no ring-scratch bytes-copy on the chunked path.
    A prefix-sharing admit starts ``cursor``/``pos`` at the shared token
    count instead of 0 (the leading prompt pages were adopted from the
    pool, never re-prefilled); the mixed segment body needs no change —
    it simply sees fewer prompt tokens left. ``keys`` is a per-slot PRNG
    stream (``fold_in`` of the serve key by request id), making sampled
    outputs independent of admission interleaving. ``prio`` is the
    slot's SLO class (higher = more urgent): it orders the mixed body's
    prompt-chunk grants, so under budget contention high-priority
    prefills finish first. ``pgen`` is the slot's preemption generation
    — bumped by every ``preempt_rows`` so host-side readbacks can tell a
    re-admitted slot from the victim it replaced."""

    tok: Any                  # (B, 1) int32 — last sampled token
    pos: Any                  # (B,) int32 — stream position (cache pos)
    keys: Any                 # (B, 2) uint32 — per-slot PRNG streams
    done: Any                 # (B,) bool — finished / empty slots
    rem: Any                  # (B,) int32 — tokens left to emit
    cursor: Any               # (B,) int32 — prompt tokens prefilled so far
    plen: Any                 # (B,) int32 — prompt length
    prompt_buf: Any           # (B, prompt_pad) int32 — queued prompt ids
    prio: Any                 # (B,) int32 — SLO class (higher = urgent)
    pgen: Any                 # (B,) int32 — preemption generation counter

    @classmethod
    def init(cls, slots: int, prompt_pad: int, key=None) -> "ServeSlotState":
        key = jax.random.PRNGKey(0) if key is None else key
        return cls(
            tok=jnp.zeros((slots, 1), jnp.int32),
            pos=jnp.zeros((slots,), jnp.int32),
            keys=fold_keys(key, jnp.arange(slots, dtype=jnp.int32)),
            done=jnp.ones((slots,), jnp.bool_),
            rem=jnp.zeros((slots,), jnp.int32),
            cursor=jnp.zeros((slots,), jnp.int32),
            plen=jnp.zeros((slots,), jnp.int32),
            prompt_buf=jnp.zeros((slots, max(prompt_pad, 1)), jnp.int32),
            prio=jnp.zeros((slots,), jnp.int32),
            pgen=jnp.zeros((slots,), jnp.int32))


jax.tree_util.register_dataclass(
    ServeSlotState,
    data_fields=("tok", "pos", "keys", "done", "rem", "cursor", "plen",
                 "prompt_buf", "prio", "pgen"),
    meta_fields=())


def aged_priority(prio: int, waited: int, aging_steps: int | None,
                  max_class: int) -> int:
    """Starvation aging (host scheduler helper): a waiting request's
    effective SLO class grows by one every ``aging_steps`` virtual steps,
    capped at ``max_class + 1`` — one above the trace's highest real
    class, so a fully aged request outranks *every* fresh arrival but
    capped requests tie with each other (FIFO within the cap) and can
    never be preemption victims of one another. The cap is what bounds
    the worst-case admission delay: a class-``c`` request reaches the
    cap after ``aging_steps * (max_class + 1 - c)`` steps of waiting
    (``ServeResult.class_summary()['aging_bound_steps']``). ``None`` or
    non-positive ``aging_steps`` disables aging (identity on ``prio``)."""
    if aging_steps is None or aging_steps <= 0:
        return prio
    return min(prio + max(int(waited), 0) // int(aging_steps),
               max_class + 1)


@jax.jit
@functools.partial(jax.named_call, name="pool")
def fold_keys(key, ids):
    """One PRNG stream per id: ``fold_in(key, ids[i])`` — request-id
    derived streams make each served request's draws a function of its
    own id, not of admission interleaving."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.asarray(ids, jnp.int32))


def admit_rows(state, slot_ids):
    """OOB-drop row indices for a fixed-width admission batch (padding
    rows carry slot_id -1 and drop out of every scatter)."""
    return jnp.where(slot_ids >= 0, slot_ids, state.done.shape[0])


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def admit_chunked(state, slot_ids, prompts, lengths, gens, req_keys,
                  shared=None, prios=None):
    """Chunked admission is *only* this state write (plus the host's page
    reservation): enqueue the prompt token ids and arm the slot's phase
    state — the segments prefill chunk-by-chunk, page-native. No prompt
    forward, no ring scratch, no bytes-copy. ``shared`` (n,) int32 is the
    per-row count of prompt tokens already covered by adopted prefix
    pages (``PagedKVState.adopt_prefix`` ran in the same admission
    round): ``cursor`` and ``pos`` start there, so chunked prefill picks
    up at the first unshared token and the skipped tokens are never
    forwarded at all. ``prios`` (n,) int32 sets the slot's SLO class
    (``None`` = class 0 — the write still happens, so a slot freed by a
    high-priority victim never leaks its stale class)."""
    rows = admit_rows(state, slot_ids)
    start = jnp.zeros_like(lengths) if shared is None \
        else jnp.asarray(shared, jnp.int32)
    prio = jnp.zeros_like(lengths) if prios is None \
        else jnp.asarray(prios, jnp.int32)
    return dataclasses.replace(
        state,
        prompt_buf=state.prompt_buf.at[rows].set(prompts, mode="drop"),
        plen=state.plen.at[rows].set(lengths, mode="drop"),
        cursor=state.cursor.at[rows].set(start, mode="drop"),
        pos=state.pos.at[rows].set(start, mode="drop"),
        tok=state.tok.at[rows].set(0, mode="drop"),
        done=state.done.at[rows].set(False, mode="drop"),
        rem=state.rem.at[rows].set(gens, mode="drop"),
        keys=state.keys.at[rows].set(req_keys, mode="drop"),
        prio=state.prio.at[rows].set(prio, mode="drop"))


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def admit_stall(state, slot_ids, lengths, tok0, new_done, new_rem,
                req_keys, prios=None):
    """Stall-mode admission state write, after the stop-the-world prefill
    sampled ``tok0``: the slot enters directly in the decode phase
    (``cursor == plen``)."""
    rows = admit_rows(state, slot_ids)
    prio = jnp.zeros_like(lengths) if prios is None \
        else jnp.asarray(prios, jnp.int32)
    return dataclasses.replace(
        state,
        tok=state.tok.at[rows].set(tok0, mode="drop"),
        pos=state.pos.at[rows].set(lengths, mode="drop"),
        plen=state.plen.at[rows].set(lengths, mode="drop"),
        cursor=state.cursor.at[rows].set(lengths, mode="drop"),
        done=state.done.at[rows].set(new_done, mode="drop"),
        rem=state.rem.at[rows].set(new_rem, mode="drop"),
        keys=state.keys.at[rows].set(req_keys, mode="drop"),
        prio=state.prio.at[rows].set(prio, mode="drop"))


@functools.partial(jax.jit, donate_argnums=(0,))
@functools.partial(jax.named_call, name="pool")
def preempt_rows(state, mask):
    """One-dispatch victim release: evict every slot in ``mask`` (B,)
    bool from the batch. The victims' phase state zeroes and ``done``
    raises — the next segment's bodies mask them out exactly like
    finished slots, so their (host-released) pages are never touched —
    while ``pgen`` bumps so readbacks attribute in-flight segment output
    to the old occupant, not a future re-admission. ``keys`` is left
    as-is: the host snapshots the victim's stream *before* preempting
    and restores it at re-admission, which is what makes a resumed
    sampled request's draws bit-identical to never having been
    preempted."""
    mask = jnp.asarray(mask, jnp.bool_)
    keep = ~mask
    zero = jnp.zeros_like(state.pos)
    return dataclasses.replace(
        state,
        tok=jnp.where(mask[:, None], 0, state.tok),
        pos=jnp.where(keep, state.pos, zero),
        done=state.done | mask,
        rem=jnp.where(keep, state.rem, zero),
        cursor=jnp.where(keep, state.cursor, zero),
        plen=jnp.where(keep, state.plen, zero),
        prio=jnp.where(keep, state.prio, zero),
        pgen=state.pgen + mask.astype(jnp.int32))


def advance_step_rows(logits, keys, temperature, done, rem, n, active, *,
                      sample: bool, eos_id: int | None, pad_id: int):
    """Per-row serve-step tail shared by the pure-decode and mixed segment
    bodies — the per-slot-PRNG analogue of ``advance_step``: sample each
    ``active`` row from its own stream, pad everything else, count active
    emissions into ``n``, charge them against ``rem`` and fold budget
    exhaustion / EOS into ``done``. Both bodies calling this one function
    keeps their emission bookkeeping structurally identical (the
    chunked ≡ stall bit-parity guarantee), not merely test-caught.
    Returns ``(tok (B, 1), keys, done, rem, n)``."""
    nxt, keys = sample_token_rows(logits, keys, temperature, sample=sample,
                                  advance=active)
    nxt = jnp.where(active[:, None], nxt, pad_id)
    n = n + jnp.sum(active).astype(jnp.int32)
    rem = rem - active.astype(jnp.int32)
    done = done | (active & (rem <= 0))
    if eos_id is not None:
        done = done | (active & (nxt[:, 0] == eos_id))
    return nxt, keys, done, rem, n


def sample_token_rows(logits, keys, temperature, *, sample: bool,
                      advance=None):
    """Per-row ``sample_token``: row ``b`` draws from its own stream
    ``keys[b]`` with the exact solo-generate split schedule (``key, sub =
    split(key)`` once per sampled token), so a request served through any
    admission interleaving consumes the same stream as generating it
    alone with ``fold_in``-derived keys. ``advance`` (B,) masks which
    rows actually consume randomness this step (rows mid-prompt draw
    nothing). Greedy (``sample=False``) is a plain argmax."""
    if not sample:
        return jnp.argmax(logits, -1).astype(jnp.int32), keys
    pair = jax.vmap(jax.random.split)(keys)          # (B, 2, key)
    subs = pair[:, 1]
    tok = jax.vmap(
        lambda s, lg: jax.random.categorical(s, lg / temperature, axis=-1)
    )(subs, logits)
    new_keys = pair[:, 0]
    if advance is not None:
        new_keys = jnp.where(advance[:, None], new_keys, keys)
    return tok.astype(jnp.int32), new_keys


def make_serve_segment(cfg, *, segment: int, sample: bool,
                       eos_id: int | None, pad_id: int,
                       chunk: int | None = None, budget: int | None = None,
                       mixed_steps: int | None = None):
    """One fused continuous-batching segment: a ``lax.scan`` of
    ``segment`` steps over a fixed-slot ``ServeSlotState``, between two
    host admission points.

    ``chunk=None`` — pure decode: every live slot advances one token per
    step through the paged decode kernel (``live = ~done`` masks
    finished/empty slots out of cache writes and position advances, so
    the host can release a finished slot's pages at the boundary without
    the scan ever touching freed memory).

    ``chunk=N`` — **mixed** chunked-prefill + decode: each step, every
    live slot processes either one decode token or one prompt chunk of up
    to ``N`` tokens written *directly into pool pages*
    (``PagedKVState.append_chunk`` + the ragged-q paged kernel — no ring
    scratch, no separate prefill dispatch). The per-step token budget is
    decode-maximal (Sarathi-style): every decoding slot gets its token
    first, then prompt chunks fill the leftover ``budget - n_decode``
    greedily in slot order — so decode throughput never stops for a
    prompt, and with ``budget >= slots`` the head prefilling slot always
    progresses. A slot whose chunk completes its prompt samples its first
    token that same step (the logits of the prompt's last token), exactly
    as a one-shot prefill would.

    ``mixed_steps=k`` runs a **two-phase** segment in one dispatch: the
    first ``k`` steps execute the mixed (chunk-wide) body, the remaining
    ``segment - k`` the 1-token decode body — the scheduler sizes ``k``
    to the prompt chunks actually outstanding, so segments stay long
    (one host round-trip per ``segment`` steps) while chunk-wide q width
    is paid only where prefill happens. ``None`` = all ``segment`` steps
    mixed.

    Returns ``seg(params, state, caches, temperature, frontend) ->
    (tokens (B, segment), emitted (B, segment), grants (B, segment),
    state, caches, n_live)`` — ``emitted`` masks which step-tokens are
    real (a prefilling slot emits nothing until its prompt completes);
    ``grants`` records per-slot granted token counts (the budget
    invariant ``sum(grants[:, t]) <= budget`` is property-tested). Jit
    with ``donate_argnums=(1, 2)``.

    Device work is named for profiler traces (``jax.named_scope``, in
    each op's ``op_name``): the whole call is ``serve_segment``; each
    phase, ``mixed_phase`` and ``decode_phase``, lowers to a ``while``
    whose ``op_name`` ends in ``<phase>/while`` (a phase of one step may
    be compiled without a loop). Inside a step: ``grant`` (the mixed
    budget and token block), ``embed``, ``attn_qkv``, ``kv_write``,
    ``attn_kernel``, ``attn_out``, ``mlp``, ``layer_carry`` (each
    layer's cache slice and write-back), ``head`` and ``sample``.
    """
    decode = make_decode_step(cfg)
    if chunk is not None:
        assert chunk >= 1, chunk
        assert budget is not None and budget >= 1, budget

    def decode_body(params, frontend, temperature, carry, _):
        caches, st, n = carry
        # slots still mid-prompt (a two-phase segment whose mixed steps
        # underestimated budget contention) pause rather than decode
        # from a token they never sampled
        live = ~st.done & (st.cursor >= st.plen)
        logits, caches = decode(params, st.tok, caches, st.pos, frontend,
                                live)
        with jax.named_scope("sample"):
            nxt, keys, done, rem, n = advance_step_rows(
                logits, st.keys, temperature, st.done, st.rem, n, live,
                sample=sample, eos_id=eos_id, pad_id=pad_id)
            pos = st.pos + live.astype(jnp.int32)
            st = dataclasses.replace(
                st, tok=jnp.where(live[:, None], nxt, st.tok), pos=pos,
                keys=keys, done=done, rem=rem)
        return (caches, st, n), (nxt[:, 0], live, live.astype(jnp.int32))

    def mixed_body(params, frontend, temperature, carry, _):
        caches, st, n = carry
        with jax.named_scope("grant"):
            live = ~st.done
            prefilling = live & (st.cursor < st.plen)
            decoding = live & (st.cursor >= st.plen)
            # decode-maximal budget: decode slots first, prompt chunks
            # fill the leftover greedily in priority order (stable argsort
            # — equal priorities keep slot order, so an all-class-0 batch
            # grants exactly as before)
            want = jnp.where(prefilling,
                             jnp.minimum(chunk, st.plen - st.cursor), 0)
            order = jnp.argsort(-st.prio, stable=True)
            want_o = want[order]
            cum_o = jnp.cumsum(want_o) - want_o          # exclusive
            left = budget - jnp.sum(decoding.astype(jnp.int32))
            grant = jnp.zeros_like(want).at[order].set(
                jnp.clip(left - cum_o, 0, want_o))
            n_new = grant + decoding.astype(jnp.int32)
            # token block: prompt chunk at the cursor, or [tok, pad...]
            cols = st.cursor[:, None] + jnp.arange(chunk, dtype=jnp.int32)
            ptoks = jnp.take_along_axis(
                st.prompt_buf,
                jnp.clip(cols, 0, st.prompt_buf.shape[1] - 1), axis=1)
            first = jnp.arange(chunk, dtype=jnp.int32)[None, :] == 0
            tokens = jnp.where(prefilling[:, None], ptoks,
                               jnp.where(first, st.tok, pad_id))
        x, caches, _ = forward(params, tokens, cfg, mode="decode",
                               frontend=frontend, caches=caches,
                               pos0=st.pos, q_lens=n_new, skip_unembed=True)
        with jax.named_scope("head"):
            # next-token logits sit at each row's last granted column;
            # only that (B, 1, d) slice is unembedded — mid-prompt rows
            # discard it
            sel = jnp.take_along_axis(
                x, jnp.maximum(n_new - 1, 0)[:, None, None], axis=1)
            logits = unembed(params["embed"], sel, cfg.logit_softcap)
        with jax.named_scope("sample"):
            completes = prefilling & (st.cursor + n_new >= st.plen)
            emits = decoding | completes
            nxt, keys, done, rem, n = advance_step_rows(
                logits, st.keys, temperature, st.done, st.rem, n, emits,
                sample=sample, eos_id=eos_id, pad_id=pad_id)
            st = dataclasses.replace(
                st, tok=jnp.where(emits[:, None], nxt, st.tok),
                pos=st.pos + n_new, keys=keys, done=done, rem=rem,
                cursor=st.cursor + jnp.where(prefilling, n_new, 0))
        return (caches, st, n), (nxt[:, 0], emits, n_new)

    k = 0 if chunk is None else \
        (segment if mixed_steps is None else min(mixed_steps, segment))

    def seg(params, state, caches, temperature, frontend=None):
        with jax.named_scope("serve_segment"):
            carry = (caches, state, jnp.zeros((), jnp.int32))
            outs = []
            if k > 0:
                with jax.named_scope("mixed_phase"):
                    carry, out = jax.lax.scan(
                        functools.partial(mixed_body, params, frontend,
                                          temperature), carry, None,
                        length=k)
                outs.append(out)
            if k < segment:
                with jax.named_scope("decode_phase"):
                    carry, out = jax.lax.scan(
                        functools.partial(decode_body, params, frontend,
                                          temperature), carry, None,
                        length=segment - k)
                outs.append(out)
            caches, state, n = carry
            toks, emits, grants = (
                jnp.concatenate(parts, axis=0) if len(outs) > 1
                else parts[0] for parts in zip(*outs, strict=True))
            return toks.T, emits.T, grants.T, state, caches, n

    return seg


# ---------------------------------------------------------------------------
# Jit with shardings
# ---------------------------------------------------------------------------

def jit_train_step(cfg, mesh, opt_cfg: AdamWConfig):
    pshape = params_shape(cfg)
    p_sh = SH.param_shardings(pshape, mesh)
    o_sh = SH.opt_state_shardings(pshape, mesh)
    rep = SH.replicated(mesh)
    dummy_batch = input_specs(cfg, _TrainShape)["batch"]
    step = make_train_step(cfg, opt_cfg)
    return jax.jit(
        step,
        in_shardings=(p_sh, o_sh, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    ), p_sh, o_sh


class _TrainShape:                      # minimal duck-typed shape for jit
    kind = "train"
    global_batch = 8
    seq_len = 128


def lower_cell(cfg, shape, mesh, opt_cfg: AdamWConfig | None = None):
    """Lower (not compile) the step for one (arch × shape × mesh) cell,
    with all in/out shardings pinned. Returns the jax ``Lowered``."""
    from repro.launch.hints import use_hints
    opt_cfg = opt_cfg or AdamWConfig()
    par = getattr(cfg, "parallelism", "tp_fsdp")
    pshape = params_shape(cfg)
    p_sh = SH.param_shardings(pshape, mesh, par)
    specs = input_specs(cfg, shape)
    rep = SH.replicated(mesh)

    with mesh, use_hints(mesh, par):
        if shape.kind == "train":
            o_sh = SH.opt_state_shardings(
                pshape, mesh, par,
                has_master=cfg.param_dtype == "bfloat16")
            b_sh = SH.batch_shardings(specs["batch"], mesh, par)
            step = make_train_step(cfg, opt_cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            return jitted.lower(pshape, opt_state_shape(cfg), specs["batch"])

        c_sh = SH.cache_shardings(specs["caches"], shape.global_batch, mesh)
        lg_sh = SH.logits_sharding(mesh, shape.global_batch, cfg.vocab_size,
                                   par)
        if shape.kind == "prefill":
            b_sh = SH.batch_shardings(
                {"tokens": specs["tokens"]}, mesh, par)["tokens"]
            f_sh = (SH.batch_shardings({"f": specs["frontend"]}, mesh,
                                       par)["f"]
                    if "frontend" in specs else None)
            step = make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh, c_sh, f_sh),
                             out_shardings=(lg_sh, c_sh),
                             donate_argnums=(2,))
            return jitted.lower(pshape, specs["tokens"], specs["caches"],
                                specs.get("frontend"))

        b_sh = SH.batch_shardings({"tokens": specs["tokens"]},
                                  mesh, par)["tokens"]
        f_sh = (SH.batch_shardings({"f": specs["frontend"]}, mesh, par)["f"]
                if "frontend" in specs else None)
        step = make_decode_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh, c_sh, rep, f_sh),
                         out_shardings=(lg_sh, c_sh), donate_argnums=(2,))
        return jitted.lower(pshape, specs["tokens"], specs["caches"],
                            specs["pos0"], specs.get("frontend"))
