"""Typed int8 KV-cache state: the contiguous ring buffer and the paged pool.

``KVCacheState`` replaces the plain ``{"k", "v", "pos", ...}`` dicts the
serving stack used to pass around: same leaves, same scan/shard/donate
behaviour (it is a registered dataclass pytree), but the ring-buffer
invariants live on the type instead of in every caller's head.

Layout: ``k``/``v`` are ``(B, C, G, hd)`` with capacity ``C`` a ring —
token ``t`` lives in slot ``t % C``. ``pos`` is **per sequence**,
``(B,)`` int32: each row of the batch tracks its own logical stream
length, so a ragged batch (different prompt lengths) shares one cache
and one kernel call. The valid prefix (``valid_len``) and the logical
position of new queries (``q_offset``) derive from ``pos`` and are
``(B,)`` vectors that flow through ``dispatch`` into the per-row kernel
meta. ``k_scale``/``v_scale`` are optional per-(kv-)head quantization
scales ``(G,)`` (the decode engine's finer-than-QAT grid); ``None`` when
the cache rides the model's per-tensor QAT scales.

``PagedKVState`` is the continuous-batching allocator: **one** shared
head-major ``(num_pages, G, page_size, hd)`` int8 arena for the whole
batch (each kv head's page is one contiguous ``(page_size, hd)`` tile,
the block the fused kernels DMA; ``hd`` padded to whole 128-lane
tiles), a
per-sequence page table translating logical KV pages to physical arena
pages, and an on-device free stack. Logical semantics are *identical* to
a ring of capacity ``n_pages * page_size`` (slot ``t % C``, same
``pos``/``valid_len``/``q_offset``), so the fused kernels' paged layout
is bit-identical to the ring path — but physically a sequence only holds
``ceil(pos / page_size)`` pages, and ``release`` returns them to the
pool the moment the sequence finishes: KV memory is O(tokens live), not
O(B * max_len) reserved. Physical page 0 is the **parking page** — never
allocated and never written (a write with nothing to land names it and
writes its zeros back), it backs unassigned page-table entries so every
gather stays in bounds without branches, and its bytes stay zero for the
life of the pool. Writes land in place through the ``ita_kv_write``
kernel (``repro.kernels.kv_write``): copy-on-write pages, then the
``(G, 32, hd)`` tiles the new tokens fall in.

Pages carry a **refcount** (``ref_count``, per physical page): rows
admitted with a shared prompt prefix point their leading page-table
entries at another row's pages (``adopt_prefix``, +1 each), the
serving-layer prefix index pins registered pages (``incref_pages``) so
they outlive their original row, and ``release``/``decref_pages`` only
push a page back onto the free stack when its count reaches zero. The
append paths copy-on-write: a write landing on a page with refcount > 1
first copies it to a freshly popped page, so sharers never observe each
other's bytes. Sharing is pure bookkeeping — the kernels read whatever
the page tables say, so the paged layout stays bit-identical to the
ring path whether or not pages are shared.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.common import LANES, MIN_BLOCK_KV
from repro.kernels.kv_write import ita_kv_write, write_tile


def _align_capacity(capacity: int) -> int:
    """Round a ring/pool capacity above one KV block up to a block
    multiple, so the fused kernels' `_pad_seq` is statically a no-op on
    the decode hot path (any block_kv dividing MIN_BLOCK_KV stays
    pad-free)."""
    capacity = max(capacity, 1)
    if capacity > MIN_BLOCK_KV:
        capacity = -(-capacity // MIN_BLOCK_KV) * MIN_BLOCK_KV
    return capacity


def _ceil_div(a, b):
    return (a + b - 1) // b


@dataclasses.dataclass(frozen=True)
class KVCacheState:
    k: Any                      # (B, C, G, hd) int8 (or compute dtype)
    v: Any                      # (B, C, G, hd)
    pos: Any                    # (B,) int32 — tokens ever written, per seq
    k_scale: Any = None         # (G,) f32 per-head scales, optional
    v_scale: Any = None         # (G,) f32

    # -- construction -----------------------------------------------------

    @classmethod
    def init(cls, batch: int, capacity: int, n_kv_heads: int, head_dim: int,
             dtype=jnp.int8, per_head_scales: bool = False) -> "KVCacheState":
        """Fresh (zeroed) ring-buffer cache. Capacities above one KV block
        are rounded up to a ``MIN_BLOCK_KV`` multiple so the per-step
        ``_pad_seq`` in the fused-attention plumbing is statically a
        no-op (it asserts as much on the decode path)."""
        capacity = _align_capacity(capacity)
        shape = (batch, capacity, n_kv_heads, head_dim)
        scales = (jnp.ones((n_kv_heads,), jnp.float32)
                  if per_head_scales else None)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((batch,), jnp.int32), k_scale=scales,
                   v_scale=scales)

    def with_scales(self, k_scale, v_scale) -> "KVCacheState":
        return dataclasses.replace(self, k_scale=k_scale, v_scale=v_scale)

    # -- ring geometry ----------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    def valid_len(self) -> jax.Array:
        """Per-sequence number of valid (non-evicted) ring entries, (B,)."""
        return jnp.minimum(self.pos, self.capacity)

    def q_offset(self, s_new: int = 1) -> jax.Array:
        """Logical position of the first of the ``s_new`` query tokens
        *just appended*, in ring coordinates: ``valid_len - s_new``, per
        sequence ``(B,)``. While a ring has not wrapped this is the
        token's stream position; after wrap the oldest surviving token is
        redefined as position 0, so the newest query sits at ``C - s_new``
        and the sliding-window mask ``(qi - kj) < window`` keeps exactly
        the last ``window`` slots visible."""
        return jnp.maximum(self.valid_len() - s_new, 0)

    # -- writes -----------------------------------------------------------

    def prefill_write(self, k_q: jax.Array, v_q: jax.Array,
                      lengths: jax.Array | None = None) -> "KVCacheState":
        """Bulk-write ``S`` prefill tokens, evicting beyond capacity.

        ``k_q``/``v_q`` (B, S, G, hd), already quantized. Token ``t``
        lands in slot ``t % C`` (so a later ``decode_append`` continues
        the same ring); when ``S >= C`` only the last ``C`` tokens
        survive. ``lengths`` (B,) declares a *ragged* batch of
        right-padded prompts: row ``b`` holds ``lengths[b] <= S`` real
        tokens, ``pos`` starts there and the pad slots are dead weight
        masked out by ``valid_len`` until decode appends overwrite them.
        Ragged prefill requires ``C >= S`` (per-sequence eviction of a
        padded prompt would need per-row rolls)."""
        b, s = k_q.shape[:2]
        cs = self.capacity
        if lengths is not None:
            if s > cs:
                raise ValueError(
                    f"ragged prefill needs capacity >= padded prompt length "
                    f"(got S={s} > C={cs}); grow the ring (max_len, or the "
                    f"window for window-capped caches) or drop lengths")
            pos = jnp.asarray(lengths, jnp.int32).reshape(b)
        else:
            pos = jnp.full((b,), s, jnp.int32)
        if s >= cs:
            # keep the tail, rolled so slot (t % C) holds token t
            k_t = jnp.roll(k_q[:, s - cs:], s % cs, axis=1)
            v_t = jnp.roll(v_q[:, s - cs:], s % cs, axis=1)
        else:
            k_t = jax.lax.dynamic_update_slice(self.k, k_q, (0, 0, 0, 0))
            v_t = jax.lax.dynamic_update_slice(self.v, v_q, (0, 0, 0, 0))
        return dataclasses.replace(self, k=k_t, v=v_t, pos=pos)

    def decode_append(self, k_q: jax.Array, v_q: jax.Array,
                      live: jax.Array | None = None) -> "KVCacheState":
        """Append ``s_new`` decode tokens per sequence: row ``b``'s token
        ``pos[b] + i`` goes to slot ``(pos[b] + i) % C``. A batched
        scatter (``.at[batch, slots]``) rather than dynamic_update_slice:
        slots differ per row in a ragged batch, and a blockwise slice
        would *clamp* at the ring boundary instead of wrapping (silently
        overwriting the newest surviving entries). ``s_new`` is 1 in
        steady-state decode, <= 8 for speculative bursts; a burst longer
        than the ring writes only its last ``C`` tokens (the survivors) —
        scattering all of them would hit duplicate slots, whose winner
        JAX leaves unspecified. ``live`` (B,) bool masks dead batch slots
        (continuous batching): their writes are dropped and their ``pos``
        does not advance."""
        b, s_new = k_q.shape[:2]
        cs = self.capacity
        start = max(s_new - cs, 0)
        slots = (self.pos[:, None] + start
                 + jnp.arange(s_new - start, dtype=jnp.int32)[None, :]) % cs
        bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
        if live is None:
            # unique_indices: consecutive slots mod C, count <= C — no
            # collisions, so XLA can emit the cheap unordered scatter
            k_t = self.k.at[bidx, slots].set(k_q[:, start:],
                                             unique_indices=True)
            v_t = self.v.at[bidx, slots].set(v_q[:, start:],
                                             unique_indices=True)
            pos = self.pos + s_new
        else:
            # dead rows: out-of-bounds slot + mode="drop" discards the
            # write without a branch (still unique within live rows)
            slots = jnp.where(live[:, None], slots, cs)
            k_t = self.k.at[bidx, slots].set(k_q[:, start:], mode="drop")
            v_t = self.v.at[bidx, slots].set(v_q[:, start:], mode="drop")
            pos = self.pos + s_new * live.astype(jnp.int32)
        return dataclasses.replace(self, k=k_t, v=v_t, pos=pos)


jax.tree_util.register_dataclass(
    KVCacheState, data_fields=("k", "v", "pos", "k_scale", "v_scale"),
    meta_fields=())


# ---------------------------------------------------------------------------
# Paged KV pool
# ---------------------------------------------------------------------------

PARKING_PAGE = 0        # physical page 0: write sink / unassigned entries


@dataclasses.dataclass(frozen=True)
class PagedKVState:
    """Shared paged int8 KV pool + per-sequence page tables + free stack.

    ``k``/``v``: head-major ``(num_pages, G, page_size, lanes)`` arena
    shared by every sequence (``lanes``: ``hd`` padded to whole 128-lane
    tiles, zeros past ``hd``), and at the model level one arena per
    layer, stacked.
    ``page_table``: ``(B, n_pages)`` int32 — logical KV page ``j`` of
    sequence ``b`` lives in physical page ``page_table[b, j]``
    (``PARKING_PAGE`` = unassigned). ``pos``: per-sequence stream length,
    exactly as in ``KVCacheState`` — logical slot ``t % capacity`` with
    ``capacity = n_pages * page_size``, so wrap/window semantics (and the
    kernels' view of the bytes) match the ring bit-for-bit.
    ``free_stack``/``free_top``: LIFO of free physical pages; entries
    ``free_stack[:free_top]`` are free. Allocation happens *inside* jit
    (a masked pop per page) so the fused generation scan never leaves the
    device to grow a sequence.

    ``ref_count``: ``(P,)`` int32, references per physical page — one per
    page-table entry within a row's held prefix, plus one per prefix-index
    pin. Exclusively-held pages sit at 1; prefix sharing raises a page
    above 1, arming copy-on-write in the append paths. The allocator
    invariant (``check_invariants``): every page is on the free stack
    XOR referenced with count >= 1, and the count equals the number of
    page-table references plus pins.

    ``layer``: ``None`` while ``k``/``v`` are one layer's arena. A model
    stacks every leaf over its layers; ``at_layer(i)`` then slices the
    bookkeeping to layer ``i`` but keeps ``k``/``v`` whole, the stacked
    ``(L, P, G, page, hd)`` pool, with ``layer = i``: the writes
    (``ita_kv_write``) and the paged attention kernels address the pool
    at that index, so no op copies a layer's pool out of the stack and
    back.
    """

    k: Any                      # (P, G, page, hd), or (L, P, G, page, hd)
    v: Any                      # with ``layer`` set
    page_table: Any             # (B, n_pages) int32
    pos: Any                    # (B,) int32
    free_stack: Any             # (P,) int32
    free_top: Any               # () int32 — number of free pages
    ref_count: Any = None       # (P,) int32 — references per physical page
    k_scale: Any = None         # (G,) f32 per-head scales, optional
    v_scale: Any = None
    layer: Any = None           # () int32 — index into a stacked k/v

    # -- construction -----------------------------------------------------

    @classmethod
    def init(cls, batch: int, capacity: int, n_kv_heads: int, head_dim: int,
             dtype=jnp.int8, per_head_scales: bool = False, *,
             page_size: int = MIN_BLOCK_KV,
             num_pages: int | None = None) -> "PagedKVState":
        """Fresh pool. ``capacity`` (per-sequence logical window) rounds
        up to a ``page_size`` multiple; ``num_pages`` sizes the shared
        arena (default: fully provisioned, ``B * pages_per_seq`` + the
        parking page — pass less to oversubscribe under an admission
        scheduler). The pool's minor dim is ``head_dim`` padded to whole
        ``LANES`` (zeros past ``head_dim``; see ``kernels.common``)."""
        capacity = max(capacity, 1)
        n_pages = _ceil_div(capacity, page_size)
        if num_pages is None:
            num_pages = batch * n_pages + 1
        if num_pages < 2:
            raise ValueError("num_pages must cover the parking page plus "
                             "at least one allocatable page")
        shape = (num_pages, n_kv_heads, page_size,
                 _ceil_div(head_dim, LANES) * LANES)
        scales = (jnp.ones((n_kv_heads,), jnp.float32)
                  if per_head_scales else None)
        # free pages are 1..P-1 (0 is parking); stack[:free_top] free,
        # laid out so the first pop hands out page 1
        stack = jnp.concatenate([
            jnp.arange(num_pages - 1, 0, -1, dtype=jnp.int32),
            jnp.zeros((1,), jnp.int32)])
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   page_table=jnp.zeros((batch, n_pages), jnp.int32),
                   pos=jnp.zeros((batch,), jnp.int32),
                   free_stack=stack,
                   free_top=jnp.asarray(num_pages - 1, jnp.int32),
                   ref_count=jnp.zeros((num_pages,), jnp.int32),
                   k_scale=scales, v_scale=scales)

    def with_scales(self, k_scale, v_scale) -> "PagedKVState":
        return dataclasses.replace(self, k_scale=k_scale, v_scale=v_scale)

    # -- layers -----------------------------------------------------------

    def at_layer(self, i) -> "PagedKVState":
        """Layer ``i`` of a pool stacked over layers: the bookkeeping
        leaves sliced at ``i``, ``k``/``v`` kept whole with ``layer =
        i``."""
        small = {f: getattr(self, f)[i] for f in _BOOKKEEPING
                 if getattr(self, f) is not None}
        return dataclasses.replace(self, layer=jnp.asarray(i, jnp.int32),
                                   **small)

    def put_layer(self, view: "PagedKVState", i) -> "PagedKVState":
        """Store ``at_layer(i)``'s updated ``view`` back into this stack:
        the bookkeeping written at ``i``, ``k``/``v`` taken whole (the
        view's writes already landed in the stacked pool)."""
        small = {f: jax.lax.dynamic_update_index_in_dim(
                     getattr(self, f), getattr(view, f), i, 0)
                 for f in _BOOKKEEPING if getattr(self, f) is not None}
        return dataclasses.replace(self, k=view.k, v=view.v, **small)

    # -- geometry ---------------------------------------------------------

    @property
    def page_size(self) -> int:
        return self.k.shape[-2]

    @property
    def num_pages(self) -> int:
        return self.k.shape[-4]

    @property
    def pages_per_seq(self) -> int:
        return self.page_table.shape[1]

    @property
    def capacity(self) -> int:
        return self.pages_per_seq * self.page_size

    @property
    def batch(self) -> int:
        return self.page_table.shape[0]

    def pages_held(self) -> jax.Array:
        """Physical pages currently backing each sequence, (B,) int32."""
        return jnp.minimum(_ceil_div(self.pos, self.page_size),
                           self.pages_per_seq)

    def valid_len(self) -> jax.Array:
        return jnp.minimum(self.pos, self.capacity)

    def q_offset(self, s_new: int = 1) -> jax.Array:
        return jnp.maximum(self.valid_len() - s_new, 0)

    # -- allocation -------------------------------------------------------

    def _alloc(self, need: jax.Array) -> "PagedKVState":
        """Pop ``need[b]`` pages per row off the free stack into each
        row's next unassigned page-table entries (refcount 1 — the row
        is the sole holder). Callers guarantee ``sum(need) <= free_top``
        (the admission scheduler's invariant; ``tests/test_paged.py``
        property-checks it) — an overdrawn pool drives ``free_top``
        negative, which ``oversubscribed`` exposes."""
        b = need.shape[0]
        npps = self.pages_per_seq
        held = self.pages_held()
        offs = jnp.cumsum(need) - need                     # exclusive
        cols = jnp.arange(npps, dtype=jnp.int32)[None, :]
        take = cols < need[:, None]                        # (B, npps)
        sidx = self.free_top - 1 - (offs[:, None] + cols)
        phys = self.free_stack[jnp.clip(sidx, 0, self.num_pages - 1)]
        dest = jnp.where(take, held[:, None] + cols, npps)  # OOB -> drop
        bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
        pt = self.page_table.at[bidx, dest].set(phys, mode="drop")
        ref = self.ref_count.at[jnp.where(take, phys, self.num_pages)] \
            .set(1, mode="drop")
        top = self.free_top - jnp.sum(take.astype(jnp.int32))
        return dataclasses.replace(self, page_table=pt, ref_count=ref,
                                   free_top=top)

    def oversubscribed(self) -> jax.Array:
        """True when an allocation overdrew the pool (scheduler bug)."""
        return self.free_top < 0

    def _decref(self, dec: jax.Array) -> "PagedKVState":
        """Apply per-page refcount decrements ``dec`` (P,) int32, pushing
        pages whose count reaches zero back onto the free stack in
        ascending page-id order (a fixed, deterministic order regardless
        of which rows dropped them). Guarded against stray decrements:
        a page already at count 0 (free) can neither underflow nor be
        pushed a second time, which is what makes ``release`` and
        ``decref_pages`` idempotent at the allocator level."""
        freed = (dec > 0) & (self.ref_count > 0) & (self.ref_count <= dec)
        freed = freed.at[PARKING_PAGE].set(False)
        ref = jnp.maximum(self.ref_count - dec, 0)
        rank = jnp.cumsum(freed.astype(jnp.int32)) - 1
        dest = jnp.where(freed, self.free_top + rank, self.num_pages)
        pages = jnp.arange(self.num_pages, dtype=jnp.int32)
        stack = self.free_stack.at[dest].set(pages, mode="drop")
        top = self.free_top + jnp.sum(freed.astype(jnp.int32))
        return dataclasses.replace(self, ref_count=ref, free_stack=stack,
                                   free_top=top)

    def release(self, finished: jax.Array) -> "PagedKVState":
        """Drop one reference per page held by every row with
        ``finished[b]``, clear those rows' tables and reset their ``pos``
        to 0 — the continuous-batching hand-back. A page returns to the
        free stack only at refcount zero, so shared prefix pages survive
        until their last holder (row or index pin) lets go.

        Idempotent: a released (or never-admitted) row holds nothing —
        ``pos == 0`` and a parked table — so releasing it again, or
        releasing with overlapping masks, moves no pages and cannot
        double-enter the free stack. Two finished rows sharing a page
        decrement it twice through one per-page count, pushing it once.

        Preemption contract: the serve loop releases *victim* rows with
        this same call — a victim's pages that the prefix index pinned
        (``incref_pages``) decref to the pin's count and stay allocated,
        never freed, so the evicted request's re-admission can adopt
        them back while any later ``evict_lru`` unpin still frees them
        exactly once. Release never needs to know which pages are
        pinned; the refcount partition ``check_invariants`` enforces is
        the whole contract."""
        finished = jnp.asarray(finished, jnp.bool_)
        npps = self.pages_per_seq
        held = self.pages_held()
        give = finished[:, None] \
            & (jnp.arange(npps, dtype=jnp.int32)[None, :] < held[:, None]) \
            & (self.page_table != PARKING_PAGE)
        idx = jnp.where(give, self.page_table, self.num_pages)
        dec = jnp.zeros((self.num_pages,), jnp.int32) \
            .at[idx.reshape(-1)].add(1, mode="drop")
        new = self._decref(dec)
        pt = jnp.where(finished[:, None], PARKING_PAGE, new.page_table)
        pos = jnp.where(finished, 0, new.pos)
        return dataclasses.replace(new, page_table=pt, pos=pos)

    # -- prefix sharing ---------------------------------------------------

    def adopt_prefix(self, rows: jax.Array, pages: jax.Array,
                     n_pages: jax.Array, n_tokens: jax.Array
                     ) -> "PagedKVState":
        """Admission-side prefix adoption: point row ``rows[i]``'s first
        ``n_pages[i]`` page-table entries at the *existing* physical
        pages ``pages[i, :n_pages[i]]`` (+1 refcount each) and start the
        row's stream at ``pos = n_tokens[i]`` — the shared-prefix admit,
        where the leading prompt pages are another request's bytes and
        are never re-prefilled. Copy-on-write protects the donors if
        this row ever wraps onto the shared pages.

        ``rows[i] < 0`` marks a dropped dummy entry of a fixed-width
        admission batch. Target rows must be fresh (released: ``pos`` 0,
        table parked). ``n_tokens`` must equal ``n_pages * page_size`` —
        sharing is page-granular (the prefix index hashes page-aligned
        token chunks), so a partial page is never adopted."""
        b = self.batch
        rows = jnp.asarray(rows, jnp.int32).reshape(-1)
        n = rows.shape[0]
        pages = jnp.asarray(pages, jnp.int32).reshape(n, -1)
        n_pages = jnp.asarray(n_pages, jnp.int32).reshape(n)
        n_tokens = jnp.asarray(n_tokens, jnp.int32).reshape(n)
        valid = rows >= 0
        rowsq = jnp.where(valid, rows, b)
        cols = jnp.arange(pages.shape[1], dtype=jnp.int32)[None, :]
        take = valid[:, None] & (cols < n_pages[:, None]) \
            & (pages != PARKING_PAGE)
        dcol = jnp.where(take, cols, self.pages_per_seq)
        pt = self.page_table.at[rowsq[:, None], dcol].set(pages,
                                                          mode="drop")
        ref = self.ref_count.at[jnp.where(take, pages, self.num_pages)] \
            .add(1, mode="drop")
        pos = self.pos.at[rowsq].set(n_tokens * valid.astype(jnp.int32),
                                     mode="drop")
        return dataclasses.replace(self, page_table=pt, ref_count=ref,
                                   pos=pos)

    def incref_pages(self, pages: jax.Array) -> "PagedKVState":
        """+1 refcount per non-negative entry of ``pages`` (flat int32;
        negative = padding, dropped) — the prefix index's *pin*: a
        pinned page survives its original row's release, keeping a
        registered prefix adoptable until the index evicts it."""
        pages = jnp.asarray(pages, jnp.int32).reshape(-1)
        idx = jnp.where((pages > PARKING_PAGE) & (pages < self.num_pages),
                        pages, self.num_pages)
        return dataclasses.replace(
            self, ref_count=self.ref_count.at[idx].add(1, mode="drop"))

    def decref_pages(self, pages: jax.Array) -> "PagedKVState":
        """Drop one reference per non-negative entry of ``pages`` (the
        index unpin / eviction); pages reaching zero return to the free
        stack. Duplicate ids in one call decrement once each."""
        pages = jnp.asarray(pages, jnp.int32).reshape(-1)
        idx = jnp.where(pages >= 0, pages, self.num_pages)
        dec = jnp.zeros((self.num_pages,), jnp.int32) \
            .at[idx].add(1, mode="drop")
        return self._decref(dec)

    def _cow(self, first: jax.Array, n_new: jax.Array,
             max_width: int) -> "PagedKVState":
        """Copy-on-write the pages the rows are about to overwrite: any
        logical page holding write slots ``[first[b], first[b]+n_new[b])``
        (ring coordinates) whose physical page is shared (refcount > 1)
        is copied to a freshly popped page before the append lands — the
        diverging row repoints its table entry and drops its reference;
        the pristine page stays with the remaining holders, or returns to
        the free stack if every holder diverged in this same call.
        ``max_width`` is the static bound on ``n_new`` (the presented
        token-block width). Touched pages that are unassigned (parking)
        or exclusively held are untouched — the unshared path costs one
        refcount gather. Callers guarantee pop headroom the same way they
        do for ``_alloc``: total references (row holds + pins) never
        exceed the allocatable pool, and a COW swap keeps that sum
        constant.

        Only the bookkeeping happens here: returns ``(state, (src,
        dst))``, the page copies for ``_write_rows`` to make (flat
        ``(B * maxp,)`` page ids, parking to parking where a page is
        not copied)."""
        ps, cs = self.page_size, self.capacity
        npps = self.pages_per_seq
        b = first.shape[0]
        maxp = min(_ceil_div(max_width + ps - 1, ps), npps)
        first = jnp.asarray(first, jnp.int32)
        n_new = jnp.asarray(n_new, jnp.int32)
        p0 = (first % cs) // ps
        npages = jnp.where(n_new > 0,
                           jnp.minimum(_ceil_div(first % ps + n_new, ps),
                                       npps), 0)
        cols = jnp.arange(maxp, dtype=jnp.int32)[None, :]
        jc = (p0[:, None] + cols) % npps                   # (B, maxp)
        bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
        phys = self.page_table[bidx, jc]
        shared = (cols < npages[:, None]) & (phys != PARKING_PAGE) \
            & (self.ref_count[phys] > 1)
        # pop one fresh page per shared entry (row-major, like _alloc)
        flat = shared.reshape(-1)
        rank = jnp.cumsum(flat.astype(jnp.int32)) - 1
        sidx = self.free_top - 1 - rank
        fresh = self.free_stack[jnp.clip(sidx, 0, self.num_pages - 1)] \
            .reshape(b, maxp)
        dst = jnp.where(shared, fresh, self.num_pages).reshape(-1)
        pt = self.page_table.at[bidx, jnp.where(shared, jc, npps)] \
            .set(fresh, mode="drop")
        ref = self.ref_count.at[dst].set(1, mode="drop")
        dec = jnp.zeros((self.num_pages,), jnp.int32) \
            .at[jnp.where(shared, phys, self.num_pages).reshape(-1)] \
            .add(1, mode="drop")
        top = self.free_top - jnp.sum(flat.astype(jnp.int32))
        cow = dataclasses.replace(self, page_table=pt, ref_count=ref,
                                  free_top=top)
        copies = (jnp.where(shared, phys, PARKING_PAGE).reshape(-1),
                  jnp.where(shared, fresh, PARKING_PAGE).reshape(-1))
        return cow._decref(dec), copies

    def _write_rows(self, k_q: jax.Array, v_q: jax.Array,
                    table: jax.Array, first: jax.Array, n: jax.Array,
                    copies=None) -> "PagedKVState":
        """Land row ``i``'s first ``n[i]`` presented tokens ``k_q[i]``/
        ``v_q[i]`` (``(R, S, G, hd)``) at ring slots ``first[i] ..
        first[i] + n[i] - 1`` of the pages its ``table[i]`` names, after
        the copy-on-write page ``copies`` — in place, through
        ``ita_kv_write``. One job per ``(G, tile, hd)`` tile a row's
        tokens can touch; a tile nothing lands in is no job (parking)."""
        ps, cs = self.page_size, self.capacity
        r, s = k_q.shape[:2]
        tile = write_tile(ps)
        nt = min((s + tile - 2) // tile + 1, cs // tile)
        f = (jnp.asarray(first, jnp.int32) % cs)[:, None]
        row0 = ((f // tile) * tile
                + tile * jnp.arange(nt, dtype=jnp.int32)[None, :]) % cs
        # the presented token each tile row takes, and whether it is real
        src = (row0[:, :, None] + jnp.arange(tile, dtype=jnp.int32)
               - f[:, :, None]) % cs                        # (R, nt, tile)
        take = src < jnp.asarray(n, jnp.int32)[:, None, None]
        live = take.any(-1)
        pages = jnp.where(live, jnp.take_along_axis(table, row0 // ps, 1),
                          PARKING_PAGE)
        tiles = jnp.where(live, (row0 % ps) // tile, 0)
        bits = jax.lax.bitcast_convert_type(jnp.sum(
            take.astype(jnp.uint32)
            << jnp.arange(tile, dtype=jnp.uint32), axis=-1), jnp.int32)
        idx = (jnp.arange(r, dtype=jnp.int32)[:, None, None],
               jnp.minimum(src, s - 1))

        def as_tiles(x):                      # (R * nt, G, tile, lanes)
            x = x[idx].swapaxes(2, 3)
            x = jnp.pad(x, [(0, 0)] * 4 + [(0, self.k.shape[-1]
                                            - x.shape[-1])])
            return x.reshape(r * nt, *x.shape[2:])

        stacked = self.layer is not None
        k, v = (self.k, self.v) if stacked else (self.k[None], self.v[None])
        cow_src, cow_dst = (None, None) if copies is None else copies
        k, v = ita_kv_write(k, v, self.layer if stacked else 0, cow_src,
                            cow_dst, pages.reshape(-1), tiles.reshape(-1),
                            bits.reshape(-1), as_tiles(k_q), as_tiles(v_q))
        return dataclasses.replace(self, k=k if stacked else k[0],
                                   v=v if stacked else v[0])

    # -- writes -----------------------------------------------------------

    def prefill_write(self, k_q: jax.Array, v_q: jax.Array,
                      lengths: jax.Array | None = None) -> "PagedKVState":
        """Bulk-write right-padded prompts for the whole batch (rows must
        be fresh/released, ``pos == 0``). Same signature and logical
        outcome as the ring's ``prefill_write`` minus wrap-eviction: a
        prompt longer than ``capacity`` is refused (serving sizes the
        window first). Only ``ceil(len/page_size)`` pages are allocated
        per row — right-pad columns are dropped, so a ragged batch holds
        pages for its *tokens*, not its padding."""
        return self.write_prompts(k_q, v_q, lengths=lengths)

    def write_prompts(self, k_q: jax.Array, v_q: jax.Array,
                      lengths: jax.Array | None = None,
                      slots: jax.Array | None = None) -> "PagedKVState":
        """``prefill_write`` generalized to target batch ``slots``: row
        ``i`` of ``k_q``/``v_q`` (n, S, G, hd) lands in batch slot
        ``slots[i]`` (negative = dummy row, dropped entirely) — the
        admission path that prefills newly arrived requests into slots
        another sequence just released, with a fixed-width dispatch shape
        regardless of how many requests actually arrived."""
        n, s = k_q.shape[:2]
        b = self.batch
        ps = self.page_size
        if lengths is None:
            if s > self.capacity:
                raise ValueError(
                    f"paged prefill needs capacity >= prompt length "
                    f"(got S={s} > C={self.capacity}); grow max_len/window")
            new_pos = jnp.full((n,), s, jnp.int32)
        else:
            # Ragged: only the *valid* lengths must fit the window — the
            # source may be wider than the pool's capacity (e.g. a
            # block-aligned admission scratch); every column beyond a
            # row's length scatters into the parking page regardless.
            # Lengths are clamped so a misdeclared over-window row can
            # never push pos past capacity (callers validate upstream).
            new_pos = jnp.minimum(jnp.asarray(lengths, jnp.int32).reshape(n),
                                  self.capacity)
        if slots is None:
            if n != b:
                raise ValueError(f"full-batch prefill expects {b} rows, "
                                 f"got {n} (pass slots= for a partial one)")
            rows = jnp.arange(b, dtype=jnp.int32)
            valid = jnp.ones((n,), jnp.bool_)
        else:
            rows = jnp.asarray(slots, jnp.int32).reshape(n)
            valid = rows >= 0
            rows = jnp.where(valid, rows, b)               # OOB -> drop
        new_pos = new_pos * valid.astype(jnp.int32)

        need_rows = _ceil_div(new_pos, ps)
        need = jnp.zeros((b,), jnp.int32).at[rows].set(need_rows,
                                                       mode="drop")
        new = self._alloc(need)

        # rows == b clamps in the gather; a dummy row writes nothing
        # (new_pos 0), and neither do columns past a row's length, so the
        # parking page's bytes stay zero
        table = new.page_table[jnp.minimum(rows, b - 1)]
        new = new._write_rows(k_q, v_q, table, jnp.zeros((n,), jnp.int32),
                              new_pos)
        pos = self.pos.at[rows].set(new_pos, mode="drop")
        return dataclasses.replace(new, pos=pos)

    @functools.partial(jax.named_call, name="kv_write")
    def decode_append(self, k_q: jax.Array, v_q: jax.Array,
                      live: jax.Array | None = None) -> "PagedKVState":
        """Append ``s_new`` decode tokens per sequence — the jit-safe hot
        path: rows crossing a page boundary pop a fresh page off the free
        stack *on device* (no host round-trip inside the fused scan);
        once a row has wrapped its logical window its existing pages are
        reused in place, exactly like the ring. A wrap onto a *shared*
        page (refcount > 1) copies it first (``_cow``) so the other
        holders keep the pristine bytes. ``live`` masks dead slots
        (writes dropped, ``pos`` frozen). Bursts longer than the window
        write only their surviving tail; the survivor slots are
        consecutive-mod-C and masked writes are dropped outright, so the
        scatter is duplicate-free — two runs produce identical bytes."""
        b, s_new = k_q.shape[:2]
        ps, cs = self.page_size, self.capacity
        if live is None:
            live = jnp.ones((b,), jnp.bool_)
        live_i = live.astype(jnp.int32)
        start = max(s_new - cs, 0)
        n_eff = s_new - start
        state, copies = self._cow(self.pos + start, n_eff * live_i, n_eff)
        held = state.pages_held()
        want = jnp.minimum(_ceil_div(state.pos + s_new, ps),
                           state.pages_per_seq)
        new = state._alloc((want - held) * live_i)
        new = new._write_rows(k_q[:, start:], v_q[:, start:],
                              new.page_table, state.pos + start,
                              n_eff * live_i, copies)
        return dataclasses.replace(new, pos=state.pos + s_new * live_i)

    @functools.partial(jax.named_call, name="kv_write")
    def append_chunk(self, k_q: jax.Array, v_q: jax.Array,
                     n_new: jax.Array) -> "PagedKVState":
        """Append a *per-row ragged* chunk: row ``b`` writes its first
        ``n_new[b]`` of the ``S`` presented tokens at logical slots
        ``pos[b] .. pos[b] + n_new[b] - 1``, scattering across page
        boundaries and popping fresh pages off the free stack *inside
        jit* exactly like ``decode_append``. Columns beyond a row's count
        (decode rows in a mixed chunked-prefill batch present 1 real
        token; dead rows 0) are dropped and that row's ``pos`` advances
        by its own ``n_new`` only — the write primitive of the mixed
        serve step, where one dispatch carries decode rows next to
        prefill chunks with no ring scratch or host bytes-copy. Shared
        pages in the write range are copied first (``_cow``)."""
        b, s = k_q.shape[:2]
        ps, cs = self.page_size, self.capacity
        if s > cs:
            raise ValueError(
                f"append_chunk width {s} exceeds the per-sequence window "
                f"{cs}; split the chunk (serving sizes chunk <= capacity)")
        n_new = jnp.clip(jnp.asarray(n_new, jnp.int32).reshape(b), 0, s)
        state, copies = self._cow(self.pos, n_new, s)
        held = state.pages_held()
        want = jnp.minimum(_ceil_div(state.pos + n_new, ps),
                           state.pages_per_seq)
        new = state._alloc(want - held)
        new = new._write_rows(k_q, v_q, new.page_table, state.pos, n_new,
                              copies)
        return dataclasses.replace(new, pos=state.pos + n_new)

    # -- debug ------------------------------------------------------------

    def check_invariants(self, pins=None) -> None:
        """Host-side allocator invariant check (debug mode / tests — np
        round-trips the whole state, never the hot path):

        * every physical page is on the free stack XOR referenced (held
          by >= 1 page-table prefix entry or pinned) — no double-booking,
          no leaked pages;
        * each page's ``ref_count`` equals its page-table references plus
          its ``pins`` entry (the prefix index's host-side pin ledger:
          a ``(P,)`` array-like or ``{page: count}`` dict);
        * the parking page is never referenced, never free-listed, and
          no row's held prefix points at it after admission;
        * ``free_top`` stays within ``[0, num_pages - 1]`` and the free
          list holds no duplicates.

        Raises ``AssertionError`` naming the violated condition."""
        import numpy as np

        pt = np.asarray(self.page_table)
        ref = np.asarray(self.ref_count)
        held = np.asarray(self.pages_held())
        top = int(self.free_top)
        P = self.num_pages
        assert 0 <= top <= P - 1, f"free_top {top} outside [0, {P - 1}]"
        free = np.asarray(self.free_stack)[:top]
        free_set = set(free.tolist())
        assert len(free_set) == top, "free stack holds duplicate pages"
        assert PARKING_PAGE not in free_set, "parking page on free stack"

        counts = np.zeros(P, np.int64)
        for row in range(self.batch):
            pages = pt[row, :int(held[row])]
            assert PARKING_PAGE not in pages, (
                f"live row {row} points at the parking page: {pages}")
            np.add.at(counts, pages, 1)
        if pins is not None:
            if isinstance(pins, dict):
                for p, c in pins.items():
                    counts[p] += c
            else:
                counts += np.asarray(pins, np.int64)
        assert ref[PARKING_PAGE] == 0 and counts[PARKING_PAGE] == 0, \
            "parking page acquired a refcount"
        for p in range(1, P):
            assert ref[p] == counts[p], (
                f"page {p}: ref_count {ref[p]} != references {counts[p]}")
            assert (p in free_set) ^ (counts[p] >= 1), (
                f"page {p}: free={p in free_set}, references={counts[p]} "
                f"(every page must be free xor referenced)")


jax.tree_util.register_dataclass(
    PagedKVState,
    data_fields=("k", "v", "page_table", "pos", "free_stack", "free_top",
                 "ref_count", "k_scale", "v_scale", "layer"),
    meta_fields=())

# the per-layer leaves ``at_layer`` slices (a few KB; k/v stay whole)
_BOOKKEEPING = ("page_table", "pos", "free_stack", "free_top", "ref_count",
                "k_scale", "v_scale")


# ---------------------------------------------------------------------------
# Prefix index (host side)
# ---------------------------------------------------------------------------

class PrefixIndex:
    """Host-side map from prompt prefixes to the physical pages already
    holding their K/V bytes — the lookup structure behind serve-time
    prefix sharing.

    Granularity is exactly one page: entry ``j`` keys on a *chain hash*
    of the prompt's ``j``-th ``page_size``-token chunk and chunk
    ``j-1``'s key, so a hit for page ``j`` implies the entire leading
    ``(j+1) * page_size`` tokens match — a lookup walks the chain and
    returns the longest registered prefix. One page id is valid for
    every layer's pool at once because the per-layer allocators run in
    lockstep (identical op sequence → identical tables and stacks),
    which the serving layer validates at startup.

    The index holds one *pin* (+1 refcount, via
    ``PagedKVState.incref_pages``) per registered page, so registered
    prefixes outlive their original request; ``evict_lru`` hands back
    the oldest unprotected pages for the caller to unpin
    (``decref_pages``) when the pool needs room. Why page bytes are
    reusable at all: a token's K/V depend only on (token id, stream
    position), so a page's bytes are a pure function of the chunk's
    tokens and its page-aligned position — exactly what the chain key
    encodes."""

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self._entries: dict = {}        # chain key -> physical page id
        self._page_key: dict = {}       # physical page id -> chain key

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def pinned_pages(self):
        """Snapshot of every registered (pinned) physical page id."""
        return list(self._page_key)

    def _chain_keys(self, tokens, n_chunks: int):
        # blake2b, not Python hash(): hash() is salted per process, and
        # the index must survive a server restart (snapshot/restore) —
        # the same prompt must map to the same chain keys in the new
        # process or every restored entry would be unreachable
        import hashlib

        import numpy as np
        toks = np.ascontiguousarray(np.asarray(tokens, np.int64).reshape(-1))
        prev = b"prefix-chain-v1:%d" % self.page_size   # chain seed
        keys = []
        for j in range(n_chunks):
            chunk = toks[j * self.page_size:(j + 1) * self.page_size]
            prev = hashlib.blake2b(prev + chunk.tobytes(),
                                   digest_size=16).digest()
            keys.append(prev.hex())
        return keys

    def lookup(self, tokens, max_tokens: int | None = None):
        """Longest registered page-aligned prefix of ``tokens`` covering
        at most ``max_tokens`` tokens. Returns the physical page ids (a
        possibly empty list); a lookup refreshes the hit entries' LRU
        position."""
        import numpy as np
        n_tok = int(np.asarray(tokens).size)
        if max_tokens is not None:
            n_tok = min(n_tok, int(max_tokens))
        pages = []
        for key in self._chain_keys(tokens, n_tok // self.page_size):
            page = self._entries.get(key)
            if page is None:
                break
            del self._entries[key]                # LRU touch: re-insert
            self._entries[key] = page
            pages.append(page)
        return pages

    def register(self, tokens, page_ids):
        """Register the pages backing ``tokens``' leading full chunks:
        ``page_ids[j]`` holds chunk ``j``'s bytes. Chunks already
        registered (by any request) are skipped; registration stops at
        the first conflict so the chain stays walkable. Returns the
        newly indexed page ids — the caller must pin exactly those
        (``incref_pages``) before the donor row can release them."""
        import numpy as np
        page_ids = [int(p) for p in np.asarray(page_ids).reshape(-1)]
        new = []
        for key, page in zip(self._chain_keys(tokens, len(page_ids)),
                             page_ids, strict=True):
            if page == PARKING_PAGE:
                break
            have = self._entries.get(key)
            if have is not None:
                continue                          # chunk already indexed
            if page in self._page_key:
                break                             # page serves another key
            self._entries[key] = page
            self._page_key[page] = key
            new.append(page)
        return new

    # -- persistence ------------------------------------------------------

    def state_dict(self) -> dict:
        """JSON-serializable snapshot. Entries are listed oldest-first
        (dict insertion order *is* the LRU order), so a round trip
        preserves eviction behaviour exactly."""
        return {
            "page_size": self.page_size,
            "entries": [[key, int(page)]
                        for key, page in self._entries.items()],
        }

    def load_state_dict(self, state: dict) -> None:
        """Rebuild the index from ``state_dict()`` output. The chain keys
        are deterministic blake2b digests, so entries written by a dead
        process resolve the same prompts here. Raises ``ValueError`` on a
        page-size mismatch (the chain seed, and therefore every key,
        depends on it)."""
        if int(state["page_size"]) != self.page_size:
            raise ValueError(
                f"prefix index snapshot has page_size "
                f"{state['page_size']}, pool uses {self.page_size}")
        self._entries = {}
        self._page_key = {}
        for key, page in state["entries"]:
            self._entries[str(key)] = int(page)
            self._page_key[int(page)] = str(key)

    def evict_lru(self, n: int, protected=frozenset()):
        """Drop up to ``n`` least-recently-used entries whose page is not
        ``protected`` (pages currently adopted by an active request must
        keep their pin — the serving layer's budget accounting depends
        on it). Returns the evicted page ids for the caller to unpin.
        Evicting a chain's head orphans its tail entries (unreachable by
        lookup); they stay evictable and age out under the same LRU
        pressure, so their pins are reclaimed, just not instantly."""
        evicted = []
        for key in list(self._entries):
            if len(evicted) >= n:
                break
            page = self._entries[key]
            if page in protected:
                continue
            del self._entries[key]
            del self._page_key[page]
            evicted.append(page)
        return evicted
