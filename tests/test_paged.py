"""Paged KV pool: ring-equivalence, allocator correctness, kernel parity.

The acceptance property (ISSUE 4): the paged decode path — one shared
``(num_pages, G, page_size, hd)`` arena consumed through page-table
index maps — is **bit-identical** to the contiguous ring path on the
``s_out`` output grid, across every backend that serves the paged spec
(the ``ita_fused`` family invariant extended to the ``bhsd_paged``
layout). On top of that, the allocator itself is property-checked: no
physical page is ever double-booked, released pages return to the free
stack, and realloc reuses them without leaking state.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import attention as ATT
from repro.attention import KVCacheState, PagedKVState
from repro.kernels.common import MIN_BLOCK_KV
from repro.runtime import kv_cache as KV

rng = np.random.default_rng(0)

S_Q, S_OUT = np.float32(0.05), np.float32(0.02)


def _i8(*shape):
    return rng.integers(-128, 128, shape, dtype=np.int8)


def _paged_from_logical(k_log, v_log, page, *, shuffle_seed=1):
    """Scatter (B, C, G, hd) logical KV into a shuffled arena + table."""
    b, c, g, hd = k_log.shape
    npps = c // page
    total = b * npps + 1
    perm = np.random.default_rng(shuffle_seed).permutation(
        np.arange(1, total))
    pt = perm.reshape(b, npps).astype(np.int32)
    k_pool = np.zeros((total, g, page, hd), np.int8)
    v_pool = np.zeros((total, g, page, hd), np.int8)
    for bb in range(b):
        for j in range(npps):
            k_pool[pt[bb, j]] = k_log[bb, j * page:(j + 1) * page].swapaxes(
                0, 1)
            v_pool[pt[bb, j]] = v_log[bb, j * page:(j + 1) * page].swapaxes(
                0, 1)
    return k_pool, v_pool, pt


# ---------------------------------------------------------------------------
# Kernel parity: paged ≡ ring, every eligible backend
# ---------------------------------------------------------------------------

PARITY_SPECS = [
    # (hq, hkv, window, per_head) — causal, sliding-window, GQA and
    # per-head-scale decode specs, as in the ring parity sweep
    pytest.param(4, 4, 0, False, id="causal"),
    pytest.param(4, 4, 80, True, id="sliding-window+per-head"),
    pytest.param(4, 2, 0, True, id="gqa+per-head"),
    pytest.param(4, 2, 80, False, id="gqa+window"),
]


@pytest.mark.parametrize("hq,hkv,window,per_head", PARITY_SPECS)
def test_paged_parity_sweep_across_backends(hq, hkv, window, per_head):
    """Every backend eligible for the paged decode spec is bit-identical
    to the ring-buffer path at block_kv == page_size, mixed (ragged)
    valid prefixes included."""
    b, d, page, npps = 2, 32, 64, 3
    cap = page * npps
    q = _i8(b, hq, 1, d)
    k_log = _i8(b, cap, hkv, d)
    v_log = _i8(b, cap, hkv, d)
    if per_head:
        sk = jnp.asarray(rng.uniform(0.03, 0.07, (hkv,)).astype(np.float32))
        sv = jnp.asarray(rng.uniform(0.03, 0.07, (hkv,)).astype(np.float32))
    else:
        sk = sv = jnp.asarray(np.float32(0.04))
    scales = ATT.QuantScales(S_Q, sk, sv, S_OUT)
    kv_lens = jnp.asarray([150, cap])              # row 1 fully wrapped
    offs = kv_lens - 1

    ring_spec = ATT.AttentionSpec(
        mode="decode", impl="ita", window=window, layout="bhsd_bsgd",
        scale_kind="per_head" if per_head else "per_tensor",
        out_dtype="int8", q_len=1)
    ring = ATT.dispatch(jnp.asarray(q), jnp.asarray(k_log),
                        jnp.asarray(v_log), spec=ring_spec, scales=scales,
                        q_offset=offs, kv_len=kv_lens,
                        backend="ita_decode_pallas", block_kv=page)

    k_pool, v_pool, pt = _paged_from_logical(k_log, v_log, page)
    spec = ring_spec.replace(layout="bhsd_paged")
    eligible = ATT.list_backends(spec)
    assert len(eligible) >= 2, eligible            # a sweep, not a singleton
    assert {ATT.get_backend(n).family for n in eligible} == {"ita_fused"}
    for name in eligible:
        out = ATT.dispatch(jnp.asarray(q), jnp.asarray(k_pool),
                           jnp.asarray(v_pool), spec=spec, scales=scales,
                           q_offset=offs, kv_len=kv_lens,
                           page_table=jnp.asarray(pt), backend=name)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ring),
            err_msg=f"{name} (paged) != ring path for {spec}")


def test_paged_layout_capability_matrix():
    """bhsd_paged is served by exactly the fused decode/onepass kernels;
    everything else declines with a reason, and dispatch enforces the
    page_table handshake."""
    spec = ATT.AttentionSpec(mode="decode", impl="ita", layout="bhsd_paged",
                             out_dtype="int8", q_len=1)
    assert ATT.list_backends(spec) == ["ita_decode_pallas",
                                       "ita_onepass_pallas"]
    for name, verdict in ATT.backend_reasons(spec).items():
        if name not in ("ita_decode_pallas", "ita_onepass_pallas"):
            assert isinstance(verdict, str) and verdict, name
    q = jnp.asarray(_i8(1, 2, 1, 32))
    pool = jnp.asarray(_i8(3, 2, 64, 32))
    sc = ATT.QuantScales.per_tensor(S_Q, s_out=S_OUT)
    with pytest.raises(ValueError, match="page_table"):
        ATT.dispatch(q, pool, pool, spec=spec, scales=sc)
    with pytest.raises(ValueError, match="page_table"):
        ATT.dispatch(q, q, q, spec=spec.replace(layout="bhsd"), scales=sc,
                     page_table=jnp.zeros((1, 1), jnp.int32))


# ---------------------------------------------------------------------------
# State: logical ring equivalence + allocator properties
# ---------------------------------------------------------------------------

def _logical_view(p: PagedKVState, hd: int):
    """(B, capacity, G, hd) bytes the page tables map; the pool's lanes
    past ``hd`` (its minor dim is lane-padded) must hold zeros."""
    pt = np.asarray(p.page_table)
    g, lanes = p.k.shape[1], p.k.shape[3]
    # (B, n_pages, G, page, lanes) -> (B, n_pages * page, G, lanes)
    view = np.asarray(p.k)[pt].swapaxes(2, 3).reshape(p.batch, p.capacity,
                                                      g, lanes)
    assert not view[..., hd:].any(), "pool lanes past head_dim written"
    return view[..., :hd]


def test_paged_state_matches_ring_through_wrap():
    """Ragged prefill + appends past the wrap: the pool's logical view
    (pages gathered through the table) equals the ring byte-for-byte on
    every valid slot, and pos/valid_len/q_offset agree."""
    b, g, hd, page, cap = 3, 2, 4, 8, 32
    toks = _i8(b, 40, g, hd)
    lens = jnp.asarray([5, 12, 9], jnp.int32)
    ring = KVCacheState.init(b, cap, g, hd).prefill_write(
        jnp.asarray(toks[:, :12]), jnp.asarray(toks[:, :12]), lengths=lens)
    paged = PagedKVState.init(b, cap, g, hd, page_size=page).prefill_write(
        jnp.asarray(toks[:, :12]), jnp.asarray(toks[:, :12]), lengths=lens)
    # lazy allocation: a 5-token row holds 1 page, not the full window
    np.testing.assert_array_equal(np.asarray(paged.pages_held()), [1, 2, 2])

    for t in range(12, 40):
        ring = ring.decode_append(jnp.asarray(toks[:, t:t + 1]),
                                  jnp.asarray(toks[:, t:t + 1]))
        paged = paged.decode_append(jnp.asarray(toks[:, t:t + 1]),
                                    jnp.asarray(toks[:, t:t + 1]))
    np.testing.assert_array_equal(np.asarray(ring.pos),
                                  np.asarray(paged.pos))
    np.testing.assert_array_equal(np.asarray(ring.valid_len()),
                                  np.asarray(paged.valid_len()))
    np.testing.assert_array_equal(np.asarray(ring.q_offset(1)),
                                  np.asarray(paged.q_offset(1)))
    lv, rv = _logical_view(paged, hd), np.asarray(ring.k)
    for row in range(b):
        n, pos = int(ring.valid_len()[row]), int(ring.pos[row])
        for t in range(pos - n, pos):
            np.testing.assert_array_equal(
                lv[row, t % cap], rv[row, t % cap],
                err_msg=f"row {row} token {t}")


def _partition_ok(p: PagedKVState, pins=None, shared=False):
    """Invariant: {parking} ∪ free stack ∪ referenced pages partition the
    arena — no double-booking, no leaks — and every page's refcount
    equals its table references plus pins (``check_invariants``). With
    ``shared=False`` additionally requires exclusively-held pages (no
    page in two rows), the pre-sharing partition property."""
    pt = np.asarray(p.page_table)
    held_counts = np.asarray(p.pages_held())
    held = []
    for row in range(p.batch):
        held.extend(pt[row, :held_counts[row]].tolist())
    free = np.asarray(p.free_stack)[:int(p.free_top)].tolist()
    try:
        p.check_invariants(pins=pins)
    except AssertionError:
        return False
    if not shared and len(set(held)) != len(held):  # a page in two rows
        return False
    if 0 in held or 0 in free:                     # parking page leaked
        return False
    if pins:
        held.extend(pg for pg, c in pins.items() for _ in range(c))
    return set(held) | set(free) | {0} == set(range(p.num_pages))


def test_page_free_and_realloc_reuse():
    """Released pages return to the stack and are handed out again; the
    re-admitted row's bytes are exactly the new prompt (no stale state
    from the page's previous owner)."""
    b, g, hd, page, cap = 2, 2, 4, 8, 16
    p = PagedKVState.init(b, cap, g, hd, page_size=page)
    total_free = int(p.free_top)
    a = _i8(b, 12, g, hd)
    p = p.prefill_write(jnp.asarray(a), jnp.asarray(a))
    assert int(p.free_top) == total_free - 4
    assert _partition_ok(p)

    p = p.release(jnp.asarray([True, False]))
    assert int(p.free_top) == total_free - 2
    assert int(p.pos[0]) == 0 and int(p.pos[1]) == 12
    assert _partition_ok(p)

    # re-admit row 0 with a fresh prompt into the recycled pages
    fresh = _i8(1, 9, g, hd)
    p = p.write_prompts(jnp.asarray(fresh), jnp.asarray(fresh),
                        lengths=jnp.asarray([9]),
                        slots=jnp.asarray([0]))
    assert int(p.pos[0]) == 9 and _partition_ok(p)
    np.testing.assert_array_equal(_logical_view(p, hd)[0, :9], fresh[0])
    # row 1 untouched by the realloc
    np.testing.assert_array_equal(_logical_view(p, hd)[1, :12], a[1])


def test_allocator_partition_property_seeded():
    """Seeded property test: a random interleaving of admissions (into
    released rows), appends (with random live masks) and releases —
    including repeated and overlapping release masks — never
    double-books a page: the partition + refcount invariant holds at
    every step and re-releasing a released row moves nothing."""
    b, g, hd, page, cap = 4, 1, 4, 4, 16
    prng = np.random.default_rng(7)
    p = PagedKVState.init(b, cap, g, hd, page_size=page,
                          num_pages=b * (cap // page) + 1)
    active = np.zeros(b, bool)
    for op in range(120):
        kind = prng.integers(0, 4)
        if kind == 0:                              # admit into a free row
            free = np.flatnonzero(~active)
            if free.size:
                row = int(prng.choice(free))
                ln = int(prng.integers(1, cap + 1))
                tok = _i8(1, ln, g, hd)
                p = p.write_prompts(jnp.asarray(tok), jnp.asarray(tok),
                                    lengths=jnp.asarray([ln]),
                                    slots=jnp.asarray([row]))
                active[row] = True
        elif kind == 1 and active.any():           # masked decode append
            live = active & (prng.random(b) < 0.8)
            tok = _i8(b, 1, g, hd)
            p = p.decode_append(jnp.asarray(tok), jnp.asarray(tok),
                                live=jnp.asarray(live))
        elif kind == 2 and active.any():           # release some rows
            fin = active & (prng.random(b) < 0.4)
            if fin.any():
                p = p.release(jnp.asarray(fin))
                active &= ~fin
        elif kind == 3 and active.any():           # repeated + overlapping
            fin = active & (prng.random(b) < 0.4)
            if fin.any():
                p = p.release(jnp.asarray(fin))
                active &= ~fin
                top_before = int(p.free_top)
                # same mask again, then a superset that only adds rows
                # already released / never admitted: both no-ops
                p = p.release(jnp.asarray(fin))
                over = fin | (~active & (prng.random(b) < 0.5))
                p = p.release(jnp.asarray(over))
                assert int(p.free_top) == top_before, \
                    f"op {op}: double release pushed pages again"
        assert not bool(p.oversubscribed()), f"op {op}: pool overdrawn"
        assert _partition_ok(p), f"op {op}: partition violated"


def test_burst_and_overlong_append_match_ring():
    """Multi-token bursts (page-crossing, ring-wrapping, over-capacity)
    keep the paged pool's logical bytes equal to the ring's."""
    b, g, hd, page, cap = 1, 2, 4, 8, 16
    toks = _i8(b, 41, g, hd)
    ring = KVCacheState.init(b, cap, g, hd).prefill_write(
        jnp.asarray(toks[:, :15]), jnp.asarray(toks[:, :15]))
    paged = PagedKVState.init(b, cap, g, hd, page_size=page).prefill_write(
        jnp.asarray(toks[:, :15]), jnp.asarray(toks[:, :15]))
    for lo, hi in ((15, 19), (19, 21), (21, 41)):  # wraps; last > capacity
        ring = ring.decode_append(jnp.asarray(toks[:, lo:hi]),
                                  jnp.asarray(toks[:, lo:hi]))
        paged = paged.decode_append(jnp.asarray(toks[:, lo:hi]),
                                    jnp.asarray(toks[:, lo:hi]))
        np.testing.assert_array_equal(np.asarray(ring.pos),
                                      np.asarray(paged.pos))
        lv, rv = _logical_view(paged, hd), np.asarray(ring.k)
        pos, n = int(ring.pos[0]), int(ring.valid_len()[0])
        for t in range(pos - n, pos):
            np.testing.assert_array_equal(lv[0, t % cap], rv[0, t % cap],
                                          err_msg=f"token {t} after "
                                                  f"burst [{lo},{hi})")


def test_paged_state_is_pytree_and_jit_safe():
    p = PagedKVState.init(2, 16, 2, 4, page_size=8, per_head_scales=True)
    leaves = jax.tree.leaves(p)
    assert len(leaves) == 9                        # + ref_count
    shp = jax.eval_shape(lambda: PagedKVState.init(2, 16, 2, 4, page_size=8))
    assert isinstance(shp, PagedKVState) and shp.k_scale is None

    @jax.jit
    def step(c, t):
        return c.decode_append(t, t)

    out = step(p, jnp.ones((2, 1, 2, 4), jnp.int8))
    assert isinstance(out, PagedKVState)
    np.testing.assert_array_equal(np.asarray(out.pos), [1, 1])
    np.testing.assert_array_equal(np.asarray(out.pages_held()), [1, 1])


# ---------------------------------------------------------------------------
# Allocator bugfixes: scatter determinism + parking-page hygiene (ISSUE 6)
# ---------------------------------------------------------------------------

def _state_equal(a: PagedKVState, b: PagedKVState, msg=""):
    for f in ("k", "v", "page_table", "pos", "free_stack", "free_top",
              "ref_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{msg}{f}")


def test_allocator_ops_bit_deterministic_under_jit():
    """The duplicate-scatter regression: ragged prefill, an
    over-capacity burst ``decode_append`` under a live mask, a ragged
    ``append_chunk`` and a double ``release`` produce **bit-identical**
    state eager vs jit vs a second jit run. Masked/pad writes scatter to
    an out-of-bounds index and are dropped — with no duplicate targets
    (the old parking-page sink), nothing depends on an unspecified
    duplicate-scatter winner, and the parking page's bytes stay zero."""
    b, g, hd, page, cap = 3, 2, 4, 8, 16
    prng = np.random.default_rng(13)
    pre = prng.integers(-128, 128, (b, 10, g, hd)).astype(np.int8)
    burst = prng.integers(-128, 128, (b, cap + 5, g, hd)).astype(np.int8)
    chunk = prng.integers(-128, 128, (b, 6, g, hd)).astype(np.int8)
    lens = jnp.asarray([10, 4, 0], jnp.int32)
    live = jnp.asarray([True, False, True])
    n_new = jnp.asarray([2, 6, 0], jnp.int32)

    def run(p, k_pre, k_burst, k_chunk):
        p = p.write_prompts(k_pre, k_pre, lengths=lens)
        p = p.decode_append(k_burst, k_burst, live=live)   # > capacity
        p = p.append_chunk(k_chunk, k_chunk, n_new)
        p = p.release(jnp.asarray([True, False, False]))
        p = p.release(jnp.asarray([True, True, False]))    # overlapping
        return p

    def init():
        return PagedKVState.init(b, cap, g, hd, page_size=page)

    args = (jnp.asarray(pre), jnp.asarray(burst), jnp.asarray(chunk))
    eager = run(init(), *args)
    jitted = jax.jit(run)
    j1 = jitted(init(), *args)
    j2 = jitted(init(), *args)
    _state_equal(eager, j1, "eager vs jit: ")
    _state_equal(j1, j2, "jit run 1 vs 2: ")
    assert not np.asarray(j1.k[0]).any() and not np.asarray(j1.v[0]).any(), \
        "parking page bytes were written"
    assert _partition_ok(j1)


def test_write_prompts_dummy_rows_keep_parking_pristine():
    """Fixed-width admission dispatch: negative ``slots`` entries are
    dummy rows whose bytes must go *nowhere* — no page allocated, no
    byte written (the parking page stays all-zero), untargeted rows
    untouched — and no live row's table ever points at page 0."""
    b, g, hd, page, cap = 3, 2, 4, 8, 16
    p = PagedKVState.init(b, cap, g, hd, page_size=page)
    a = _i8(2, 12, g, hd)
    p = p.write_prompts(jnp.asarray(a), jnp.asarray(a),
                        lengths=jnp.asarray([12, 7]),
                        slots=jnp.asarray([0, 2]))
    snap_k = np.asarray(p.k).copy()
    dummy = _i8(2, 12, g, hd)
    p2 = p.write_prompts(jnp.asarray(dummy), jnp.asarray(dummy),
                         lengths=jnp.asarray([12, 9]),
                         slots=jnp.asarray([-1, -1]))
    np.testing.assert_array_equal(np.asarray(p2.k), snap_k,
                                  err_msg="dummy admission wrote bytes")
    np.testing.assert_array_equal(np.asarray(p2.pos), np.asarray(p.pos))
    assert int(p2.free_top) == int(p.free_top), "dummy row leaked a page"
    assert not np.asarray(p2.k[0]).any(), "parking page written"
    p2.check_invariants()
    pt = np.asarray(p2.page_table)
    held = np.asarray(p2.pages_held())
    for row in range(b):
        assert 0 not in pt[row, :held[row]].tolist(), \
            f"live row {row} points at the parking page"


# ---------------------------------------------------------------------------
# Prefix sharing: adopt_prefix + copy-on-write (state level, ISSUE 6)
# ---------------------------------------------------------------------------

def test_append_chunk_straddling_pages_during_neighbor_cow():
    """One ragged ``append_chunk`` whose row-0 chunk straddles three page
    boundaries and wraps onto its *shared* prefix pages, while the
    neighbor row copy-on-writes the same shared pages in the same call:
    logical bytes match (a) the identical tokens applied as sequential
    masked ``decode_append`` steps and (b) an unshared pool fed each
    row's full stream — and a shared page abandoned by *both* diverging
    rows at once returns to the free stack exactly once."""
    b, g, hd, page, npps = 2, 2, 4, 4, 4
    cap = page * npps                              # 16
    prng = np.random.default_rng(21)
    P = 2 * npps + 3                               # COW pop headroom

    def mk():
        return PagedKVState.init(b, cap, g, hd, page_size=page,
                                 num_pages=P)

    pre = prng.integers(-128, 128, (1, 8, g, hd)).astype(np.int8)
    shared = mk().write_prompts(jnp.asarray(pre), jnp.asarray(pre),
                                lengths=jnp.asarray([8]),
                                slots=jnp.asarray([0]))
    donor_pages = np.asarray(shared.page_table)[0, :2]
    shared = shared.adopt_prefix(jnp.asarray([1]),
                                 jnp.asarray(donor_pages[None, :]),
                                 jnp.asarray([2]), jnp.asarray([8]))
    np.testing.assert_array_equal(
        np.asarray(shared.ref_count)[donor_pages], [2, 2])
    assert _partition_ok(shared, shared=True)

    s = 13
    toks = prng.integers(-128, 128, (b, s, g, hd)).astype(np.int8)
    n_new = np.asarray([13, 9], np.int32)
    # row 0: slots 8..20 -> page boundaries at 12, 16 (the wrap) and 20,
    # landing on shared logical pages 0 and 1 -> COW both; row 1: slots
    # 8..16 -> COWs shared logical page 0 in the same dispatch. Both rows
    # abandon the donor copy of logical page 0 simultaneously.
    chunked = shared.append_chunk(jnp.asarray(toks), jnp.asarray(toks),
                                  jnp.asarray(n_new))
    assert _partition_ok(chunked)                  # fully diverged again

    # (a) sequential masked single-token appends from the same shared state
    ref = shared
    for t in range(s):
        ref = ref.decode_append(jnp.asarray(toks[:, t:t + 1]),
                                jnp.asarray(toks[:, t:t + 1]),
                                live=jnp.asarray(t < n_new))
    np.testing.assert_array_equal(np.asarray(chunked.pos),
                                  np.asarray(ref.pos))
    np.testing.assert_array_equal(np.asarray(chunked.pages_held()),
                                  np.asarray(ref.pages_held()))
    assert int(chunked.free_top) == int(ref.free_top)

    # (b) the unshared path: a fresh pool where each row owns its prefix
    prompts = np.broadcast_to(pre, (b, 8, g, hd))
    unshared = mk().write_prompts(jnp.asarray(prompts), jnp.asarray(prompts))
    unshared = unshared.append_chunk(jnp.asarray(toks), jnp.asarray(toks),
                                     jnp.asarray(n_new))
    lv_c, lv_r, lv_u = (_logical_view(x, hd)
                        for x in (chunked, ref, unshared))
    for row in range(b):
        n = int(chunked.valid_len()[row])
        pos = int(chunked.pos[row])
        for t in range(pos - n, pos):
            np.testing.assert_array_equal(
                lv_c[row, t % cap], lv_r[row, t % cap],
                err_msg=f"row {row} token {t}: chunked vs sequential")
            np.testing.assert_array_equal(
                lv_c[row, t % cap], lv_u[row, t % cap],
                err_msg=f"row {row} token {t}: shared vs unshared")


def test_shared_refcount_partition_property_seeded():
    """Seeded property test over admit / adopt / pin / unpin / ragged
    append (arming copy-on-write on wrap) / repeated-release cycles:
    after every op each page is on the free stack XOR referenced, each
    refcount equals its page-table references plus pins, the parking
    page stays untouched, and a stray decref of an already-free page is
    a guarded no-op."""
    b, g, hd, page, npps = 3, 1, 4, 4, 3
    cap = page * npps
    max_pins = 4
    P = b * npps + max_pins + 2
    prng = np.random.default_rng(17)
    p = PagedKVState.init(b, cap, g, hd, page_size=page, num_pages=P)
    active = np.zeros(b, bool)
    pins: dict = {}
    for op in range(160):
        kind = prng.integers(0, 6)
        if kind == 0:                              # admit a fresh row
            free = np.flatnonzero(~active)
            if free.size:
                row = int(prng.choice(free))
                ln = int(prng.integers(1, cap + 1))
                tok = _i8(1, ln, g, hd)
                p = p.write_prompts(jnp.asarray(tok), jnp.asarray(tok),
                                    lengths=jnp.asarray([ln]),
                                    slots=jnp.asarray([row]))
                active[row] = True
        elif kind == 1:                            # adopt a donor's prefix
            free = np.flatnonzero(~active)
            donors = [r for r in np.flatnonzero(active)
                      if int(np.asarray(p.pos)[r]) >= page]
            if free.size and donors:
                row = int(prng.choice(free))
                donor = int(prng.choice(donors))
                full = min(int(np.asarray(p.pos)[donor]) // page, npps)
                n_pg = int(prng.integers(1, full + 1))
                pages = np.asarray(p.page_table)[donor, :n_pg]
                p = p.adopt_prefix(jnp.asarray([row]),
                                   jnp.asarray(pages[None, :]),
                                   jnp.asarray([n_pg]),
                                   jnp.asarray([n_pg * page]))
                active[row] = True
        elif kind == 2 and active.any():           # ragged append, may COW
            live = active & (prng.random(b) < 0.8)
            width = int(prng.integers(1, page + 2))
            n_new = np.where(live, prng.integers(0, width + 1, b),
                             0).astype(np.int32)
            tok = _i8(b, width, g, hd)
            p = p.append_chunk(jnp.asarray(tok), jnp.asarray(tok),
                               jnp.asarray(n_new))
        elif kind == 3 and active.any():           # release, maybe twice
            fin = active & (prng.random(b) < 0.4)
            if fin.any():
                p = p.release(jnp.asarray(fin))
                active &= ~fin
                if prng.random() < 0.5:
                    p = p.release(jnp.asarray(fin))    # idempotent
        elif kind == 4 and len(pins) < max_pins:   # pin a held page
            cand: set = set()
            pt = np.asarray(p.page_table)
            held = np.asarray(p.pages_held())
            for r in np.flatnonzero(active):
                cand.update(pt[r, :held[r]].tolist())
            cand -= set(pins)
            if cand:
                pg = int(prng.choice(sorted(cand)))
                p = p.incref_pages(jnp.asarray([pg]))
                pins[pg] = 1
        elif kind == 5 and pins:                   # unpin (+ stray decref)
            pg = int(prng.choice(sorted(pins)))
            p = p.decref_pages(jnp.asarray([pg]))
            del pins[pg]
            if int(np.asarray(p.ref_count)[pg]) == 0 \
                    and prng.random() < 0.5:
                p = p.decref_pages(jnp.asarray([pg]))  # stray: guarded
        assert not bool(p.oversubscribed()), f"op {op}: pool overdrawn"
        try:
            p.check_invariants(pins=pins)
        except AssertionError as e:
            raise AssertionError(f"op {op}: {e}") from e


def test_prefix_index_lookup_register_evict():
    """PrefixIndex host semantics: chain-hashed page-granular lookup
    returns the longest registered prefix (partial pages never match),
    registration skips known chunks and halts on conflicts or the
    parking page, and LRU eviction respects the protected set while
    orphaned chain tails stay evictable."""
    from repro.attention import PrefixIndex
    idx = PrefixIndex(page_size=4)
    a = np.arange(12, dtype=np.int32)              # 3 full chunks
    assert idx.register(a, [5, 6, 7]) == [5, 6, 7]
    assert len(idx) == 3
    assert idx.lookup(a) == [5, 6, 7]
    assert idx.lookup(a[:11]) == [5, 6]            # partial page 3: no hit
    assert idx.lookup(a, max_tokens=9) == [5, 6]   # cap binds
    b2 = np.concatenate([a[:8], 90 + np.arange(4)]).astype(np.int32)
    assert idx.lookup(b2) == [5, 6]                # diverges at chunk 2
    c = np.concatenate([[99], a[1:]]).astype(np.int32)
    assert idx.lookup(c) == []                     # position-0 mismatch
    assert idx.register(a, [5, 6, 7]) == []        # all known: no new pins
    assert idx.register(b2, [5, 6, 9]) == [9]      # only the new tail
    assert idx.register(c, [0, 11]) == []          # parking page halts
    idx.lookup(b2)                                 # LRU-touch 5, 6, 9
    ev = idx.evict_lru(2, protected={7})
    assert ev == [5, 6] and 7 not in ev
    assert idx.lookup(b2) == []                    # chain head evicted
    assert 9 in idx.pinned_pages                   # orphaned tail ...
    assert sorted(idx.evict_lru(5)) == [7, 9]      # ... still evictable
    assert len(idx) == 0 and idx.pinned_pages == []


# ---------------------------------------------------------------------------
# Engine level: decode_attend over a paged cache
# ---------------------------------------------------------------------------

def test_paged_decode_attend_matches_ring_engine():
    """The float-in/int8-out engine path over a paged cache is
    bit-identical to the ring cache engine at block_kv == page_size."""
    b, hq, hkv, d, page, cap = 2, 4, 2, 32, 64, 128
    s, prefill = cap, 96
    qf = rng.normal(0, 1, (b, hq, s, d)).astype(np.float32)
    kf = rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32)
    vf = rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32)
    q8 = KV.quantize_with_scale(jnp.asarray(qf), S_Q)

    ring = KV.init_cache(b, cap, hkv, d, per_head_scales=True)
    paged = KV.init_paged_cache(b, cap, hkv, d, per_head_scales=True,
                                page_size=page)
    _, ring = KV.prefill_attend(ring, q8[:, :, :prefill],
                                jnp.asarray(kf[:, :prefill]),
                                jnp.asarray(vf[:, :prefill]), S_Q, S_OUT,
                                block_q=32, block_kv=page)
    _, paged = KV.prefill_attend(paged, q8[:, :, :prefill],
                                 jnp.asarray(kf[:, :prefill]),
                                 jnp.asarray(vf[:, :prefill]), S_Q, S_OUT,
                                 block_q=32, block_kv=page)
    for t in range(prefill, s):
        o_r, ring = KV.decode_attend(ring, q8[:, :, t:t + 1],
                                     jnp.asarray(kf[:, t:t + 1]),
                                     jnp.asarray(vf[:, t:t + 1]),
                                     S_Q, S_OUT, block_kv=page)
        o_p, paged = KV.decode_attend(paged, q8[:, :, t:t + 1],
                                      jnp.asarray(kf[:, t:t + 1]),
                                      jnp.asarray(vf[:, t:t + 1]),
                                      S_Q, S_OUT, block_kv=page)
        np.testing.assert_array_equal(np.asarray(o_r), np.asarray(o_p),
                                      err_msg=f"decode step t={t}")


# ---------------------------------------------------------------------------
# Satellite: ring block-alignment kills the decode pad-copy
# ---------------------------------------------------------------------------

def test_ring_capacity_block_aligned_at_init():
    assert KVCacheState.init(1, 144, 2, 4).capacity == MIN_BLOCK_KV * 2
    assert KVCacheState.init(1, 128, 2, 4).capacity == 128
    assert KVCacheState.init(1, 96, 2, 4).capacity == 96   # <= one block
    p = PagedKVState.init(1, 144, 2, 4, page_size=64)
    assert p.capacity == 192                               # page multiple


def test_decode_pad_copy_statically_forbidden():
    """A decode dispatch over a non-block-multiple ring above one block
    raises instead of silently pad-copying the ring every step."""
    b, h, d, cap = 1, 2, 32, 192
    q = jnp.asarray(_i8(b, h, 1, d))
    kv = jnp.asarray(_i8(b, h, cap, d))
    spec = ATT.AttentionSpec(mode="decode", impl="ita", layout="bhsd",
                             out_dtype="int8", q_len=1)
    sc = ATT.QuantScales.per_tensor(S_Q, s_out=S_OUT)
    with pytest.raises(ValueError, match="block_kv"):
        ATT.dispatch(q, kv, kv, spec=spec, scales=sc, q_offset=cap - 1,
                     kv_len=cap, backend="ita_decode_pallas", block_kv=80)
    # block-multiple capacities dispatch fine (the init-aligned case)
    out = ATT.dispatch(q, kv, kv, spec=spec, scales=sc, q_offset=cap - 1,
                       kv_len=cap, backend="ita_decode_pallas", block_kv=64)
    assert out.shape == (b, h, 1, d)


def test_block_defaults_recorded():
    from repro.kernels.common import (BLOCK_DEFAULTS, default_blocks,
                                      default_matmul_blocks)
    for name in ("ita_onepass_pallas", "ita_twopass_pallas",
                 "ita_decode_pallas"):
        assert name in BLOCK_DEFAULTS
        bq, bkv = default_blocks(name)
        assert bkv in (64, 128, 256)
    assert default_blocks("ita_decode_pallas")[0] is None  # no q tiling
    # the matmul entry is 3-wide and fenced off from default_blocks()
    assert len(default_matmul_blocks()) == 3
    with pytest.raises(AssertionError, match="default_matmul_blocks"):
        default_blocks("int8_matmul")


# ---------------------------------------------------------------------------
# Chunked prefill: append_chunk + ragged q_len mixed calls (ISSUE 5)
# ---------------------------------------------------------------------------

def test_append_chunk_equals_sequential_appends():
    """A ragged ``append_chunk`` (per-row n_new, one dispatch) is
    state-identical to applying the same tokens as single-token
    ``decode_append`` steps with live masks: same bytes, same pos, same
    pages held, allocator partition intact — including rows whose chunk
    crosses a page boundary and dead rows (n_new = 0)."""
    b, g, hd, page, cap = 3, 2, 4, 8, 32
    base = PagedKVState.init(b, cap, g, hd, page_size=page)
    pre = _i8(b, 6, g, hd)
    base = base.prefill_write(jnp.asarray(pre), jnp.asarray(pre),
                              lengths=jnp.asarray([6, 3, 0]))
    s = 12
    toks = _i8(b, s, g, hd)
    n_new = np.asarray([1, 12, 0], np.int32)       # decode / chunk / dead

    chunked = base.append_chunk(jnp.asarray(toks), jnp.asarray(toks),
                                jnp.asarray(n_new))
    ref = base
    for t in range(s):
        live = jnp.asarray(t < n_new)
        ref = ref.decode_append(jnp.asarray(toks[:, t:t + 1]),
                                jnp.asarray(toks[:, t:t + 1]), live=live)
    np.testing.assert_array_equal(np.asarray(chunked.pos),
                                  np.asarray(ref.pos))
    np.testing.assert_array_equal(np.asarray(chunked.pages_held()),
                                  np.asarray(ref.pages_held()))
    assert _partition_ok(chunked)
    lv_c, lv_r = _logical_view(chunked, hd), _logical_view(ref, hd)
    for row in range(b):
        n = int(chunked.valid_len()[row])
        pos = int(chunked.pos[row])
        for t in range(pos - n, pos):
            np.testing.assert_array_equal(
                lv_c[row, t % cap], lv_r[row, t % cap],
                err_msg=f"row {row} token {t}")
    with pytest.raises(ValueError, match="append_chunk width"):
        wide = _i8(b, cap + 1, g, hd)
        base.append_chunk(jnp.asarray(wide), jnp.asarray(wide),
                          jnp.asarray([1, 1, 1]))


def test_ragged_qlens_mixed_call_matches_pure_paths():
    """One ragged-q paged call carrying a decode row (q_len 1), a prefill
    chunk row (q_len = chunk) and a dead row (q_len 0) matches the pure
    decode kernel / one-shot onepass on the same streams; the dead row
    emits zeros."""
    b, g, hq, hd, page, npages = 3, 2, 4, 16, 32, 16
    scales = ATT.QuantScales.per_tensor(S_Q, s_out=S_OUT)
    pool = PagedKVState.init(b, 128, g, hd, page_size=page,
                             num_pages=npages)
    pre = _i8(b, 40, g, hd)
    pool = pool.prefill_write(jnp.asarray(pre), jnp.asarray(pre),
                              lengths=jnp.asarray([40, 17, 0]))
    chunk = 12
    kc = _i8(b, chunk, g, hd)
    n_new = jnp.asarray([1, chunk, 0])
    pool2 = pool.append_chunk(jnp.asarray(kc), jnp.asarray(kc), n_new)

    q = _i8(b, hq, chunk, hd)
    spec = ATT.AttentionSpec(mode="decode", impl="ita", layout="bhsd_paged",
                             out_dtype="int8", q_len=chunk, ragged_q=True)
    assert ATT.list_backends(spec) == ["ita_onepass_pallas"]
    out = ATT.dispatch(jnp.asarray(q), pool2.k, pool2.v, spec=spec,
                       scales=scales, q_offset=pool2.q_offset(n_new),
                       kv_len=pool2.valid_len(),
                       page_table=pool2.page_table, q_lens=n_new)

    # row 0 (decode): equals the single-query decode kernel on the pool
    dec_spec = spec.replace(q_len=1, ragged_q=False)
    dec = ATT.dispatch(jnp.asarray(q[:, :, :1]), pool2.k, pool2.v,
                       spec=dec_spec, scales=scales,
                       q_offset=pool2.q_offset(1), kv_len=pool2.valid_len(),
                       page_table=pool2.page_table,
                       backend="ita_decode_pallas")
    np.testing.assert_array_equal(np.asarray(out[0, :, 0]),
                                  np.asarray(dec[0, :, 0]))
    # row 2 (dead, q_len 0): all-zero output
    assert not np.asarray(out[2]).any()
    # row 1 (chunk): equals a one-shot onepass over the same stream
    full = np.concatenate([pre[1:2, :17], kc[1:2]], axis=1)
    solo = PagedKVState.init(1, 128, g, hd, page_size=page,
                             num_pages=npages)
    solo = solo.prefill_write(jnp.asarray(full), jnp.asarray(full))
    one_spec = spec.replace(ragged_q=False)
    one = ATT.dispatch(jnp.asarray(q[1:2]), solo.k, solo.v, spec=one_spec,
                       scales=scales, q_offset=17, kv_len=solo.valid_len(),
                       page_table=solo.page_table,
                       backend="ita_onepass_pallas")
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(one[0]))

    # dispatch handshake: q_lens required by exactly ragged_q specs
    with pytest.raises(ValueError, match="q_lens"):
        ATT.dispatch(jnp.asarray(q), pool2.k, pool2.v, spec=spec,
                     scales=scales, q_offset=pool2.q_offset(n_new),
                     kv_len=pool2.valid_len(), page_table=pool2.page_table)
    with pytest.raises(ValueError, match="q_lens"):
        ATT.dispatch(jnp.asarray(q), pool2.k, pool2.v, spec=one_spec,
                     scales=scales, q_offset=pool2.q_offset(n_new),
                     kv_len=pool2.valid_len(), page_table=pool2.page_table,
                     q_lens=n_new)


def test_ragged_q_capability_verdicts():
    """ragged_q is a capability of exactly the fused one-pass kernels:
    everything else declines with a reason, on serve specs it could
    otherwise run."""
    base = ATT.AttentionSpec(mode="decode", impl="ita", layout="bhsd_paged",
                             out_dtype="int8", q_len=16)
    assert ATT.list_backends(base.replace(ragged_q=True)) == \
        ["ita_onepass_pallas"]
    for impl, layout in (("ita", "bshd"), ("ibert", "bshd")):
        spec = ATT.AttentionSpec(mode="decode", impl=impl, layout=layout,
                                 q_len=4, ragged_q=True)
        for name, verdict in ATT.backend_reasons(spec).items():
            if name != "ita_onepass_pallas":
                assert verdict is not True, (name, impl)


# ---------------------------------------------------------------------------
# Preemption / prefix-sharing seam (ISSUE 8): release-decrefs-not-frees
# ---------------------------------------------------------------------------

def test_preempt_readmit_evict_cycles_keep_invariants_seeded():
    """Seeded property test over the serve loop's preemption cycle at
    state level: admit (adopting registered prefixes), register + pin
    full prompt pages, preempt (release a victim whose pages are pinned
    — must decref, never free), ragged decode appends, re-admit adopting
    the victim's pages back, and LRU-evict + unpin. After *every* op the
    refcount partition holds (``check_invariants(pins)``) and no pinned
    page sits on the free stack."""
    from repro.attention import PrefixIndex

    b, g, hd, page, cap = 3, 1, 4, 4, 16
    prng = np.random.default_rng(13)
    p = PagedKVState.init(b, cap, g, hd, page_size=page, num_pages=11)
    index = PrefixIndex(page)
    pins = {}
    # three 2-page prompt families: adoption + re-adoption actually hit
    fams = [prng.integers(0, 100, 2 * page).astype(np.int32)
            for _ in range(3)]
    tokens = [None] * b                  # host stream per row (like
    adopted = [[] for _ in range(b)]     # slot_prompt / slot_shared)

    def rand_kv(s):
        return jnp.asarray(prng.integers(-127, 128, (b, s, g, hd)),
                           jnp.int8)

    def checked(op):
        assert not bool(p.oversubscribed()), f"op {op}: pool overdrawn"
        p.check_invariants(pins=pins)
        free = set(np.asarray(p.free_stack)[:int(p.free_top)].tolist())
        assert not free & set(pins), \
            f"op {op}: pinned page on the free stack: {free & set(pins)}"

    for op in range(160):
        kind = int(prng.integers(0, 5))
        live = [r for r in range(b) if tokens[r] is not None]
        if kind == 0:                              # admit, adopting hits
            free_rows = [r for r in range(b) if tokens[r] is None]
            if not free_rows:
                continue
            row = int(prng.choice(free_rows))
            fam = fams[int(prng.integers(len(fams)))]
            tail = prng.integers(0, 100,
                                 int(prng.integers(1, 8))).astype(np.int32)
            stream = np.concatenate([fam, tail])
            sh = index.lookup(stream, max_tokens=stream.size - 1)
            rest = stream.size - len(sh) * page
            need = -(-stream.size // page) - len(sh)
            if need > int(p.free_top):
                continue                           # admission would gate
            if sh:
                pad = np.full((1, p.pages_per_seq), -1, np.int32)
                pad[0, :len(sh)] = sh
                p = p.adopt_prefix(jnp.asarray([row]), jnp.asarray(pad),
                                   jnp.asarray([len(sh)]),
                                   jnp.asarray([len(sh) * page]))
            n_new = np.zeros(b, np.int32)
            n_new[row] = rest
            p = p.append_chunk(rand_kv(rest), rand_kv(rest),
                               jnp.asarray(n_new))
            tokens[row], adopted[row] = stream, list(sh)
        elif kind == 1 and live:                   # register + pin
            row = int(prng.choice(live))
            full = int(np.asarray(p.pos)[row]) // page
            table = np.asarray(p.page_table)[row, :full]
            got = index.register(tokens[row], table)
            if got:
                pins.update((pg, 1) for pg in got)
                p = p.incref_pages(jnp.asarray(got, jnp.int32))
        elif kind == 2 and live:                   # preempt a victim
            row = int(prng.choice(live))
            mask = np.zeros(b, bool)
            mask[row] = True
            p = p.release(jnp.asarray(mask))
            tokens[row], adopted[row] = None, []
        elif kind == 3 and live:                   # ragged decode append
            row = int(prng.choice(live))
            ln = int(np.asarray(p.pos)[row])
            if ln >= cap or (ln % page == 0 and int(p.free_top) < 1):
                continue
            n_new = np.zeros(b, np.int32)
            n_new[row] = 1
            p = p.append_chunk(rand_kv(1), rand_kv(1), jnp.asarray(n_new))
            tokens[row] = np.concatenate(
                [tokens[row], prng.integers(0, 100, 1).astype(np.int32)])
        elif kind == 4 and len(index):             # LRU evict + unpin
            protected = {pg for lst in adopted for pg in lst}
            evicted = index.evict_lru(int(prng.integers(1, 3)), protected)
            for pg in evicted:
                pins.pop(pg, None)
            if evicted:
                p = p.decref_pages(jnp.asarray(evicted, jnp.int32))
        checked(op)
    # drain: release everything, evict every pin -> the pool is whole
    p = p.release(jnp.asarray([tokens[r] is not None for r in range(b)]))
    tokens, adopted = [None] * b, [[] for _ in range(b)]
    evicted = index.evict_lru(len(index))
    for pg in evicted:
        pins.pop(pg, None)
    if evicted:
        p = p.decref_pages(jnp.asarray(evicted, jnp.int32))
    checked("drain")
    assert not pins and int(p.free_top) == 10, \
        "pages leaked through the preempt/pin cycle"


# ---------------------------------------------------------------------------
# ita_kv_write: the in-place pool write against the XLA scatter it replaced
# ---------------------------------------------------------------------------

def _xla_append(p: PagedKVState, k_q, v_q, n):
    """``append_chunk``'s bookkeeping with the XLA writes the in-place
    kernel replaced: each copy-on-write page as ``k.at[dst].set(k[src])``,
    then the ``(page, head, slot)`` scatter of the real rows (dead rows
    and columns past ``n`` dropped)."""
    ps, cs = p.page_size, p.capacity
    b, s = k_q.shape[:2]
    n = jnp.asarray(n, jnp.int32)
    state, (src, dst) = p._cow(p.pos, n, s)
    held = state.pages_held()
    want = jnp.minimum(-(-(state.pos + n) // ps), state.pages_per_seq)
    new = state._alloc(want - held)
    dst = jnp.where(dst == 0, p.num_pages, dst)          # parking: no copy
    k, v = p.k.at[dst].set(p.k[src], mode="drop"), \
        p.v.at[dst].set(p.v[src], mode="drop")
    cols = jnp.arange(s, dtype=jnp.int32)[None, :]
    toks = (state.pos[:, None] + cols) % cs
    phys = jnp.where(cols < n[:, None],
                     new.page_table[jnp.arange(b)[:, None], toks // ps],
                     p.num_pages)
    lanes = [(0, 0)] * 3 + [(0, p.k.shape[-1] - k_q.shape[-1])]
    k = k.at[phys, :, toks % ps].set(jnp.pad(k_q, lanes), mode="drop")
    v = v.at[phys, :, toks % ps].set(jnp.pad(v_q, lanes), mode="drop")
    return dataclasses.replace(new, k=k, v=v, pos=state.pos + n)


def _kv_write_case(case):
    """(pool, K rows, V rows, n, chunked) for one write pattern: page 64
    (two 32-row write tiles), two pages per row."""
    b, g, hd, page, cap = 3, 2, 4, 64, 128
    prng = np.random.default_rng(sum(map(ord, case)))

    def rows(n, s):
        return prng.integers(-128, 128, (n, s, g, hd)).astype(np.int8)

    p = PagedKVState.init(b, cap, g, hd, page_size=page,
                          num_pages=2 * b + 3)
    if case == "cow-shared-page":
        # row 1 adopts row 0's first page, then its chunk wraps onto it
        p = p.write_prompts(jnp.asarray(rows(1, 64)), jnp.asarray(
            rows(1, 64)), lengths=jnp.asarray([64]), slots=jnp.asarray([0]))
        p = p.adopt_prefix(jnp.asarray([1]), p.page_table[:1, :1],
                           jnp.asarray([1]), jnp.asarray([64]))
        p = p.append_chunk(jnp.asarray(rows(b, 36)), jnp.asarray(
            rows(b, 36)), jnp.asarray([0, 36, 0]))
        assert int(p.ref_count[p.page_table[1, 0]]) == 2
        return p, rows(b, 40), rows(b, 40), [9, 40, 0], True
    lengths = {"decode": [32, 63, 31], "decode-dead-rows": [32, 63, 31],
               "chunk-across-page": [30, 60, 10],
               "wrap": [120, 127, 100]}[case]
    pre = rows(b, max(lengths))
    p = p.prefill_write(jnp.asarray(pre), jnp.asarray(pre),
                        lengths=jnp.asarray(lengths))
    if case == "decode":
        return p, rows(b, 1), rows(b, 1), [1, 1, 1], False
    if case == "decode-dead-rows":
        return p, rows(b, 1), rows(b, 1), [1, 0, 1], False
    if case == "chunk-across-page":
        return p, rows(b, 40), rows(b, 40), [40, 3, 0], True
    return p, rows(b, 16), rows(b, 16), [16, 9, 16], True      # wrap


@pytest.mark.parametrize("case", ["decode", "decode-dead-rows",
                                  "chunk-across-page", "wrap",
                                  "cow-shared-page"])
def test_kv_write_matches_xla_scatter(case):
    """``decode_append`` / ``append_chunk`` through ``ita_kv_write``
    leave the pool and its bookkeeping bit-identical to the XLA scatter
    path: decode rows, dead rows, ragged chunks across tile and page
    boundaries with padded columns, ring wraps, and a copy-on-write of a
    shared page in the same call as the rows written into it."""
    p, k_q, v_q, n, chunked = _kv_write_case(case)
    if chunked:
        got = p.append_chunk(jnp.asarray(k_q), jnp.asarray(v_q),
                             jnp.asarray(n))
    else:
        got = p.decode_append(jnp.asarray(k_q), jnp.asarray(v_q),
                              live=jnp.asarray(n) > 0)
    want = _xla_append(p, jnp.asarray(k_q), jnp.asarray(v_q), n)
    for name in ("k", "v", "page_table", "pos", "free_stack", "free_top",
                 "ref_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=f"{case}: {name}")
    assert not np.asarray(got.k[0]).any(), "parking page written"
    got.check_invariants()


@pytest.mark.parametrize("ragged", [False, True], ids=["decode", "ragged"])
def test_paged_kernels_read_stacked_pool_at_layer(ragged):
    """The paged kernels on a pool stacked over layers, read at layer
    ``i``, equal the same call on that layer's pool alone, bit for bit —
    the layer only moves the index maps."""
    b, g, hq, hd, page, sq = 2, 2, 4, 16, 32, 8
    pools = [PagedKVState.init(b, 96, g, hd, page_size=page, num_pages=7)
             for _ in range(3)]
    pre = _i8(3, b, 70, g, hd)
    pools = [p.prefill_write(jnp.asarray(x), jnp.asarray(x[:, ::-1]),
                             lengths=jnp.asarray([70, 41]))
             for p, x in zip(pools, pre)]
    k_stack = jnp.stack([p.k for p in pools])
    v_stack = jnp.stack([p.v for p in pools])
    q_len = sq if ragged else 1
    spec = ATT.AttentionSpec(mode="decode", impl="ita", layout="bhsd_paged",
                             out_dtype="int8", q_len=q_len, ragged_q=ragged)
    q = jnp.asarray(_i8(b, hq, q_len, hd))
    scales = ATT.QuantScales.per_tensor(S_Q, s_out=S_OUT)
    n_new = jnp.asarray([1, sq]) if ragged else None
    for layer, p in enumerate(pools):
        kw = dict(spec=spec, scales=scales, q_offset=p.q_offset(q_len),
                  kv_len=p.valid_len(), page_table=p.page_table,
                  q_lens=n_new)
        one = ATT.dispatch(q, p.k, p.v, **kw)
        stacked = ATT.dispatch(q, k_stack, v_stack, layer=layer, **kw)
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(one),
                                      err_msg=f"layer {layer}")
    with pytest.raises(ValueError, match="rank"):
        ATT.dispatch(q, k_stack, v_stack, **kw)


@pytest.mark.parametrize("admission", ["chunked", "stall"])
def test_serve_on_lane_padded_pool_matches_solo_generate(admission):
    """Served through the in-place pool writes, at a head_dim the pool
    pads to whole lanes (24 of 128), every request's greedy tokens equal
    generating it alone: the writes and the layer-indexed kernels change
    nothing about a sequence's arithmetic."""
    from repro.configs.base import ModelConfig
    from repro.models import init_model
    from repro.runtime.generate import (ServeRequest, generate,
                                        serve_continuous)
    cfg = ModelConfig(name="kvwrite-lanes", family="dense", d_model=48,
                      n_heads=4, n_kv_heads=2, head_dim=24, d_ff=96,
                      vocab_size=128, layer_groups=((("attn",), 2),),
                      dtype="float32", attention_impl="ita",
                      attention_backend="ita_onepass_pallas")
    params = init_model(jax.random.PRNGKey(1), cfg)
    prng = np.random.default_rng(5)
    reqs = [ServeRequest(
        prompt=prng.integers(0, cfg.vocab_size, int(n)).astype(np.int32),
        gen=int(g), arrival=int(a))
        for n, g, a in ((9, 6, 0), (40, 3, 0), (3, 8, 1), (21, 5, 2))]
    res = serve_continuous(params, cfg, reqs, slots=2, segment=4,
                           max_len=128, page_size=128, admission=admission,
                           chunk_size=16)
    assert len(res.completed) == len(reqs)
    for c in res.completed:
        r = reqs[c.index]
        solo = generate(params, cfg, jnp.asarray(r.prompt)[None], r.gen,
                        max_len=128)
        np.testing.assert_array_equal(np.asarray(c.tokens),
                                      np.asarray(solo.tokens)[0],
                                      err_msg=f"request {c.index}")
