"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode; integer results must match EXACTLY). Kernels are driven
through the public engine (``repro.attention.dispatch`` with explicit
``backend=`` overrides) — the registry is the only entry point."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import attention as ATT
from repro.core.quant import EPS_MAX
from repro.kernels.int8_matmul.ops import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref
from repro.kernels.ita_attention import ref as AR
from repro.kernels.ita_softmax.ops import ita_softmax
from repro.kernels.ita_softmax.ref import ita_softmax_ref

rng = np.random.default_rng(0)


def _i8(*shape):
    return rng.integers(-128, 128, shape, dtype=np.int8)


def fused(q, k, v, s_q, s_k, s_v, s_out, *, kind, causal=True, window=0,
          q_offset=0, kv_len=None, adaptive=True, block_q=128,
          block_kv=128):
    """Drive one fused Pallas backend via the registry (kernel layout,
    int8 in / int8-at-s_out out)."""
    spec = ATT.AttentionSpec(
        mode="decode" if kind == "decode" else "prefill", impl="ita",
        causal=causal, window=window,
        softmax="adaptive" if adaptive else "paper", layout="bhsd",
        out_dtype="int8",
        q_len=q.shape[2] if kind == "decode" else None)
    return ATT.dispatch(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), spec=spec,
        scales=ATT.QuantScales(s_q, s_k, s_v, s_out), q_offset=q_offset,
        kv_len=kv_len, backend=f"ita_{kind}_pallas", block_q=block_q,
        block_kv=block_kv)


# ---------------------------------------------------------------------------
# int8 weight-stationary matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (8, 32, 16), (100, 200, 96),
    pytest.param(256, 128, 128, marks=pytest.mark.slow),
    (33, 65, 17)])
@pytest.mark.parametrize("schedule", ["tpu", "weight_stationary"])
def test_int8_matmul_sweep(m, k, n, schedule):
    x, w = _i8(m, k), _i8(k, n)
    b = rng.integers(-1000, 1000, (n,), dtype=np.int32)
    mult = np.float32(0.002)
    ref = int8_matmul_ref(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
                          jnp.broadcast_to(mult, (n,)))
    out = int8_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), mult,
                      block_m=32, block_n=16, block_k=32, schedule=schedule)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_int8_matmul_per_channel_and_batched():
    x = _i8(2, 3, 40)                       # leading batch dims
    w = _i8(40, 24)
    mult = rng.uniform(1e-4, 1e-2, (24,)).astype(np.float32)
    out = int8_matmul(jnp.asarray(x), jnp.asarray(w), None,
                      jnp.asarray(mult), block_m=8, block_n=8, block_k=8)
    ref = int8_matmul_ref(jnp.asarray(x.reshape(6, 40)), jnp.asarray(w),
                          jnp.zeros((24,), jnp.int32), jnp.asarray(mult))
    np.testing.assert_array_equal(np.asarray(out).reshape(6, 24),
                                  np.asarray(ref))


# ---------------------------------------------------------------------------
# standalone streaming softmax kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,c,bc", [
    (16, 128, 64), (48, 300, 128), (8, 64, 64),
    pytest.param(128, 512, 128, marks=pytest.mark.slow)])
@pytest.mark.parametrize("adaptive", [False, True])
def test_ita_softmax_kernel_sweep(r, c, bc, adaptive):
    x = _i8(r, c)
    mask = (rng.random((r, c)) > 0.2).astype(np.int8)
    out = ita_softmax(jnp.asarray(x), jnp.asarray(mask), block_r=16,
                      block_c=bc, adaptive=adaptive)
    pad = (-c) % bc
    xp = np.pad(x, ((0, 0), (0, pad)))
    mp = np.pad(mask, ((0, 0), (0, pad)))
    ref = ita_softmax_ref(jnp.asarray(xp), jnp.asarray(mp),
                          num_parts=(c + pad) // bc, adaptive=adaptive)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref)[:, :c])


# ---------------------------------------------------------------------------
# fused attention kernels
# ---------------------------------------------------------------------------

SQ = np.float32(0.05)
SO = np.float32(0.02)


def _attn_ref(q, k, v, causal, window, mode, adaptive, bkv, q_offset=0):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    lmult = np.float32(SQ * SQ / (np.sqrt(d) * EPS_MAX))
    omult = np.float32(SQ / SO)
    return AR.ita_attention_stream_ref(
        jnp.asarray(q.reshape(b * h, sq, d)),
        jnp.asarray(k.reshape(b * h, skv, d)),
        jnp.asarray(v.reshape(b * h, skv, d)),
        lmult, omult, skv, causal=causal, window=window, adaptive=adaptive,
        block_kv=bkv, kind=mode, q_offset=q_offset)


@pytest.mark.parametrize("mode", ["onepass", "twopass"])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 48)])
@pytest.mark.parametrize("sq,skv", [
    (64, 192), (32, 32),
    pytest.param(128, 256, marks=pytest.mark.slow)])
def test_ita_attention_sweep(mode, causal, window, sq, skv):
    b, h, d = 2, 2, 64
    q, k, v = _i8(b, h, sq, d), _i8(b, h, skv, d), _i8(b, h, skv, d)
    out = fused(q, k, v, SQ, SQ, SQ, SO, causal=causal, window=window,
                kind=mode, adaptive=True, block_q=32, block_kv=64)
    ref = _attn_ref(q, k, v, causal, window, mode, True, 64)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(b * h, sq, d), np.asarray(ref))


def test_ita_attention_gqa_and_decode():
    b, hq, hkv, d, skv = 1, 8, 2, 64, 512
    q, k, v = _i8(b, hq, 1, d), _i8(b, hkv, skv, d), _i8(b, hkv, skv, d)
    out = fused(q, k, v, SQ, SQ, SQ, SO, q_offset=skv - 1, causal=True,
                kind="onepass", block_q=8, block_kv=128)
    kr = np.repeat(k, 4, axis=1)
    vr = np.repeat(v, 4, axis=1)
    ref = _attn_ref(q, kr, vr, True, 0, "onepass", True, 128,
                    q_offset=skv - 1)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(b * hq, 1, d), np.asarray(ref))


def test_twopass_matches_paper_oneshot_single_tile():
    """Single kv tile -> streaming == one-shot paper semantics exactly."""
    b, h, s, d = 1, 2, 64, 64
    q, k, v = _i8(b, h, s, d), _i8(b, h, s, d), _i8(b, h, s, d)
    out = fused(q, k, v, SQ, SQ, SQ, SO, causal=True, kind="twopass",
                adaptive=False, block_q=64, block_kv=64)
    lmult = np.float32(SQ * SQ / (np.sqrt(d) * EPS_MAX))
    ref, _ = AR.ita_attention_ref(
        jnp.asarray(q.reshape(b * h, s, d)), jnp.asarray(k.reshape(b * h, s, d)),
        jnp.asarray(v.reshape(b * h, s, d)), lmult, np.float32(SQ / SO), s,
        causal=True, adaptive=False)
    np.testing.assert_array_equal(np.asarray(out).reshape(b * h, s, d),
                                  np.asarray(ref))


# ---------------------------------------------------------------------------
# cross-implementation parity: Pallas kernels ≡ jnp oracle ≡ chunked XLA path
# ---------------------------------------------------------------------------

PARITY_CASES = [
    # hq, hkv, causal, window, kv_len   (skv=128, block_kv=64: 2 kv tiles)
    (4, 4, True, 0, None),              # causal MHA
    (4, 2, True, 0, None),              # GQA
    (4, 2, True, 48, None),             # GQA + sliding window
    pytest.param(2, 2, False, 0, None,  # bidirectional (encoder)
                 marks=pytest.mark.slow),
    (4, 4, True, 0, 100),               # masked tail (padded-seq serving)
]


@pytest.mark.parametrize("hq,hkv,causal,window,kv_len", PARITY_CASES)
def test_kernel_ref_chunked_parity(hq, hkv, causal, window, kv_len):
    """onepass ≡ twopass' stream oracle ≡ chunked ``ita_int`` across
    causal/window/GQA/masked shapes.

    - onepass / twopass: exact (bit-identical to the streaming oracle at
      matching tile size).
    - chunked ``ita_int`` (repro.attention.chunked): same DA/DI at
      chunk granularity but clips the ``u = 128>>k`` numerator to 127 so
      A·V rides the int8 MXU — max-element terms differ by ≤ 1/128, so
      parity there is near-exact on the int8 output grid, not bitwise.
    """
    from repro.attention.chunked import streaming_attention

    b, sq, skv, d, bkv = 2, 64, 128, 32, 64
    q = _i8(b, hq, sq, d)
    k = _i8(b, hkv, skv, d)
    v = _i8(b, hkv, skv, d)
    eff_kv = skv if kv_len is None else kv_len
    lmult = np.float32(SQ * SQ / (np.sqrt(d) * EPS_MAX))
    omult = np.float32(SQ / SO)

    kr = np.repeat(k, hq // hkv, axis=1)
    vr = np.repeat(v, hq // hkv, axis=1)
    ref = np.asarray(AR.ita_attention_stream_ref(
        jnp.asarray(q.reshape(b * hq, sq, d)),
        jnp.asarray(kr.reshape(b * hq, skv, d)),
        jnp.asarray(vr.reshape(b * hq, skv, d)),
        lmult, omult, eff_kv, causal=causal, window=window, adaptive=True,
        block_kv=bkv, kind="onepass")).reshape(b, hq, sq, d)

    for mode in ("onepass", "twopass"):
        out = np.asarray(fused(
            q, k, v, SQ, SQ, SQ, SO, kv_len=eff_kv, causal=causal,
            window=window, kind=mode, adaptive=True, block_q=32,
            block_kv=bkv))
        if mode == "onepass":
            np.testing.assert_array_equal(out, ref, err_msg=mode)
        else:
            ref2 = np.asarray(AR.ita_attention_stream_ref(
                jnp.asarray(q.reshape(b * hq, sq, d)),
                jnp.asarray(kr.reshape(b * hq, skv, d)),
                jnp.asarray(vr.reshape(b * hq, skv, d)),
                lmult, omult, eff_kv, causal=causal, window=window,
                adaptive=True, block_kv=bkv,
                kind="twopass")).reshape(b, hq, sq, d)
            np.testing.assert_array_equal(out, ref2, err_msg=mode)

    # chunked XLA path (model layout (B,S,H,hd)); requant to the s_out grid
    chunk = streaming_attention(
        jnp.asarray(q.transpose(0, 2, 1, 3)),
        jnp.asarray(k.transpose(0, 2, 1, 3)),
        jnp.asarray(v.transpose(0, 2, 1, 3)),
        impl="ita_int", scale=d ** -0.5, s_q=SQ, s_k=SQ, s_v=SQ,
        causal=causal, window=window, kv_len=eff_kv, q_chunk=32,
        kv_chunk=bkv)
    chunk_i8 = np.clip(np.round(np.asarray(chunk) / SO), -128, 127
                       ).transpose(0, 2, 1, 3).astype(np.int64)
    diff = np.abs(chunk_i8 - ref.astype(np.int64))
    assert diff.max() <= 1, diff.max()          # u-clip skew: ≤ 1 LSB
    assert (diff > 0).mean() < 0.12, (diff > 0).mean()


def test_attention_accuracy_vs_float():
    """End-to-end: ITA integer attention approximates float attention on
    realistically-scaled inputs (the paper's Fig. 5 effect)."""
    b, h, s, d = 2, 4, 128, 64
    qf = rng.normal(0, 1, (b, h, s, d)).astype(np.float32)
    kf = rng.normal(0, 1, (b, h, s, d)).astype(np.float32)
    vf = rng.normal(0, 1, (b, h, s, d)).astype(np.float32)
    s_act = np.float32(3.0 / 127)
    q8 = np.clip(np.round(qf / s_act), -128, 127).astype(np.int8)
    k8 = np.clip(np.round(kf / s_act), -128, 127).astype(np.int8)
    v8 = np.clip(np.round(vf / s_act), -128, 127).astype(np.int8)
    out8 = fused(q8, k8, v8, s_act, s_act, s_act, np.float32(2.0 / 127),
                 causal=True, kind="onepass")
    out = np.asarray(out8).astype(np.float32) * (2.0 / 127)
    ref = np.asarray(AR.float_attention_ref(
        jnp.asarray(qf.reshape(b * h, s, d)),
        jnp.asarray(kf.reshape(b * h, s, d)),
        jnp.asarray(vf.reshape(b * h, s, d)), causal=True))
    rel = np.abs(out.reshape(b * h, s, d) - ref).mean() / np.abs(ref).mean()
    assert rel < 0.25, rel


@pytest.mark.parametrize("kind", ["onepass", "decode"])
def test_float_output_is_the_int8_requant_without_its_clip(kind):
    """Without ``s_out`` the fused kernels emit the f32 attention output
    (dequantized through ``s_v``): within half a step of the int8 result
    wherever that is not clipped, and past the clip where it is — what a
    float spec gets instead of an output cut at ``127 * s_out``."""
    from repro.kernels.ita_attention.ops import fused_attention
    b, h, d, skv = 2, 2, 64, 384
    sq = 1 if kind == "decode" else 64
    q, k, v = _i8(b, h, sq, d), _i8(b, h, skv, d), _i8(b, h, skv, d)
    s_out = np.float32(0.002)               # small: many outputs clip
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), SQ, SQ, SQ)
    kw = dict(kind=kind, q_offset=skv - sq, causal=True, block_q=32,
              block_kv=128)
    i8 = np.asarray(fused_attention(*args, s_out, **kw)).astype(np.float64)
    f32 = np.asarray(fused_attention(*args, None, **kw))
    assert f32.dtype == np.float32
    steps = f32.astype(np.float64) / s_out
    inside, past = np.abs(steps) < 126, np.abs(steps) > 128
    assert np.abs(steps - i8)[inside].max() <= 0.5 + 1e-3
    assert past.any() and (np.abs(i8[past]) >= 127).all()
