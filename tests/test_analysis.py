"""Static analysis: the integer-range verifier and the jit-hygiene lints.

Coverage contract (ISSUE 7 acceptance):
- the interval domain's transfer helpers are exact on their corner
  cases (truncating division, logical shifts of negative bit patterns,
  count-leading-zeros);
- the analyzer proves no-overflow for the certified softmax cases and
  the proven bounds match a golden snapshot — the certificate is a
  regression artifact, not just a boolean;
- the verifier has teeth: seeded mutants (a dropped requant clip, a
  dropped shift clamp, a widened softmax numerator) each flip their
  case to FAIL with the expected finding kind;
- ``serve_continuous`` over a mixed chunked trace compiles a bounded,
  asserted number of segment variants, each exactly once, with every
  donated carry actually aliased (the PR-5 pow2-rounding and PR-3
  donation contracts).
"""

import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import analyze_jaxpr, build_matrix, run_case
from repro.analysis.intervals import (Interval, clz, div_int, dtype_range,
                                      fits, point, shift_right_logical)

# ---------------------------------------------------------------------------
# Interval domain corner cases
# ---------------------------------------------------------------------------

def test_div_int_truncation_corners():
    # trunc-toward-zero: -7 // 2 == -3 (lax.div), not python's -4
    out, had_zero = div_int(Interval(-7, -7), Interval(2, 2))
    assert not had_zero and (out.lo, out.hi) == (-3, -3)
    out, had_zero = div_int(Interval(-10, 9), Interval(3, 5))
    assert not had_zero
    assert out.contains(point(-10 // 3 + 1))    # -3 (truncated)
    assert out.contains(point(3)) and out.lo == -3 and out.hi == 3
    out, had_zero = div_int(Interval(1, 8), Interval(-2, 2))
    assert had_zero                             # divisor straddles zero


def test_shift_right_logical_negative_patterns():
    # shift 0 is the identity even for negatives
    out = shift_right_logical(Interval(-5, 7), Interval(0, 0), 32)
    assert (out.lo, out.hi) == (-5, 7)
    # shift >= 1 reinterprets the sign bit: bound is (2^32-1) >> s
    out = shift_right_logical(Interval(-1, -1), Interval(1, 1), 32)
    assert out.hi == (1 << 31) - 1              # 0xFFFFFFFF >> 1
    assert out.lo == 0
    # non-negative values shift exactly
    out = shift_right_logical(Interval(128, 128), Interval(2, 5), 32)
    assert (out.lo, out.hi) == (4, 32)


def test_clz_bounds():
    assert clz(point(1), 32) == point(31)
    assert clz(point(0), 32) == point(32)
    out = clz(Interval(1, 1 << 20), 32)
    assert (out.lo, out.hi) == (11, 31)
    assert clz(Interval(-5, -1), 32) == point(0)   # sign bit set


def test_dtype_fit():
    assert fits(Interval(-128, 127), jnp.int8)
    assert not fits(Interval(-129, 0), jnp.int8)
    assert dtype_range(jnp.int32).hi == (1 << 31) - 1


# ---------------------------------------------------------------------------
# Analyzer end-to-end on synthetic jaxprs
# ---------------------------------------------------------------------------

def test_analyzer_proves_clipped_matmul_and_flags_unclipped():
    def clipped(x, y):
        acc = jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return jnp.clip(acc, -128, 127).astype(jnp.int8)

    def unclipped(x, y):
        acc = jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(jnp.int8)

    args = (jax.ShapeDtypeStruct((4, 64), jnp.int8),
            jax.ShapeDtypeStruct((64, 4), jnp.int8))
    seeds = [Interval(-128, 127), Interval(-128, 127)]

    res = analyze_jaxpr(jax.make_jaxpr(clipped)(*args), seeds)
    assert res.ok and res.max_int_magnitude == 64 * 128 * 128

    res = analyze_jaxpr(jax.make_jaxpr(unclipped)(*args), seeds)
    assert not res.ok
    assert [f.kind for f in res.findings] == ["narrowing"]


def test_analyzer_flags_int32_product_overflow():
    def f(x):
        return x * x                            # (2^20)^2 >> int32

    res = analyze_jaxpr(
        jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8,), jnp.int32)),
        [Interval(-(1 << 20), 1 << 20)])
    assert not res.ok
    assert res.findings[0].kind == "overflow"
    assert res.findings[0].prim == "mul"


# ---------------------------------------------------------------------------
# Golden range-report snapshot: the ita_softmax certificates
# ---------------------------------------------------------------------------

# (ok, proven output intervals, widest |int| bound, unproven-op count)
# for the smoke geometry. The 2^27-1 max is the *unclamped* DA exponent
# k = (max - x) >> 5 before its min(k, 31) clamp — the analyzer cannot
# know max >= x (relational), so the logical shift of a possibly-
# negative diff spans [0, (2^32-1) >> 5]; everything downstream of the
# clamp is tight. Changing any of these numbers means the proven range
# behaviour of the softmax changed — that is a semantics review, not a
# snapshot refresh.
SOFTMAX_GOLDEN = {
    "ita_softmax_pallas/paper": (True, [[0.0, 256.0]], (1 << 27) - 1, 0),
    "ita_softmax_pallas/adaptive": (True, [[0.0, 256.0]], (1 << 27) - 1, 0),
    "ita_softmax_ref/paper": (
        True, [[0, 256], [1, 32768], [-256, 127]], (1 << 27) - 1, 0),
    "ita_softmax_ref/adaptive": (
        True, [[0, 256], [0, 15], [-256, 127]], (1 << 27) - 1, 0),
    # the fused kernels' grid-aligned arithmetic: k is a difference of
    # buckets in [-8, 3], clipped to [0, 31], so no logical shift of a
    # negative difference appears; the widest value is the 2^16 dividend
    "ita_softmax_ref/aligned": (
        True, [[0, 256], [0, 32768], [-8, 3]], 1 << 16, 0),
    "ita_softmax_ref/aligned-streaming": (True, [[0.0, 256.0]], 1 << 16, 0),
}


def _case(name, smoke=True):
    matches = [c for c in build_matrix(smoke=smoke) if c.name == name]
    assert len(matches) == 1, name
    return matches[0]


@pytest.mark.parametrize("name", sorted(SOFTMAX_GOLDEN))
def test_softmax_range_report_matches_golden(name):
    r = run_case(_case(name))
    ok, out, mag, unproven = SOFTMAX_GOLDEN[name]
    assert r["ok"] == ok, r.get("findings", r.get("error"))
    assert r["out"] == out
    assert r["max_int_magnitude"] == mag
    assert r["n_unproven"] == unproven
    assert json.dumps(r)                        # JSON-serializable artifact


# ---------------------------------------------------------------------------
# Teeth: seeded mutants must flip their certificate to FAIL
# ---------------------------------------------------------------------------

def test_mutant_dropped_requant_clip_is_flagged(monkeypatch):
    """Remove the int8 clip from the QK requant: the two-pass kernel's
    int8 logit store is no longer proven in range -> narrowing."""
    import repro.kernels.ita_attention.kernel as K

    def qk_noclip(q_tile, k_tile, mult):
        acc = jax.lax.dot_general(q_tile, k_tile, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return jnp.round(acc.astype(jnp.float32) * mult).astype(jnp.int32)

    monkeypatch.setattr(K, "_qk_logits", qk_noclip)
    r = run_case(_case("ita_twopass_pallas/prefill-paper"))
    assert not r["ok"]
    assert "narrowing" in {f["kind"] for f in r["findings"]}


def _da_noclamp(b_ref, sigma_ref, logits_i32, valid, at=...):
    """``da_update_aligned`` without the clip of k: the bucket difference
    ``max_bucket - (x >> 5)`` is not provably >= 0 (relational), so the
    128 >> k shift is no longer proven legal."""
    from repro.core.quant import SOFTMAX_SHIFT
    from repro.kernels.common import MASK_K, NEG_BUCKET

    xb = jax.lax.shift_right_arithmetic(logits_i32, jnp.int32(SOFTMAX_SHIFT))
    new = jnp.maximum(b_ref[at], jnp.max(
        jnp.where(valid, xb, NEG_BUCKET), axis=1, keepdims=True))
    delta = jnp.clip(new - b_ref[at], 0, MASK_K)
    k = jnp.where(valid, new - xb, MASK_K)      # clip(k, 0, 31) dropped
    u = jax.lax.shift_right_logical(jnp.int32(128), k)
    sigma_ref[at] = jax.lax.shift_right_logical(
        sigma_ref[at], delta) + 2 * jnp.sum(u, axis=1, keepdims=True)
    b_ref[at] = new
    return u, delta


def test_mutant_dropped_shift_clamp_is_flagged(monkeypatch):
    """Remove the clip of k from the fused kernels' DA update: the
    shift is no longer proven legal."""
    import repro.kernels.ita_attention.kernel as K

    monkeypatch.setattr(K, "da_update_aligned", _da_noclamp)
    r = run_case(_case("ita_onepass_pallas/prefill-paper"))
    assert not r["ok"]
    assert "shift_range" in {f["kind"] for f in r["findings"]}


@pytest.mark.parametrize("name", ["ita_onepass_pallas/serve-ragged-paged",
                                  "ita_decode_pallas/paged-adaptive"])
def test_paged_kernels_certify_through_their_head_loop(name, monkeypatch):
    """The paged kernels' shared body (one grid step per sequence, its
    query heads streamed in a loop) is certified in range, and the
    verifier reads into that loop: without the DA update's clip of k the
    same case fails on the shift."""
    import repro.kernels.ita_attention.kernel as K

    r = run_case(_case(name))
    assert r["ok"], r.get("findings", r.get("error"))
    assert r["out"] == [[-128, 127]] and r["n_unproven"] == 0
    monkeypatch.setattr(K, "da_update_aligned", _da_noclamp)
    jax.clear_caches()                  # the dispatch's jit traced it above
    r = run_case(_case(name))
    monkeypatch.undo()
    jax.clear_caches()                  # and leaves no mutant behind
    assert not r["ok"]
    assert "shift_range" in {f["kind"] for f in r["findings"]}


def test_mutant_widened_softmax_numerator_is_flagged(monkeypatch):
    """Remove the p <= 256 identity clamp from the direct path's softmax:
    at production kv length the p*V int32 accumulator (65536 * 127 *
    2048) is no longer proven in range -> overflow."""
    from repro.core import quant as Q
    from repro.core import softmax as SM

    def noclamp(x_q, mask=None, axis=-1):
        xb = SM.ita_bucket(x_q)
        row_bucket = SM._masked_bucket_max(xb, mask, axis)
        k = SM._aligned_k(xb, row_bucket, mask)
        terms = jax.lax.shift_right_logical(jnp.int32(SM._UNIT), k)
        sigma = jnp.maximum(jnp.sum(terms, axis=axis, keepdims=True), 1)
        sigma_inv = (jnp.int32(1) << SM._W_INV) // sigma
        p = jax.lax.shift_right_logical(sigma_inv, k)
        return p, sigma, row_bucket             # p <= _UNIT clamp dropped

    monkeypatch.setattr(SM, "ita_softmax_aligned_int", noclamp)
    r = run_case(_case("ita_direct_xla/decode-paper", smoke=False))
    assert not r["ok"]
    kinds = {f["kind"] for f in r["findings"]}
    assert kinds & {"overflow", "narrowing"}, r["findings"]
    assert Q  # keep the import exercised (quant constants stay loaded)


# ---------------------------------------------------------------------------
# Jit hygiene: recompile count + donation over a real mixed trace
# ---------------------------------------------------------------------------

def test_serve_recompile_count_bounded_and_donation_used():
    from repro.analysis.lints import (expected_variant_bound,
                                      run_lints)

    report = run_lints(smoke=True)
    by_name = {lint["name"]: lint for lint in report["lints"]}
    assert by_name["pow2-variant-contract"]["ok"], by_name
    assert by_name["serve-recompile-bound"]["ok"], by_name
    assert by_name["no-retrace-per-variant"]["ok"], by_name
    assert by_name["preemption-no-retrace"]["ok"], by_name
    assert by_name["donation-used"]["ok"], by_name
    assert expected_variant_bound(8) == 5


# ---------------------------------------------------------------------------
# CLI artifact
# ---------------------------------------------------------------------------

def test_cli_writes_schema_checked_report(tmp_path, capsys):
    from repro.analysis.__main__ import main
    from repro.analysis.verify import REPORT_SCHEMA

    out = tmp_path / "range_report.json"
    rc = main(["--smoke", "--backend", "ita_softmax", "--no-lints",
               "--json", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["schema"] == REPORT_SCHEMA
    assert rep["ok"] and rep["n_failed"] == 0
    assert rep["certified_backends"] == ["ita_softmax"]
    assert {c["name"] for c in rep["cases"]} == set(SOFTMAX_GOLDEN)
    assert "certificates" in capsys.readouterr().out
