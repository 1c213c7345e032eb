"""No-overflow certificates for every registered quantized kernel.

Builds the (backend x spec x geometry) matrix from the attention
registry's own capability verdicts, traces each case to a jaxpr
(interpret-mode for the Pallas kernels, so the kernel *body* is in the
trace), seeds the inputs from the declared operand ranges in
``attention/spec.py``, and runs the interval analyzer. A case passes
when the walk produces zero findings: every integer op's proven
interval fits its dtype, every narrowing convert is proven in range,
every shift amount is proven legal.

Geometries are chosen so interval bounds are *representative of the
production shapes*: the full geometry runs a 2048-token KV at the
shipped 128-wide kv tile — the per-tile reduction widths (which is what
the accumulators see) match production exactly, and longer sequences
only add more grid trips of the same proven-in-range tile math.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import attention as ATT
from repro.analysis.intervals import INF, Interval
from repro.analysis.ranges import AnalysisResult, analyze_jaxpr
from repro.attention.spec import declared_ranges

REPORT_SCHEMA = "ita-range-report-v1"


@dataclasses.dataclass(frozen=True)
class Geometry:
    b: int
    hq: int
    hkv: int
    sq: int
    skv: int
    d: int
    bq: int
    bkv: int
    page: int

    def to_json(self):
        return dataclasses.asdict(self)


SMOKE_GEOMETRY = Geometry(b=1, hq=2, hkv=2, sq=32, skv=128, d=32,
                          bq=16, bkv=32, page=32)
FULL_GEOMETRY = Geometry(b=1, hq=4, hkv=2, sq=128, skv=2048, d=64,
                         bq=64, bkv=128, page=128)


@dataclasses.dataclass
class Case:
    """One traceable closure + seeded inputs to certify."""

    name: str
    backend: str
    desc: str
    fn: object                    # closure over static config
    args: list                    # ShapeDtypeStructs / concrete leaves
    seeds: list                   # Interval | None per flattened arg

    def trace(self):
        return jax.make_jaxpr(self.fn)(*self.args)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _iv(bounds) -> Interval:
    return Interval(bounds[0], bounds[1])


# ---------------------------------------------------------------------------
# Case builders
# ---------------------------------------------------------------------------

def _softmax_cases(g: Geometry) -> list:
    from repro.core import softmax as SM
    from repro.kernels.ita_softmax.kernel import ita_softmax_pallas
    x = _sds((g.sq, g.skv), jnp.int8)
    m = _sds((g.sq, g.skv), jnp.bool_)
    seeds = [Interval(-128, 127), Interval(0, 1)]
    cases = []
    for adaptive in (False, True):
        mode = "adaptive" if adaptive else "paper"

        def pallas_fn(x, mask, *, _a=adaptive):
            return ita_softmax_pallas(x, mask, block_r=g.bq, block_c=g.bkv,
                                      adaptive=_a, interpret=True)

        cases.append(Case(
            name=f"ita_softmax_pallas/{mode}", backend="ita_softmax",
            desc=f"Pallas DA/DI/EN softmax, {mode} inverse, "
                 f"({g.sq},{g.skv}) tiles ({g.bq},{g.bkv})",
            fn=pallas_fn, args=[x, m], seeds=list(seeds)))

        def ref_fn(x, mask, *, _a=adaptive):
            if _a:
                return SM.ita_softmax_adaptive_int(x, mask)
            return SM.ita_softmax_int(x, mask)

        cases.append(Case(
            name=f"ita_softmax_ref/{mode}", backend="ita_softmax",
            desc=f"one-shot jnp reference softmax, {mode} inverse",
            fn=ref_fn, args=[x, m], seeds=list(seeds)))
    return cases


def _matmul_cases(g: Geometry) -> list:
    from repro.kernels.int8_matmul.ops import int8_matmul
    mdim, kdim, ndim = 4 * g.bq, 4 * g.bkv, 2 * g.bkv
    x = _sds((mdim, kdim), jnp.int8)
    w = _sds((kdim, ndim), jnp.int8)
    bias = _sds((ndim,), jnp.int32)
    mult = _sds((ndim,), jnp.float32)
    spec = ATT.AttentionSpec(mode="prefill", impl="ita")
    r = declared_ranges(spec)
    # bias rides the int32 accumulator: |bias| <= kdim * 127 * 127 keeps
    # acc + bias inside the certified budget (serve checkpoints are far
    # below this)
    bias_seed = Interval(-(1 << 20), 1 << 20)
    seeds = [_iv(r["q"]), _iv(r["k"]), bias_seed, Interval(0.0, 1.0)]
    cases = []
    for use_pallas in (True, False):
        eng = "pallas" if use_pallas else "xla"

        def fn(x, w, bias, mult, *, _p=use_pallas):
            return int8_matmul(x, w, bias, mult, block_m=g.bq * 2,
                               block_n=g.bkv, block_k=g.bkv,
                               use_pallas=_p, interpret=True)

        cases.append(Case(
            name=f"int8_matmul/{eng}", backend="int8_matmul",
            desc=f"int8 GEMM + bias + requant, {eng}, "
                 f"({mdim},{kdim})x({kdim},{ndim})",
            fn=fn, args=[x, w, bias, mult], seeds=seeds))
    return cases


def _scales_args(spec, g, r):
    """(args, seeds, n) for the QuantScales leaves of ``spec``."""
    siv = _iv(r["scale"])
    if spec.scale_kind == "per_head":
        shapes = [(g.hq,), (g.hkv,), (g.hkv,), (g.hq,)]
    else:
        shapes = [(), (), (), ()]
    return ([_sds(s, jnp.float32) for s in shapes], [siv] * 4)


def _attention_case(name, backend, spec, g: Geometry, *, desc,
                    kv_len=False, q_offset=False, paged=False,
                    ragged=False, opts=None) -> Case:
    npages = (g.b * g.skv) // g.page + 1
    npps = g.skv // g.page
    r = declared_ranges(spec, kv_capacity=g.skv, num_pages=npages)
    qlen = spec.q_len if spec.q_len else g.sq
    if spec.layout == "bshd":
        q = _sds((g.b, qlen, g.hq, g.d), jnp.int8)
        k = v = _sds((g.b, g.skv, g.hkv, g.d), jnp.int8)
    elif spec.layout == "bhsd":
        q = _sds((g.b, g.hq, qlen, g.d), jnp.int8)
        k = v = _sds((g.b, g.hkv, g.skv, g.d), jnp.int8)
    elif spec.layout == "bhsd_bsgd":
        q = _sds((g.b, g.hq, qlen, g.d), jnp.int8)
        k = v = _sds((g.b, g.skv, g.hkv, g.d), jnp.int8)
    else:                                           # bhsd_paged
        q = _sds((g.b, g.hq, qlen, g.d), jnp.int8)
        k = v = _sds((npages, g.hkv, g.page, g.d), jnp.int8)
    if spec.impl == "float":
        q = _sds(q.shape, jnp.float32)
        k = v = _sds(k.shape, jnp.float32)

    args = [q, k, v]
    seeds = [_iv(r["q"]), _iv(r["k"]), _iv(r["v"])]
    extra_names = []
    if spec.impl != "float":
        s_args, s_seeds = _scales_args(spec, g, r)
        args += s_args
        seeds += s_seeds
    if kv_len:
        args.append(_sds((g.b,), jnp.int32))
        seeds.append(_iv(r["kv_len"]))
        extra_names.append("kv_len")
    if q_offset:
        args.append(_sds((g.b,), jnp.int32))
        seeds.append(_iv(r["q_offset"]))
        extra_names.append("q_offset")
    if paged:
        args.append(_sds((g.b, npps), jnp.int32))
        seeds.append(_iv(r["page_table"]))
        extra_names.append("page_table")
    if ragged:
        args.append(_sds((g.b,), jnp.int32))
        seeds.append(Interval(0, qlen))
        extra_names.append("q_lens")

    call_opts = dict(opts or {})
    call_opts.setdefault("interpret", True)

    def fn(q, k, v, *rest):
        if spec.impl == "float":
            scales, extras = None, rest
        else:
            scales = ATT.QuantScales(*rest[:4])
            extras = rest[4:]
        kw = dict(zip(extra_names, extras, strict=True))
        return ATT.dispatch(q, k, v, spec=spec, scales=scales,
                            backend=backend, **kw, **call_opts)

    return Case(name=name, backend=backend, desc=desc, fn=fn,
                args=args, seeds=seeds)


def build_matrix(*, smoke: bool = False, backends=None) -> list:
    """The certification matrix. ``smoke`` runs the small geometry only
    (CI gate); the full run re-certifies at production tile widths."""
    g = SMOKE_GEOMETRY if smoke else FULL_GEOMETRY
    S = ATT.AttentionSpec
    cases = _softmax_cases(g) + _matmul_cases(g)

    fused_kw = dict(out_dtype="int8")
    cases += [
        _attention_case(
            "float_xla/prefill", "float_xla",
            S(mode="prefill", impl="float", causal=True), g,
            desc="float oracle, streaming prefill",
            opts={"q_chunk": g.bq * 2, "kv_chunk": g.bkv * 2}),
        _attention_case(
            "ita_chunked_xla/prefill-paper", "ita_chunked_xla",
            S(mode="prefill", impl="ita", causal=True, softmax="paper",
              out_dtype="int8"),
            g, desc="streaming ITA int path, paper inverse",
            opts={"q_chunk": g.bq * 2, "kv_chunk": g.bkv * 2}),
        _attention_case(
            "ita_chunked_xla/prefill-adaptive", "ita_chunked_xla",
            S(mode="prefill", impl="ita", causal=True, softmax="adaptive",
              out_dtype="int8"),
            g, desc="streaming ITA int path, adaptive inverse",
            opts={"q_chunk": g.bq * 2, "kv_chunk": g.bkv * 2}),
        _attention_case(
            "ita_direct_xla/decode-paper", "ita_direct_xla",
            S(mode="decode", impl="ita", causal=True, q_len=8,
              softmax="paper", out_dtype="int8"), g,
            desc="one-shot XLA decode fallback, paper inverse",
            kv_len=True, q_offset=True),
        _attention_case(
            "ita_direct_xla/decode-adaptive", "ita_direct_xla",
            S(mode="decode", impl="ita", causal=True, q_len=8,
              softmax="adaptive", out_dtype="int8"), g,
            desc="one-shot XLA decode fallback, adaptive inverse",
            kv_len=True, q_offset=True),
        _attention_case(
            "ibert_xla/decode", "ibert_xla",
            S(mode="decode", impl="ibert", causal=True, q_len=1), g,
            desc="I-BERT polynomial softmax decode baseline",
            kv_len=True, q_offset=True),
        _attention_case(
            "ita_onepass_pallas/prefill-paper", "ita_onepass_pallas",
            S(mode="prefill", impl="ita", causal=True, layout="bhsd",
              softmax="paper", **fused_kw), g,
            desc="fused one-pass kernel, causal prefill, paper inverse",
            opts={"block_q": g.bq, "block_kv": g.bkv}),
        _attention_case(
            "ita_onepass_pallas/serve-ragged-paged", "ita_onepass_pallas",
            S(mode="decode", impl="ita", causal=True, layout="bhsd_paged",
              q_len=g.bq, ragged_q=True, softmax="adaptive",
              scale_kind="per_head", **fused_kw), g,
            desc="the serve path: ragged chunked-prefill+decode rows over "
                 "paged KV, adaptive inverse, per-head scales",
            kv_len=True, q_offset=True, paged=True, ragged=True,
            opts={"block_q": g.bq}),
        _attention_case(
            "ita_twopass_pallas/prefill-paper", "ita_twopass_pallas",
            S(mode="prefill", impl="ita", causal=True, layout="bhsd",
              softmax="paper", **fused_kw), g,
            desc="two-pass QK->DA + AV->EN kernels, paper inverse",
            opts={"block_q": g.bq, "block_kv": g.bkv}),
        _attention_case(
            "ita_twopass_pallas/prefill-adaptive", "ita_twopass_pallas",
            S(mode="prefill", impl="ita", causal=True, layout="bhsd",
              softmax="adaptive", **fused_kw), g,
            desc="two-pass kernels, adaptive inverse (needs the "
                 "SIGMA_INV_MAX identity clamp to certify)",
            opts={"block_q": g.bq, "block_kv": g.bkv}),
        _attention_case(
            "ita_decode_pallas/ring", "ita_decode_pallas",
            S(mode="decode", impl="ita", causal=True, layout="bhsd_bsgd",
              q_len=1, scale_kind="per_head", **fused_kw), g,
            desc="single-token decode kernel over the ring layout, "
                 "per-head scales",
            kv_len=True, q_offset=True, opts={"block_kv": g.bkv}),
        _attention_case(
            "ita_decode_pallas/paged-adaptive", "ita_decode_pallas",
            S(mode="decode", impl="ita", causal=True, layout="bhsd_paged",
              q_len=1, softmax="adaptive", **fused_kw), g,
            desc="decode kernel over paged KV via scalar-prefetched page "
                 "table, adaptive inverse",
            kv_len=True, q_offset=True, paged=True),
    ]
    if backends:
        cases = [c for c in cases if c.backend in backends]
    return cases


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def _bound_json(v):
    if v == INF:
        return "inf"
    if v == -INF:
        return "-inf"
    return v


def run_case(case: Case) -> dict:
    t0 = time.monotonic()
    try:
        closed = case.trace()
        res: AnalysisResult = analyze_jaxpr(closed, case.seeds)
    except Exception as e:  # noqa: BLE001 — a crash is a failed certificate
        return {
            "name": case.name, "backend": case.backend, "desc": case.desc,
            "ok": False, "error": f"{type(e).__name__}: {e}",
            "elapsed_s": round(time.monotonic() - t0, 3),
        }
    outs = [o for o in res.outvals if isinstance(o, Interval)]
    return {
        "name": case.name,
        "backend": case.backend,
        "desc": case.desc,
        "ok": res.ok,
        "n_ops": len(res.records),
        "n_unproven": res.n_unproven,
        "max_int_magnitude": res.max_int_magnitude,
        "int32_headroom_bits": _headroom_bits(res.max_int_magnitude),
        "out": [[_bound_json(o.lo), _bound_json(o.hi)] for o in outs],
        "findings": [f.to_json() for f in res.findings],
        "notes": [n.to_json() for n in res.notes],
        "elapsed_s": round(time.monotonic() - t0, 3),
    }


def _headroom_bits(mag: int) -> int:
    """How many doublings the widest proven int value has before int32."""
    if mag <= 0:
        return 31
    bits = 0
    while mag < (1 << 31) and bits < 31:
        mag <<= 1
        bits += 1
    return bits - 1 if bits else 0


def run_verification(*, smoke: bool = False, backends=None) -> dict:
    g = SMOKE_GEOMETRY if smoke else FULL_GEOMETRY
    cases = build_matrix(smoke=smoke, backends=backends)
    results = [run_case(c) for c in cases]
    certified = sorted({r["backend"] for r in results if r["ok"]})
    failed = sorted({r["backend"] for r in results if not r["ok"]})
    return {
        "schema": REPORT_SCHEMA,
        "mode": "smoke" if smoke else "full",
        "geometry": g.to_json(),
        "n_cases": len(results),
        "n_failed": sum(1 for r in results if not r["ok"]),
        "certified_backends": certified,
        "failed_backends": failed,
        "ok": all(r["ok"] for r in results),
        "cases": results,
    }
