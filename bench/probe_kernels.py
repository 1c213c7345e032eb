"""Single calls of the paged attention kernels against one-shot ITA
arithmetic, at a configuration's widths: where the kernels' streaming
softmax leaves the one-shot result.

    python3 bench/probe_kernels.py --workload phi3-4l-reasoning \
        [--contexts 512,1024,2048,4096] [--seed 7] [--heads 8]

For each kernel (``decode``: the decode-paged kernel, one query per row;
``onepass``: the ragged onepass-paged kernel, a 32-query prefill chunk at
the end of each context) and each key order (``ascending``: every row's
keys sorted so its max comes last and grows tile by tile; ``descending``:
the max comes first; ``random``), int8 q, k and v are drawn from the seed
at the configuration's head dim, with the benchmark's clips, written
into a page-table-indexed pool (pages of the mix's ``page_size``, the
head dim padded to 128 lanes as the serve path stores it) and attended
through ``kernels.ita_attention.ops.fused_attention``. The int8 result is
compared, in int64 numpy, with two one-shot computations on the same
int8 logits: ``paper`` (``k = (max - x) >> 5``, the arithmetic of
``core.softmax.ita_softmax``) and ``aligned`` (``k = (max >> 5) - (x >> 5)``,
``core.softmax.ita_softmax_aligned``), both with the fused kernels'
``u = 128 >> k`` numerators and adaptive inverse; and, for scale, each of
the three with float attention on the same int8 operands. Prints one JSON
line per (kernel, order): the largest difference in output steps (LSB)
and the share of outputs that differ from each one-shot result, and the
mean distance from float in LSB. Runs on whatever JAX's default device
is (the kernels compile on a TPU and run interpreted elsewhere).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

from benchlib import spec  # noqa: E402
from repro.core.quant import EPS_MAX  # noqa: E402

ORDERS = ("ascending", "descending", "random")
CHUNK = 32          # query rows of the onepass call, as the mixes' chunk
LANES = 128


def quantize(x, clip):
    step = np.float32(clip / 127.0)
    return np.clip(np.round(x / step), -128, 127).astype(np.int8), step


def draw(rng, b, h, sq, ctx, d, clip, order):
    """int8 q (b, h, sq, d) and k, v (b, h, ctx, d): queries of one row
    share a direction (so one key order serves all of them), keys sorted
    per row by that direction's logit as ``order`` says."""
    base = rng.standard_normal((b, h, 1, d))
    q = base + 0.3 * rng.standard_normal((b, h, sq, d))
    k = rng.standard_normal((b, h, ctx, d))
    v = rng.standard_normal((b, h, ctx, d))
    if order != "random":
        score = np.einsum("bhd,bhkd->bhk", base[:, :, 0], k)
        idx = np.argsort(score, axis=-1)
        if order == "descending":
            idx = idx[..., ::-1]
        k = np.take_along_axis(k, idx[..., None], axis=2)
        v = np.take_along_axis(v, idx[..., None], axis=2)
    (q8, sq_), (k8, sk), (v8, sv) = (quantize(x, clip) for x in (q, k, v))
    return q8, k8, v8, sq_, sk, sv


def paged(x8, page, n_pages):
    """(b, h, ctx, d) int8 -> pool (1 + b * n_pages, h, page, 128) and the
    page table (b, n_pages); page 0 is the parking page, lanes past d
    zero."""
    b, h, ctx, d = x8.shape
    full = np.zeros((b, h, n_pages * page, LANES), np.int8)
    full[:, :, :ctx, :d] = x8
    pages = full.reshape(b, h, n_pages, page, LANES).transpose(0, 2, 1, 3, 4)
    pool = np.concatenate([np.zeros((1, h, page, LANES), np.int8),
                           pages.reshape(b * n_pages, h, page, LANES)])
    table = 1 + np.arange(b * n_pages, dtype=np.int32).reshape(b, n_pages)
    return pool, table


def one_shot(q8, k8, v8, lmult, omult, kv_len, q_off, kind):
    """int8 output (b, h, sq, d) of a one-shot fused-ITA computation,
    ``kind`` ``paper`` or ``aligned``, in int64 with the kernels' f32
    requants; and the float attention's output in the same LSB."""
    b, h, sq, d = q8.shape
    ctx = k8.shape[2]
    acc = np.einsum("bhqd,bhkd->bhqk", q8.astype(np.int64),
                    k8.astype(np.int64))
    lg = np.clip(np.round(acc.astype(np.float32) * lmult), -128, 127
                 ).astype(np.int64)
    qi = q_off[:, None, None, None] + np.arange(sq)[None, None, :, None]
    kj = np.arange(ctx)[None, None, None, :]
    valid = (kj <= qi) & (kj < kv_len[:, None, None, None])
    if kind == "float":
        z = np.where(valid, lg.astype(np.float64) * EPS_MAX, -np.inf)
        p = np.exp(z - z.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bhkd->bhqd", p, v8.astype(np.float64)) \
            * omult
    x = np.where(valid, lg, -256)
    if kind == "paper":
        kk = (x.max(-1, keepdims=True) - lg) >> 5
    else:
        kk = (x.max(-1, keepdims=True) >> 5) - (lg >> 5)
    kk = np.where(valid, np.clip(kk, 0, 31), 31)
    u = np.int64(128) >> kk
    sigma = np.maximum((2 * u).sum(-1, keepdims=True), 1)
    e_r = np.floor(np.log2(sigma)).astype(np.int64)     # exact below 2^52
    inv = (np.int64(1) << (e_r + 8)) // sigma
    o = np.einsum("bhqk,bhkd->bhqd", u, v8.astype(np.int64))
    scale = np.float32(2.0) * inv.astype(np.float32) \
        * np.exp2(-(e_r + 8)).astype(np.float32) * np.float32(omult)
    y = np.round(o.astype(np.float32) * scale)
    return np.clip(y, -128, 127).astype(np.int64)


def probe(kind, order, rng, conf, contexts, heads, page):
    import jax.numpy as jnp

    from repro.kernels.ita_attention.ops import fused_attention
    from benchlib.weights import INIT
    d = conf["head_dim"]
    b, sq = len(contexts), (1 if kind == "decode" else CHUNK)
    ctx_max = max(contexts)
    n_pages = -(-ctx_max // page)
    clip = INIT["clip"]["q"]
    q8 = np.zeros((b, heads, sq, d), np.int8)
    k8 = np.zeros((b, heads, ctx_max, d), np.int8)
    v8 = np.zeros_like(k8)
    for i, n in enumerate(contexts):          # keys sorted within a context
        q_i, k_i, v_i, s_q, s_k, s_v = draw(rng, 1, heads, sq, n, d, clip,
                                            order)
        q8[i], k8[i, :, :n], v8[i, :, :n] = q_i[0], k_i[0], v_i[0]
    kv_len = np.asarray(contexts, np.int32)
    s_out = np.float32(INIT["clip"]["out"] / 127.0)
    # the multipliers in f32, as ``ops.fused_attention`` forms them
    lmult = np.float32(s_q) * np.float32(s_k) \
        / np.float32(np.sqrt(d) * EPS_MAX)
    omult = np.float32(s_v) / np.float32(s_out)
    q_off = kv_len - sq
    kpool, table = paged(k8, page, n_pages)
    vpool, _ = paged(v8, page, n_pages)
    out = fused_attention(
        jnp.asarray(q8), jnp.asarray(kpool), jnp.asarray(vpool),
        jnp.float32(s_q), jnp.float32(s_k), jnp.float32(s_v),
        jnp.float32(s_out), q_offset=jnp.asarray(q_off),
        kv_len=jnp.asarray(kv_len), causal=True, kind=kind,
        page_table=jnp.asarray(table),
        q_lens=None if kind == "decode" else jnp.full((b,), sq, jnp.int32))
    got = np.asarray(out).astype(np.int64)
    refs = {r: one_shot(q8, k8, v8, lmult, omult, kv_len, q_off, r)
            for r in ("paper", "aligned", "float")}
    line = {"kernel": kind, "order": order, "contexts": list(contexts),
            "heads": heads, "head_dim": d}
    for r in ("paper", "aligned"):
        diff = np.abs(got - refs[r])
        line[f"vs_{r}_max_lsb"] = int(diff.max())
        line[f"vs_{r}_differ"] = float((diff > 0).mean())
    for name, y in (("kernel", got), ("paper", refs["paper"]),
                    ("aligned", refs["aligned"])):
        line[f"{name}_vs_float_lsb"] = float(np.abs(y - refs["float"]).mean())
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--contexts", default="512,1024,2048,4096")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    contexts = [int(c) for c in args.contexts.split(",")]
    page = cell.traffic["geometry"]["page_size"]
    rng = np.random.default_rng(args.seed)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "workload": args.workload}), flush=True)
    for kind in ("decode", "onepass"):
        for order in ORDERS:
            print(json.dumps(probe(kind, order, rng, cell.config, contexts,
                                   args.heads, page)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
