"""``correct``: served tokens against the plain reference.

After the window has closed and the program's serving state is gone, a
sample of finished requests, drawn from the seed and always holding the
longest, is run through ``reference`` once each, over the prompt and the
served tokens. At every served position the reference's logits give the
gap by which the served token lies below the reference's best token.
Three numbers are compared with the configuration's limits: the mean gap
over the sample's served tokens, the worst request's own mean gap (a
fault confined to one slot or to a few tokens of a short request moves
it, where the sample's mean dilutes it), and the widest gap. Greedy
decoding makes the served token the program's own argmax, so a sound
program reads gaps near zero, and a wrong token, a stale cache or a
lower precision reads wide.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

import reference
from benchlib.traffic import rng_for
from benchlib.weights import INIT

ROW_BUCKET = 256
BUCKET = 4096          # sequences are padded to a multiple of this
SAMPLE_REQUESTS = 3    # the longest finished request and two drawn ones


@dataclasses.dataclass(frozen=True)
class Gaps:
    widest: float         # widest gap of a served token
    mean: float           # mean gap over the served tokens
    request: float        # the largest of the requests' own mean gaps


@dataclasses.dataclass(frozen=True)
class Reading:
    program: Gaps
    served: int                     # served tokens compared
    requests: int
    control: Gaps | None = None     # the control's, at the same positions


def reference_weights(params) -> dict:
    """The reference's view of the benchmark-made weights (no copies)."""
    blk = params["groups"][0][0]
    layers = {k: blk["attn"][k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                          "bv") if k in blk["attn"]}
    layers.update({k: blk["mlp"][k] for k in ("w_gate", "w_up", "w_down")})
    return {"embed": params["embed"]["table"],
            "unembed": params["embed"]["unembed"], "layers": layers}


def sample(stamped, n: int, seed: int):
    """The longest finished request plus ``n - 1`` others drawn from the
    seed."""
    order = sorted(stamped, key=lambda r: (r.plen + r.gen, r.rid))
    longest, rest = order[-1], order[:-1]
    pick = rng_for(seed, 5).choice(len(rest), size=min(n - 1, len(rest)),
                                   replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _pad(x: np.ndarray, mult: int) -> np.ndarray:
    return np.pad(x, (0, (-x.size) % mult))


def _gaps(per_request: list) -> Gaps:
    """``per_request``: one array of per-token gaps for each request."""
    return Gaps(widest=max(float(g.max()) for g in per_request),
                mean=float(np.concatenate(per_request).mean()),
                request=max(float(g.mean()) for g in per_request))


def read(weights, conf: dict, requests, control: bool = False) -> Reading:
    got_gaps, ctrl_gaps = [], []
    for r in requests:
        toks = np.asarray(r.tokens, np.int32)
        prompt = np.asarray(r.prompt, np.int32)
        seq = jnp.asarray(_pad(np.concatenate([prompt, toks]), BUCKET))
        rows = np.arange(prompt.size - 1, prompt.size - 1 + toks.size)
        rows_p = jnp.asarray(_pad(rows, ROW_BUCKET))
        ref = reference.logits(weights, seq, rows_p, conf,
                               INIT["clip"])[:rows.size]
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(toks)[:, None], 1)[:, 0]
        got_gaps.append(np.asarray(best - got))
        if control:
            low = reference.logits(weights, seq, rows_p, conf,
                                   INIT["clip"], "low")[:rows.size]
            pick = jnp.argmax(low, axis=-1)
            chosen = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
            ctrl_gaps.append(np.asarray(best - chosen))
    served = sum(g.size for g in got_gaps)
    return Reading(_gaps(got_gaps), served, len(requests),
                   _gaps(ctrl_gaps) if control else None)


def compare(gaps: Gaps, failed: int, conf: dict) -> dict:
    """Each number compared for ``correct`` beside its limit: the gaps
    on which the configuration's ``check`` group sets a limit (PERF.md
    gives the readings each was set from), and the requests that came
    back with the wrong number of tokens (limit 0)."""
    limits = conf["check"]
    out = {}
    for name, value in (("mean_logit_gap", gaps.mean),
                        ("request_logit_gap", gaps.request),
                        ("logit_gap", gaps.widest)):
        if limits.get(f"{name}_limit") is not None:
            out[name] = {"value": value, "limit": limits[f"{name}_limit"]}
    if not out:
        raise ValueError(f"{conf['name']} sets no logit gap limit")
    out["wrong_length"] = {"value": failed, "limit": 0}
    return out


def is_correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
