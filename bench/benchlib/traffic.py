"""The one traffic generator: a mix file of parameters in, requests out.

The schedule, every request's prompt length, output length and arrival
step, is one draw of the mix's distributions from the mix file's own
``schedule_seed``: lognormal lengths clipped to their bounds and a
Poisson process of arrivals at ``arrivals.rate_per_step``. It is the
same for every run. The run's seed draws the prompt token ids (and,
elsewhere, the weights and the requests the check compares). So every
seed offers the same work at the same steps, and two runs differ only in
what the tokens say; the work in a window a few dozen requests long
would otherwise swing with the slice of the distributions it holds.

Arrivals are counted in decode steps (the serve loop's virtual clock).
The mix starts once the warm-up requests are through
(``warmup_steps``): ``warm_requests`` arrive at once to fill the slots,
the rest follow the Poisson process. Each quantity is drawn from a
stream of its own, so the first ``n`` requests are the same whatever
``n_requests`` is, and scaling the rate stretches the same arrivals.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: np.ndarray     # (plen,) int32 token ids
    gen: int               # tokens to generate, the first included
    arrival: int           # decode step at which the request is due
    rid: str


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, stream])


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of a lognormal (``median``, ``sigma``) clipped to
    [``min``, ``max``]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    vals = np.rint(dist["median"] * np.exp(dist["sigma"]
                                           * rng.standard_normal(n)))
    return np.clip(vals, dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Schedule:
    plens: np.ndarray      # (n,) prompt lengths
    gens: np.ndarray       # (n,) output lengths
    offsets: np.ndarray    # (n,) arrival steps after the mix's start


def schedule(mix: dict, rate: float | None = None) -> Schedule:
    """The mix's schedule; ``rate`` (requests per step) overrides the
    mix's own, stretching the same unit gaps."""
    n, warm = int(mix["n_requests"]), int(mix["warm_requests"])
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = arr["rate_per_step"] if rate is None else rate
    if not rate or rate <= 0:
        raise ValueError(f"mix {mix['name']!r} has no arrival rate; run "
                         f"bench/knee.py to set it")
    s = int(mix["schedule_seed"])
    unit = rng_for(s, 3).standard_exponential(n - warm)
    return Schedule(
        plens=lengths(mix["prompt_len"], n, rng_for(s, 1)),
        gens=lengths(mix["output_len"], n, rng_for(s, 2)),
        offsets=np.concatenate([np.zeros(warm), np.cumsum(unit / rate)]))


def generate(mix: dict, vocab: int, seed: int,
             rate: float | None = None) -> list[Request]:
    """The requests of one run of ``mix`` for ``seed``."""
    sched = schedule(mix, rate)
    tok = rng_for(seed, 4)
    start = warmup_steps(mix)
    return [Request(prompt=tok.integers(0, vocab, int(p), dtype=np.int32),
                    gen=int(g), arrival=start + int(math.floor(s)),
                    rid=f"r{i:05d}")
            for i, (p, g, s) in enumerate(zip(sched.plens, sched.gens,
                                              sched.offsets, strict=True))]


def warmup_requests(mix: dict) -> list[Request]:
    """Requests that run ahead of the mix, one at a time, so that the
    served path builds every program the window can use before the
    window opens: one prompt per mixed-segment width (1, 2, 4, ...
    chunks, up to a whole segment), each decoding past the end of its
    segment so that a pure-decode segment runs too. Their ids start
    with ``warm`` and no metric reads them."""
    geo = mix["geometry"]
    chunk, seg = geo["chunk_size"], geo["segment"]
    out, width = [], 1
    while width <= seg:
        out.append(Request(prompt=np.full(width * chunk, len(out), np.int32),
                           gen=seg + 2, arrival=2 * seg * len(out),
                           rid=f"warm{len(out)}"))
        width *= 2
    return out


def warmup_steps(mix: dict) -> int:
    """Steps the warm-up requests own before the mix's first arrival."""
    return 2 * mix["geometry"]["segment"] * len(warmup_requests(mix))
