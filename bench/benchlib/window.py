"""End-to-end numbers of one measured window, from the served requests'
own stamps (host clock, taken by the serve loop after each blocking
readback) and the window's two round boundaries."""

from __future__ import annotations

import dataclasses

WARM_PREFIX = "warm"


def quantile(values, q: float) -> float:
    """Nearest-rank quantile, as ``runtime.generate.ServeResult`` and
    ``benchmarks/bench_serve.py`` take it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no samples")
    return vals[min(int(q * len(vals)), len(vals) - 1)]


@dataclasses.dataclass(frozen=True)
class Stamped:
    rid: str
    prompt: object
    plen: int
    gen: int
    arrival: int             # step due
    admitted_step: int
    first: float             # perf_counter of the readback of token 1
    finish: float            # perf_counter of the readback of the last
    ttft_s: float
    tokens: object


def stamp(result, requests, clock) -> list[Stamped]:
    """Completed requests on the benchmark's clock. The serve loop stamps
    relative to its own start; a request due at the loop's first round
    has ``arrived_s`` equal to that round's offset, which the clock
    recorded as its first mark."""
    by_index = {c.index: c for c in result.completed}
    first_round = min(c.arrived_s for c in result.completed
                      if c.arrival <= clock.marks[0][0])
    base = clock.marks[0][1] - first_round
    out = []
    for i, r in enumerate(requests):
        c = by_index.get(i)
        if c is None or r.rid.startswith(WARM_PREFIX):
            continue
        out.append(Stamped(rid=r.rid, prompt=r.prompt,
                           plen=int(r.prompt.size), gen=r.gen,
                           arrival=r.arrival,
                           admitted_step=int(c.admitted_step),
                           first=base + c.first_token_s,
                           finish=base + c.finished_s, ttft_s=c.ttft_s,
                           tokens=c.tokens))
    return out


def emitted_by(r: Stamped, t: float) -> float:
    """Tokens of ``r`` read back by time ``t``: the first at ``first``,
    the rest spread evenly up to ``finish`` (tokens come back once per
    segment, so within a request's span the benchmark interpolates)."""
    if t < r.first:
        return 0.0
    if t >= r.finish or r.gen <= 1:
        return float(r.gen)
    return 1.0 + (r.gen - 1) * (t - r.first) / (r.finish - r.first)


@dataclasses.dataclass(frozen=True)
class Window:
    t0: float
    t1: float
    step0: int
    step1: int
    tokens: float                    # output tokens read back in it
    first_in: tuple                  # requests whose first token is in it
    done_in: tuple                   # requests that finished in it

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def steps(self) -> int:
        return self.step1 - self.step0

    def tok_s(self) -> float:
        return self.tokens / self.seconds

    def ttft_p95_s(self) -> float:
        return quantile([r.ttft_s for r in self.first_in], 0.95)

    def tpot_p95_ms(self) -> float:
        return 1e3 * quantile([(r.finish - r.first) / (r.gen - 1)
                               for r in self.done_in if r.gen > 1], 0.95)


def window(stamped, clock) -> Window:
    t0, t1 = clock.t_open, clock.t_close
    return Window(
        t0=t0, t1=t1, step0=clock.step_open, step1=clock.step_close,
        tokens=sum(emitted_by(r, t1) - emitted_by(r, t0) for r in stamped),
        first_in=tuple(r for r in stamped if t0 < r.first <= t1),
        done_in=tuple(r for r in stamped if t0 < r.finish <= t1))
