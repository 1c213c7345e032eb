"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy
time, per-kernel device time and the idle gaps between device work.

Read with ``jax.profiler.ProfileData`` alone. Device planes are those
named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed HLO op, named by the op's HLO text (``%_fused.9 = s8[512,8,96]
... custom-call(...)``). Control-flow ops (``while``, ``conditional``,
``call``) span the ops of their bodies; they count towards busy time
but not as ops of their own. Host planes (``/host:...``) hold the
runtime's and the benchmark's own spans, which name what the host was
doing in a gap.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"\s(while|conditional|call)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """The op's HLO name (``%_fused.9``) and result shape."""
        return self.name.split("{", 1)[0].strip()[:120]

    @property
    def container(self) -> bool:
        return bool(CONTAINER.search(self.name))


@dataclasses.dataclass
class Trace:
    device_ops: dict          # plane name -> [Event] (sorted by start)
    host: list                # [Event] of every host line

    @property
    def devices(self) -> list:
        return sorted(self.device_ops)


def from_events(device_ops: dict, host: list) -> Trace:
    return Trace({k: sorted(v, key=lambda e: e.start_ns)
                  for k, v in device_ops.items()},
                 sorted(host, key=lambda e: e.start_ns))


def load(trace_dir) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns))
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, float(e.start_ns),
                                  float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return from_events(device, host)


def merged(ops) -> list[tuple[float, float]]:
    """Union of the ops' intervals as disjoint sorted (start, end)."""
    out: list[list[float]] = []
    for e in sorted(ops, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some op ran, averaged over the traced devices."""
    if not trace.device_ops:
        return 0.0
    return sum(sum(b - a for a, b in merged(ops))
               for ops in trace.device_ops.values()) \
        / len(trace.device_ops) / 1e9


def kernel_s(trace: Trace, match) -> tuple[float, int]:
    """Summed device seconds and count of the ops ``match(event)``
    accepts, averaged over the traced devices."""
    if not trace.device_ops:
        return 0.0, 0
    tot, n = 0.0, 0
    for ops in trace.device_ops.values():
        for e in ops:
            if match(e):
                tot += e.dur_ns
                n += 1
    k = len(trace.device_ops)
    return tot / k / 1e9, n // k


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` op names that took the most device time (first device)."""
    if not trace.device_ops:
        return []
    acc: dict[str, float] = {}
    for e in trace.device_ops[trace.devices[0]]:
        if not e.container:
            acc[e.short] = acc.get(e.short, 0.0) + e.dur_ns
    return [[name, ns / 1e9]
            for name, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest gaps between device work on the first device,
    each named by the shortest host span that covers most of it, or
    ``unattributed``."""
    if not trace.device_ops:
        return []
    spans = merged(trace.device_ops[trace.devices[0]])
    gaps = sorted(((b0, a1) for (_, b0), (a1, _) in zip(spans, spans[1:])),
                  key=lambda g: g[0] - g[1])[:k]
    out = []
    for g0, g1 in gaps:
        best = None
        for h in trace.host:
            if h.start_ns >= g1:
                break
            cover = min(h.end_ns, g1) - max(h.start_ns, g0)
            if cover >= 0.5 * (g1 - g0) and (best is None
                                             or h.dur_ns < best.dur_ns):
                best = h
        out.append([best.name if best else "unattributed", (g1 - g0) / 1e9])
    return out
