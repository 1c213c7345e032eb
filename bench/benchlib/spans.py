"""The program's own names in a traced run: the serve loop's host spans
with their arguments, and each device op with the ``op_name`` of the
program scope that wrote it.

``benchlib.trace`` keeps each event's name and interval; this reads the
same ``.xplane.pb`` once more for what that drops. A host span of
``runtime.generate.serve_continuous`` (``serve.round``, ``serve.wait``,
``serve.dispatch`` with its ``steps`` and ``mixed`` ...) carries its
arguments as event stats, which ``ProfileData`` gives. A device op's
``op_name`` (``jit(seg)/serve_segment/decode_phase/while/...``, naming the
``jax.named_scope``s around the code that wrote it) is not among those:
the TPU trace keeps it as the ``tf_op`` stat of the op's event metadata,
and not even there for control flow, whose ``op_name`` is only in the
optimised HLO module that the trace's ``/host:metadata`` plane holds
(``Hlo Proto``). ``op_names`` reads both from the file's protobuf. A copy
that XLA inserts has no metadata, and so no program scope.

Rounds are told apart on the host's clock, which the profiler puts the
device's ops on too: a device op belongs to the round whose span holds
it. The trace starts and stops between rounds, so a round it records is
whole; a segment whose round is not recorded does not count.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
import statistics
import sys

from benchlib.trace import CONTAINER, DEVICE_PLANE, OPS_LINE

SPAN_PREFIX = "serve."
# every jax.named_scope the program puts on the device work of its serve
# path: the segment, its two phases and the model step's parts, and the
# small state and pool programs dispatched between segments
PROGRAM_SCOPES = frozenset((
    "serve_segment", "mixed_phase", "decode_phase", "grant", "embed",
    "attn_qkv", "kv_write", "attn_kernel", "attn_out", "mlp", "layer_carry",
    "head", "sample", "pool"))
METADATA_PLANE = "/host:metadata"
PROGRAM = re.compile(r"\((\d+)\)$")          # jit_seg(3124243304534210411)


@dataclasses.dataclass(frozen=True)
class Op:
    name: str                 # the op's HLO text, as in benchlib.trace
    op_name: str              # its metadata's op_name ("" where none)
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def container(self) -> bool:
        return bool(CONTAINER.search(self.name))

    @property
    def scopes(self) -> list[str]:
        """The program's scopes in the op's ``op_name``, outermost first."""
        return [p for p in self.op_name.split("/") if p in PROGRAM_SCOPES]

    def is_phase(self, phase: str) -> bool:
        """The ``while`` op of one phase of a serve segment."""
        return self.container and self.op_name.endswith(phase + "/while")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float
    args: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def holds(self, other) -> bool:
        return self.start_ns <= other.start_ns and other.end_ns <= self.end_ns


@dataclasses.dataclass(frozen=True)
class Round:
    span: Span                # serve.round
    children: tuple           # the serve.* spans inside it, by start

    def child(self, name: str):
        return next((c for c in self.children if c.name == name), None)


@dataclasses.dataclass(frozen=True)
class Spans:
    ops: tuple                # Op of the first device, by start
    host: tuple               # serve.* Span, by start

    def rounds(self) -> list[Round]:
        """Every recorded round that dispatched a segment."""
        out = []
        for r in self.host:
            if r.name != SPAN_PREFIX + "round":
                continue
            kids = tuple(c for c in self.host
                         if c is not r and r.holds(c))
            rnd = Round(r, kids)
            if rnd.child(SPAN_PREFIX + "dispatch") is not None:
                out.append(rnd)
        return out

    def instrumented(self) -> bool:
        """Whether the traced program names its work at all."""
        return any(op.scopes for op in self.ops)


def from_events(ops, host) -> Spans:
    return Spans(tuple(sorted(ops, key=lambda e: e.start_ns)),
                 tuple(sorted(host, key=lambda e: (e.start_ns, -e.dur_ns))))


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int = 0, end: int | None = None):
    """The ``(number, value)`` fields of one protobuf message in
    ``buf[i:end]``: an int for varint and fixed-width fields, a
    ``(start, end)`` slice of ``buf`` for length-delimited ones."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            val, i = int.from_bytes(buf[i:i + width], "little"), i + width
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _plane(buf, span):
    """An ``XPlane``'s name, event metadata ``{id: (name, display_name,
    [XStat fields])}`` and stat names ``{id: name}``; its lines (the
    events themselves) are skipped."""
    name, events, stat_names = "", {}, {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f in (4, 5):                     # map<int64, X...Metadata>
            for g, w in _fields(buf, *v):
                if g != 2:
                    continue
                if f == 5:                    # XStatMetadata: id, name
                    md = dict(_fields(buf, *w))
                    stat_names[md.get(1, 0)] = _text(buf, md[2]) \
                        if 2 in md else ""
                    continue
                md_id, ev_name, shown, stats = 0, "", "", []
                for h, x in _fields(buf, *w):   # XEventMetadata
                    if h == 1:
                        md_id = x
                    elif h == 2:
                        ev_name = _text(buf, x)
                    elif h == 4:
                        shown = _text(buf, x)
                    elif h == 5:                # XStat: metadata_id, value
                        stats.append(list(_fields(buf, *x)))
                events[md_id] = (ev_name, shown, stats)
    return name, events, stat_names


def _hlo_op_names(buf, span) -> dict:
    """``{instruction name: op_name}`` of an ``HloProto``'s module."""
    out = {}
    for f, module in _fields(buf, *span):
        if f != 1:                            # HloProto.hlo_module
            continue
        for g, comp in _fields(buf, *module):
            if g != 3:                        # HloModuleProto.computations
                continue
            for h, ins in _fields(buf, *comp):
                if h != 2:                    # .instructions
                    continue
                name = op_name = ""
                for k, x in _fields(buf, *ins):
                    if k == 1:
                        name = _text(buf, x)
                    elif k == 7:              # OpMetadata.op_name
                        op_name = next((_text(buf, y) for j, y
                                        in _fields(buf, *x) if j == 2), "")
                out[name] = op_name
    return out


def _stats(buf, stats, stat_names) -> dict:
    """``{stat name: value}`` of ``XStat`` fields: a string for a string
    or a reference to a stat name, a ``buf`` slice for bytes, else an
    int."""
    out = {}
    for st in stats:
        st = dict(st)
        key = stat_names.get(st.pop(1, None))
        if 7 in st:                           # ref_value
            out[key] = stat_names.get(st[7], "")
        elif 5 in st:                         # str_value
            out[key] = _text(buf, st[5])
        elif st:
            out[key] = next(iter(st.values()))
    return out


def op_names(path) -> dict:
    """``{op event name: op_name}`` for the ops of the first device plane
    of the ``.xplane.pb`` at ``path`` ("" where the op has none). An op's
    event name is its HLO text; where two programs share one, the first
    program's ``op_name`` is kept."""
    buf = memoryview(pathlib.Path(path).read_bytes())
    planes = [_plane(buf, v) for f, v in _fields(buf) if f == 1]
    device = sorted((p for p in planes if DEVICE_PLANE.match(p[0])),
                    key=lambda p: p[0])
    if not device:
        return {}
    hlo = {}                                  # program id -> op names
    for name, events, stat_names in planes:
        if name != METADATA_PLANE:
            continue
        for ev_name, _, stats in events.values():
            prog = PROGRAM.search(ev_name)
            proto = _stats(buf, stats, stat_names).get("Hlo Proto")
            if prog and isinstance(proto, tuple):
                hlo[int(prog.group(1))] = _hlo_op_names(buf, proto)
    _, events, stat_names = device[0]
    out = {}
    for ev_name, shown, stats in events.values():
        vals = _stats(buf, stats, stat_names)
        op = vals.get("tf_op")
        if not isinstance(op, str):
            op = hlo.get(vals.get("program_id"), {}).get(shown, "")
        out.setdefault(ev_name, op.rstrip(":"))
    return out


def load(trace_dir) -> Spans:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    try:
        names = op_names(files[-1])
    except (ValueError, IndexError, KeyError) as e:   # not an XSpace we know
        print(f"[bench] device op names unreadable: {e!r}", file=sys.stderr)
        names = {}
    data = ProfileData.from_file(str(files[-1]))
    device = sorted(p.name for p in data.planes
                    if DEVICE_PLANE.match(p.name))
    ops, host = [], []
    for plane in data.planes:
        if device and plane.name == device[0]:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Op(e.name, names.get(e.name, ""),
                              float(e.start_ns), float(e.duration_ns))
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Span(e.name, float(e.start_ns),
                                 float(e.duration_ns), dict(e.stats))
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return from_events(ops, host)


_CACHE: dict = {}


def of_run(run):
    """The run's trace with its names (read once per run), or None for
    an untraced run or one that left no trace file."""
    if run.trace is None:
        return None
    from benchlib.runner import TRACE_DIR
    files = sorted(pathlib.Path(TRACE_DIR).rglob("*.xplane.pb"))
    if not files:
        return None
    key = tuple((str(f), f.stat().st_mtime_ns) for f in files)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load(TRACE_DIR)
    return _CACHE[key]


def phase_step_ms(spans: Spans | None, phase: str):
    """Device milliseconds per step of one phase (``mixed_phase`` or
    ``decode_phase``): the phase's ``while`` ops that lie inside a
    recorded round, over the steps of that phase in those rounds'
    segments (``mixed``, or ``steps - mixed``, of ``serve.dispatch``). A
    phase of one step may be compiled without a loop; its segments do
    not count."""
    if spans is None:
        return None
    starts = [op.start_ns for op in spans.ops]
    secs, steps = 0.0, 0
    for rnd in spans.rounds():
        lo = bisect.bisect_left(starts, rnd.span.start_ns)
        hi = bisect.bisect_left(starts, rnd.span.end_ns)
        ops = [op for op in spans.ops[lo:hi]
               if op.is_phase(phase) and rnd.span.holds(op)]
        if not ops:
            continue
        args = rnd.child(SPAN_PREFIX + "dispatch").args
        mixed = int(args["mixed"])
        steps += mixed if phase == "mixed_phase" else \
            int(args["steps"]) - mixed
        secs += sum(op.dur_ns for op in ops) / 1e9
    if steps == 0:
        return None
    return 1e3 * secs / steps


def unscoped_share(spans: Spans | None):
    """Percent of device busy time spent in ops (not control flow) whose
    ``op_name`` names none of the program's scopes; None where the
    program names none of its ops."""
    if spans is None or not spans.ops or not spans.instrumented():
        return None
    from benchlib.trace import merged
    busy = sum(b - a for a, b in merged(spans.ops))
    if busy <= 0:
        return None
    loose = sum(op.dur_ns for op in spans.ops
                if not op.container and not op.scopes)
    return 100.0 * loose / busy


def host_round_ms(spans: Spans | None):
    """Median milliseconds of host work per round: a dispatching round's
    span less its ``serve.wait`` child."""
    if spans is None:
        return None
    own = []
    for rnd in spans.rounds():
        wait = rnd.child(SPAN_PREFIX + "wait")
        own.append(rnd.span.dur_ns - (wait.dur_ns if wait else 0.0))
    return statistics.median(own) / 1e6 if own else None

