"""Jaxpr-walking abstract interpreter over the integer-interval domain.

``analyze_jaxpr`` walks a closed jaxpr with every input seeded to a
declared interval (int8 tensors to [-128, 127], scale scalars to the
``attention.spec`` declared bounds, kv_len to the pool capacity, ...)
and propagates per-primitive transfer functions. Three checks turn the
propagation into a no-overflow certificate:

- **overflow**: the result of integer add/sub/mul/dot_general/
  reduce_sum/shift_left, computed in unbounded integers, must fit the
  op's dtype;
- **narrowing**: ``convert_element_type`` to an integer dtype requires
  the operand interval to already sit inside the target range — this is
  what catches a dropped requant clip (the int32 logits would no longer
  provably fit the int8 store);
- **shift_range**: shift amounts must be proven within ``[0, bits-1]``
  (an unclamped ``k = (max - x) >> 5`` on a masked row reaches 2^27,
  which is UB for the lowered shift).

Structured control flow is walked, not approximated away: nested ``jit``
and custom-derivative calls recurse; ``cond`` evaluates the taken branch
when the predicate interval is a point and joins all branches
otherwise; ``scan``/``while`` unroll up to a budget and then widen the
carry to the dtype range; ``pallas_call`` maps operand intervals onto
the kernel body's refs and *simulates the grid*: the innermost (last)
grid axis runs concretely for two full sweeps — scratch accumulators
(the DA ``sigma``) reach their true per-row bound on sweep one, and
sweep two re-runs every read against the converged state so
cross-pass dependencies (the softmax kernel's EN pass reading DA
stats) see post-reduction values. Outer grid axes stay abstract; their
``program_id`` is the whole ``[0, n-1]`` interval.

Unknown primitives produce the full dtype range and a ``note`` (the
report counts them as *unproven*, never silently as proven).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

from repro.analysis import intervals as iv
from repro.analysis.intervals import (BOOL, INF, TOP, Interval, dtype_bits,
                                      dtype_range, fits, is_bool_dtype,
                                      is_int_dtype, join_all, point)

# Unroll budgets. The verify matrix uses small geometries on purpose —
# interval bounds are geometry-monotone (larger kv_len only scales the
# reduction counts), so a certificate at the registered geometry plus
# the analytic scaling note covers the family.
MAX_GRID_TRIPS = 512
MAX_SCAN_TRIPS = 64
PALLAS_SWEEPS = 2


@dataclasses.dataclass
class Finding:
    """A failed check — the interval could not be proven in range."""

    kind: str          # overflow | narrowing | shift_range | budget
    prim: str
    path: str
    dtype: str
    ival: str
    bound: str
    message: str

    def to_json(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Note:
    """Non-failing diagnostics (unproven prims, possible zero divisors)."""

    kind: str          # unproven | zero_divisor | uninit_read | join_init
    path: str
    message: str

    def to_json(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class OpRecord:
    prim: str
    path: str
    dtype: str
    lo: float
    hi: float


class RefCell:
    """Abstract state of one pallas ref (input block / output / scratch).

    ``ival is None`` = uninitialized (never written). Output refs join
    on write (each grid step writes a different block of the same
    array); scratch refs strong-update (whole-ref writes, persisted
    across the simulated grid sweep); input refs are read-only views of
    the operand interval.
    """

    __slots__ = ("kind", "ival", "dtype")

    def __init__(self, kind: str, ival, dtype):
        self.kind = kind
        self.ival = ival
        self.dtype = dtype

    def __repr__(self):
        return f"RefCell({self.kind}, {self.ival})"


class _PallasFrame:
    """Grid position during body simulation: trailing axes run
    concretely (their current trip value is known exactly — this is
    what makes ``j == 0`` init predicates and ``pass == 1`` cross-pass
    reads decide to a point), leading axes stay abstract."""

    __slots__ = ("grid", "concrete")

    def __init__(self, grid):
        self.grid = tuple(grid)
        self.concrete: dict[int, int] = {}

    def program_id(self, axis: int) -> Interval:
        if axis in self.concrete:
            return point(self.concrete[axis])
        n = self.grid[axis] if axis < len(self.grid) else 1
        return Interval(0, max(n - 1, 0))


@dataclasses.dataclass
class AnalysisResult:
    findings: list
    notes: list
    records: list
    outvals: list

    @property
    def ok(self) -> bool:
        return not self.findings

    @property
    def n_unproven(self) -> int:
        return sum(1 for n in self.notes if n.kind == "unproven")

    @property
    def max_int_magnitude(self) -> int:
        """Largest |bound| proven over every integer-dtype op — the
        headline of a certificate (how close the pipeline comes to the
        int32 rail)."""
        m = 0
        for r in self.records:
            if is_int_dtype(r.dtype) and abs(r.lo) != INF and abs(r.hi) != INF:
                m = max(m, int(abs(r.lo)), int(abs(r.hi)))
        return m

    def findings_by_kind(self):
        out: dict[str, int] = {}
        for f in self.findings:
            out[f.kind] = out.get(f.kind, 0) + 1
        return out


def _aval(v):
    a = v.aval
    return getattr(a, "inner_aval", a)


def _literal_interval(val) -> Interval:
    arr = np.asarray(val)
    lo, hi = arr.min(), arr.max()
    if np.issubdtype(arr.dtype, np.integer) or arr.dtype == np.bool_:
        return Interval(int(lo), int(hi))
    if not (np.isfinite(lo) and np.isfinite(hi)):
        return TOP
    return Interval(float(lo), float(hi))


def _seed_for(avl) -> Interval:
    """Default seed when the caller declared nothing: the dtype range."""
    return dtype_range(avl.dtype)


class Interp:
    """One analysis run. Not reentrant; build a fresh one per jaxpr."""

    def __init__(self):
        self.findings: list[Finding] = []
        self.notes: list[Note] = []
        self.records: list[OpRecord] = []
        self.cells: list[RefCell] = []          # every live pallas ref
        self.frames: list[_PallasFrame] = []
        self._noted: set[tuple] = set()
        self._found: set[tuple] = set()
        self.mute = False       # True during pallas warm-up sweeps

    # -- env plumbing -------------------------------------------------------

    def read(self, env, atom):
        if hasattr(atom, "val"):                # Literal
            return _literal_interval(atom.val)
        return env[atom]

    def note_once(self, kind, path, message):
        if self.mute:
            return
        key = (kind, message)
        if key not in self._noted:
            self._noted.add(key)
            self.notes.append(Note(kind, path, message))

    def add_finding(self, finding: Finding):
        if self.mute:
            return
        # the same op fires once per simulated grid trip — keep the first
        key = (finding.kind, finding.prim, finding.path)
        if key not in self._found:
            self._found.add(key)
            self.findings.append(finding)

    def check_fit(self, kind, prim, path, dtype, ival: Interval) -> Interval:
        if is_int_dtype(dtype) and not fits(ival, dtype):
            self.add_finding(Finding(
                kind=kind, prim=prim, path=path, dtype=str(dtype),
                ival=repr(ival), bound=repr(dtype_range(dtype)),
                message=f"{prim}: proven interval {ival!r} exceeds "
                        f"{dtype} range {dtype_range(dtype)!r}"))
            return ival.meet(dtype_range(dtype))
        return ival

    def check_shift(self, prim, path, dtype, sh: Interval):
        bits = dtype_bits(dtype) or 32
        ok = Interval(0, bits - 1)
        if not ok.contains(sh):
            self.add_finding(Finding(
                kind="shift_range", prim=prim, path=path, dtype=str(dtype),
                ival=repr(sh), bound=repr(ok),
                message=f"{prim}: shift amount {sh!r} not proven within "
                        f"{ok!r} (shift >= width is undefined)"))

    # -- jaxpr walking ------------------------------------------------------

    def run_closed(self, closed_jaxpr, seeds, path="") -> list:
        jaxpr = closed_jaxpr.jaxpr
        consts = [_literal_interval(c) if not isinstance(c, RefCell) else c
                  for c in closed_jaxpr.consts]
        return self.run_jaxpr(jaxpr, consts, seeds, path)

    def run_jaxpr(self, jaxpr, consts, args, path) -> list:
        env: dict[Any, Any] = {}
        assert len(jaxpr.constvars) == len(consts), \
            (len(jaxpr.constvars), len(consts))
        for v, c in zip(jaxpr.constvars, consts, strict=True):
            env[v] = c
        assert len(jaxpr.invars) == len(args), \
            (path, len(jaxpr.invars), len(args))
        for v, a in zip(jaxpr.invars, args, strict=True):
            env[v] = a
        for i, eqn in enumerate(jaxpr.eqns):
            self.eqn(eqn, env, f"{path}/{i}:{eqn.primitive.name}")
        return [self.read(env, v) for v in jaxpr.outvars]

    def eqn(self, eqn, env, path):
        name = eqn.primitive.name
        handler = _STRUCTURAL.get(name)
        if handler is not None:
            outs = handler(self, eqn, env, path)
        else:
            invals = [self.read(env, a) for a in eqn.invars]
            fn = _TRANSFER.get(name)
            if fn is None:
                outs = []
                for ov in eqn.outvars:
                    outs.append(dtype_range(_aval(ov).dtype))
                self.note_once("unproven", path,
                               f"no transfer function for '{name}' "
                               "(result widened to dtype range)")
            else:
                outs = fn(self, eqn, invals, path)
                if not isinstance(outs, list):
                    outs = [outs]
        for ov, out in zip(eqn.outvars, outs, strict=True):
            if type(ov).__name__ == "DropVar":
                continue        # unused result (e.g. a store's old value)
            env[ov] = out
            if isinstance(out, Interval) and not self.mute:
                a = _aval(ov)
                self.records.append(OpRecord(
                    prim=name, path=path, dtype=str(a.dtype),
                    lo=out.lo, hi=out.hi))

    # -- pallas simulation --------------------------------------------------

    def run_pallas(self, eqn, env, path):
        params = eqn.params
        body = params["jaxpr"]
        gm = params["grid_mapping"]
        grid = tuple(gm.grid)
        n_index = gm.num_index_operands
        n_in = gm.num_inputs
        n_out = gm.num_outputs
        n_scratch = gm.num_scratch_operands
        invals = [self.read(env, a) for a in eqn.invars]
        kname = params.get("name", "") or "body"
        bpath = f"{path}[{kname}]"

        cells = []
        for k in range(n_index + n_in):
            a = _aval(body.invars[k])
            cells.append(RefCell("input", invals[k], a.dtype))
        for k in range(n_out):
            a = _aval(body.invars[n_index + n_in + k])
            cells.append(RefCell("output", None, a.dtype))
        for k in range(n_scratch):
            a = _aval(body.invars[n_index + n_in + n_out + k])
            cells.append(RefCell("scratch", None, a.dtype))
        assert len(body.invars) == len(cells), \
            (bpath, len(body.invars), len(cells))
        self.cells.extend(cells)

        # Concretize as many *trailing* grid axes as fit the trip budget
        # (trailing axes iterate fastest and carry the reduction /
        # multi-pass structure — init-at-first-trip and finalize /
        # cross-pass predicates only decide when those axes are points).
        # Leading axes are independent program instances and stay
        # abstract. The reduction axis itself must be concrete or the
        # certificate is refused (budget finding), because an abstract
        # accumulator never converges.
        n_axes = len(grid)
        first_concrete = n_axes
        trips = 1
        while first_concrete > 0 and trips * grid[first_concrete - 1] \
                <= MAX_GRID_TRIPS:
            first_concrete -= 1
            trips *= grid[first_concrete]
        if n_axes and first_concrete == n_axes:
            self.add_finding(Finding(
                kind="budget", prim="pallas_call", path=bpath, dtype="",
                ival="", bound=str(MAX_GRID_TRIPS),
                message=f"innermost grid axis {grid[-1]} exceeds the "
                        f"{MAX_GRID_TRIPS}-trip simulation budget; "
                        "analyze a smaller geometry"))
            trips = 0

        frame = _PallasFrame(grid)
        self.frames.append(frame)
        concrete_axes = list(range(first_concrete, n_axes))
        concrete_sizes = [grid[a] for a in concrete_axes]
        saved_mute = self.mute
        try:
            # Sweep 0 warms scratch to its converged state with
            # reporting muted (cross-sweep reads of not-yet-written
            # scratch would otherwise pollute the report); sweep 1
            # replays from the converged state and records.
            for sweep in range(PALLAS_SWEEPS):
                self.mute = saved_mute or sweep < PALLAS_SWEEPS - 1
                if sweep == PALLAS_SWEEPS - 1:
                    for c in cells:
                        if c.kind == "output":
                            c.ival = None
                for t in range(trips):
                    rem = t
                    for a, n in zip(reversed(concrete_axes),
                                    reversed(concrete_sizes), strict=True):
                        frame.concrete[a] = rem % n
                        rem //= n
                    self.run_jaxpr(body, [], list(cells), bpath)
        finally:
            self.mute = saved_mute
            self.frames.pop()
            for c in cells:
                self.cells.remove(c)

        outs = []
        for k in range(n_out):
            c = cells[n_index + n_in + k]
            if c.ival is None:
                self.note_once("uninit_read", bpath,
                               "pallas output never written during the "
                               "simulated sweep")
                outs.append(dtype_range(c.dtype))
            else:
                outs.append(c.ival)
        return outs

    # -- ref state ----------------------------------------------------------

    def cell_read(self, cell: RefCell, path) -> Interval:
        if cell.ival is None:
            self.note_once("uninit_read", path,
                           "read of uninitialized scratch (widened to "
                           "dtype range)")
            return dtype_range(cell.dtype)
        return cell.ival

    def cell_write(self, cell: RefCell, val: Interval):
        if cell.kind == "output":
            cell.ival = val if cell.ival is None else cell.ival.join(val)
        else:
            cell.ival = val

    def snapshot_cells(self):
        return [(c, c.ival) for c in self.cells]

    def restore_cells(self, snap):
        for c, ival in snap:
            c.ival = ival


# ---------------------------------------------------------------------------
# Structural handlers (control flow, refs) — signature (interp, eqn, env,
# path) -> list of out values
# ---------------------------------------------------------------------------

def _h_jit(self: Interp, eqn, env, path):
    invals = [self.read(env, a) for a in eqn.invars]
    inner = eqn.params["jaxpr"]
    name = eqn.params.get("name", "")
    return self.run_closed(inner, invals, f"{path}({name})")


def _h_custom_call(self: Interp, eqn, env, path):
    invals = [self.read(env, a) for a in eqn.invars]
    inner = eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
    num_consts = eqn.params.get("num_consts", 0)
    return self.run_closed(inner, invals[num_consts:], path) \
        if num_consts else self.run_closed(inner, invals, path)


def _h_cond(self: Interp, eqn, env, path):
    invals = [self.read(env, a) for a in eqn.invars]
    pred, ops = invals[0], invals[1:]
    branches = eqn.params["branches"]
    if isinstance(pred, Interval) and pred.is_point:
        idx = min(max(int(pred.lo), 0), len(branches) - 1)
        return self.run_closed(branches[idx], ops, f"{path}#b{idx}")
    # unknown predicate: evaluate every branch from the same ref state,
    # join outputs and ref post-states
    snap = self.snapshot_cells()
    all_outs, post_states = [], []
    for idx, br in enumerate(branches):
        self.restore_cells(snap)
        all_outs.append(self.run_closed(br, ops, f"{path}#b{idx}"))
        post_states.append([c.ival for c, _ in snap])
    for k, (c, _) in enumerate(snap):
        posts = [st[k] for st in post_states if st[k] is not None]
        c.ival = join_all(posts) if posts else None
    outs = []
    for vals in zip(*all_outs, strict=True):
        if all(isinstance(v, Interval) for v in vals):
            outs.append(join_all(vals))
        else:                               # refs pass through unchanged
            outs.append(vals[0])
    return outs


def _h_scan(self: Interp, eqn, env, path):
    invals = [self.read(env, a) for a in eqn.invars]
    p = eqn.params
    inner, nc, ncarry = p["jaxpr"], p["num_consts"], p["num_carry"]
    length = p["length"]
    consts, carry, xs = invals[:nc], invals[nc:nc + ncarry], \
        invals[nc + ncarry:]
    trips = min(length, MAX_SCAN_TRIPS)
    ys = None
    for t in range(trips):
        outs = self.run_closed(inner, consts + carry + xs, f"{path}@{t}")
        new_carry, y = outs[:ncarry], outs[ncarry:]
        if t == trips - 1 and length > trips:
            # budget exceeded: widen the carry to its dtype range and
            # run one final sound iteration
            self.note_once("unproven", path,
                           f"scan length {length} > unroll budget "
                           f"{MAX_SCAN_TRIPS}; carry widened")
            widened = [dtype_range(_aval(v).dtype)
                       for v in inner.jaxpr.outvars[:ncarry]]
            outs = self.run_closed(inner, consts + widened + xs,
                                   f"{path}@w")
            new_carry, y = outs[:ncarry], outs[ncarry:]
        carry = new_carry
        ys = y if ys is None else [a.join(b) if isinstance(a, Interval)
                                   else a for a, b in zip(ys, y, strict=True)]
    if ys is None:                          # length == 0
        ys = [dtype_range(_aval(v).dtype)
              for v in inner.jaxpr.outvars[ncarry:]]
    return list(carry) + list(ys)


def _h_while(self: Interp, eqn, env, path):
    invals = [self.read(env, a) for a in eqn.invars]
    p = eqn.params
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    body = p["body_jaxpr"]
    bconsts = invals[cn:cn + bn]
    carry = invals[cn + bn:]
    for t in range(MAX_SCAN_TRIPS):
        new = self.run_closed(body, bconsts + carry, f"{path}@{t}")
        joined = [a.join(b) if isinstance(a, Interval) else b
                  for a, b in zip(carry, new, strict=True)]
        if all(not isinstance(a, Interval) or (a.lo == b.lo and a.hi == b.hi)
               for a, b in zip(carry, joined, strict=True)):
            return joined
        carry = joined
    self.note_once("unproven", path,
                   "while_loop did not converge within budget; carry "
                   "widened")
    return [dtype_range(_aval(v).dtype) for v in eqn.outvars]


def _h_pallas(self: Interp, eqn, env, path):
    return self.run_pallas(eqn, env, path)


def _h_get(self: Interp, eqn, env, path):
    cell = env[eqn.invars[0]]
    return [self.cell_read(cell, path)]


def _h_swap(self: Interp, eqn, env, path):
    cell = env[eqn.invars[0]]
    old = cell.ival if cell.ival is not None else dtype_range(cell.dtype)
    val = self.read(env, eqn.invars[1])
    self.cell_write(cell, val)
    return [old]


def _h_addupdate(self: Interp, eqn, env, path):
    cell = env[eqn.invars[0]]
    val = self.read(env, eqn.invars[1])
    old = self.cell_read(cell, path)
    self.cell_write(cell, old + val)
    return []


def _h_program_id(self: Interp, eqn, env, path):
    axis = eqn.params["axis"]
    if not self.frames:
        return [TOP]
    return [self.frames[-1].program_id(axis)]


def _h_num_programs(self: Interp, eqn, env, path):
    axis = eqn.params["axis"]
    if not self.frames:
        return [TOP]
    grid = self.frames[-1].grid
    return [point(grid[axis] if axis < len(grid) else 1)]


_STRUCTURAL = {
    "jit": _h_jit,
    "closed_call": _h_custom_call,
    "custom_jvp_call": _h_custom_call,
    "custom_vjp_call": _h_custom_call,
    "custom_vjp_call_jaxpr": _h_custom_call,
    "remat2": _h_custom_call,
    "cond": _h_cond,
    "scan": _h_scan,
    "while": _h_while,
    "pallas_call": _h_pallas,
    "get": _h_get,
    "swap": _h_swap,
    "addupdate": _h_addupdate,
    "program_id": _h_program_id,
    "num_programs": _h_num_programs,
}


# ---------------------------------------------------------------------------
# Transfer functions — signature (interp, eqn, invals, path) -> Interval
# or list of Intervals
# ---------------------------------------------------------------------------

def _odtype(eqn):
    return _aval(eqn.outvars[0]).dtype


def _t_add(self, eqn, invals, path):
    return self.check_fit("overflow", "add", path, _odtype(eqn),
                          invals[0] + invals[1])


def _t_sub(self, eqn, invals, path):
    return self.check_fit("overflow", "sub", path, _odtype(eqn),
                          invals[0] - invals[1])


def _t_mul(self, eqn, invals, path):
    return self.check_fit("overflow", "mul", path, _odtype(eqn),
                          invals[0] * invals[1])


def _t_neg(self, eqn, invals, path):
    return self.check_fit("overflow", "neg", path, _odtype(eqn), -invals[0])


def _t_abs(self, eqn, invals, path):
    return self.check_fit("overflow", "abs", path, _odtype(eqn),
                          invals[0].abs())


def _t_max(self, eqn, invals, path):
    a, b = invals
    return Interval(max(a.lo, b.lo), max(a.hi, b.hi))


def _t_min(self, eqn, invals, path):
    a, b = invals
    return Interval(min(a.lo, b.lo), min(a.hi, b.hi))


def _t_clamp(self, eqn, invals, path):
    lo, x, hi = invals
    return Interval(max(min(x.lo, hi.hi), lo.lo), min(max(x.hi, lo.lo),
                                                      hi.hi))


def _t_div(self, eqn, invals, path):
    dt = _odtype(eqn)
    if is_int_dtype(dt):
        out, had_zero = iv.div_int(invals[0], invals[1])
        if had_zero:
            self.note_once("zero_divisor", path,
                           f"integer divisor {invals[1]!r} may contain 0 "
                           "(quotient widened)")
        return out.meet(dtype_range(dt))
    return iv.div_float(invals[0], invals[1])


def _t_rem(self, eqn, invals, path):
    out, had_zero = iv.rem_int(invals[0], invals[1])
    if had_zero:
        self.note_once("zero_divisor", path,
                       f"rem divisor {invals[1]!r} may contain 0")
    dt = _odtype(eqn)
    return out.meet(dtype_range(dt)) if is_int_dtype(dt) else out


def _t_dot_general(self, eqn, invals, path):
    (lhs_c, _rhs_c), _ = eqn.params["dimension_numbers"]
    lhs_shape = _aval(eqn.invars[0]).shape
    n = 1
    for d in lhs_c:
        n *= lhs_shape[d]
    elem = invals[0] * invals[1]
    out = Interval(iv._mul(elem.lo, n), iv._mul(elem.hi, n))
    return self.check_fit("overflow", "dot_general", path, _odtype(eqn), out)


def _t_reduce_sum(self, eqn, invals, path):
    shape = _aval(eqn.invars[0]).shape
    n = 1
    for a in eqn.params["axes"]:
        n *= shape[a]
    x = invals[0]
    out = Interval(iv._mul(x.lo, n), iv._mul(x.hi, n))
    return self.check_fit("overflow", "reduce_sum", path, _odtype(eqn), out)


def _t_cumsum(self, eqn, invals, path):
    shape = _aval(eqn.invars[0]).shape
    n = shape[eqn.params["axis"]]
    x = invals[0]
    out = Interval(iv._mul(x.lo, n), iv._mul(x.hi, n))
    return self.check_fit("overflow", "cumsum", path, _odtype(eqn), out)


def _t_identity(self, eqn, invals, path):
    return invals[0]


def _t_reduce_bool(self, eqn, invals, path):
    return BOOL


def _t_pad(self, eqn, invals, path):
    return invals[0].join(invals[1])


def _t_concat(self, eqn, invals, path):
    return join_all(invals)


def _t_dus(self, eqn, invals, path):
    return invals[0].join(invals[1])


def _t_select_n(self, eqn, invals, path):
    pred, cases = invals[0], invals[1:]
    if pred.is_point:
        idx = min(max(int(pred.lo), 0), len(cases) - 1)
        return cases[idx]
    return join_all(cases)


def _t_iota(self, eqn, invals, path):
    shape = _aval(eqn.outvars[0]).shape
    dim = eqn.params["dimension"]
    return Interval(0, max(shape[dim] - 1, 0))


def _t_convert(self, eqn, invals, path):
    dt = _odtype(eqn)
    x = invals[0]
    if is_bool_dtype(dt):
        return BOOL
    if is_int_dtype(dt):
        lo = x.lo if x.lo in (-INF, INF) else math.floor(x.lo)
        hi = x.hi if x.hi in (-INF, INF) else math.ceil(x.hi)
        return self.check_fit("narrowing", "convert_element_type", path,
                              dt, Interval(lo, hi))
    return x


def _t_cmp_factory(op):
    def t(self, eqn, invals, path):
        a, b = invals
        if op == "eq":
            if a.is_point and b.is_point:
                return point(int(a.lo == b.lo))
            if a.hi < b.lo or b.hi < a.lo:
                return point(0)
        elif op == "ne":
            if a.is_point and b.is_point:
                return point(int(a.lo != b.lo))
            if a.hi < b.lo or b.hi < a.lo:
                return point(1)
        elif op == "lt":
            if a.hi < b.lo:
                return point(1)
            if a.lo >= b.hi:
                return point(0)
        elif op == "le":
            if a.hi <= b.lo:
                return point(1)
            if a.lo > b.hi:
                return point(0)
        elif op == "gt":
            if a.lo > b.hi:
                return point(1)
            if a.hi <= b.lo:
                return point(0)
        elif op == "ge":
            if a.lo >= b.hi:
                return point(1)
            if a.hi < b.lo:
                return point(0)
        return BOOL
    return t


def _t_and(self, eqn, invals, path):
    a, b = invals
    if not is_bool_dtype(_odtype(eqn)):
        return dtype_range(_odtype(eqn)).meet(
            Interval(0, max(a.hi, b.hi)) if a.lo >= 0 and b.lo >= 0
            else dtype_range(_odtype(eqn)))
    if (a.is_point and a.lo == 0) or (b.is_point and b.lo == 0):
        return point(0)
    if a.is_point and b.is_point:
        return point(int(bool(a.lo) and bool(b.lo)))
    return BOOL


def _t_or(self, eqn, invals, path):
    a, b = invals
    if not is_bool_dtype(_odtype(eqn)):
        return dtype_range(_odtype(eqn))
    if (a.is_point and a.lo == 1) or (b.is_point and b.lo == 1):
        return point(1)
    if a.is_point and b.is_point:
        return point(int(bool(a.lo) or bool(b.lo)))
    return BOOL


def _t_not(self, eqn, invals, path):
    a = invals[0]
    if not is_bool_dtype(_odtype(eqn)):
        return dtype_range(_odtype(eqn))
    if a.is_point:
        return point(int(not a.lo))
    return BOOL


def _t_xor(self, eqn, invals, path):
    if not is_bool_dtype(_odtype(eqn)):
        return dtype_range(_odtype(eqn))
    a, b = invals
    if a.is_point and b.is_point:
        return point(int(bool(a.lo) != bool(b.lo)))
    return BOOL


def _t_shift_left(self, eqn, invals, path):
    dt = _odtype(eqn)
    self.check_shift("shift_left", path, dt, invals[1])
    out = iv.shift_left(invals[0], invals[1].meet(
        Interval(0, max(dtype_bits(dt) - 1, 0))))
    return self.check_fit("overflow", "shift_left", path, dt, out)


def _t_shift_right_logical(self, eqn, invals, path):
    dt = _odtype(eqn)
    self.check_shift("shift_right_logical", path, dt, invals[1])
    bits = dtype_bits(dt) or 32
    sh = invals[1].meet(Interval(0, bits - 1))
    return iv.shift_right_logical(invals[0], sh, bits)


def _t_shift_right_arith(self, eqn, invals, path):
    dt = _odtype(eqn)
    self.check_shift("shift_right_arithmetic", path, dt, invals[1])
    sh = invals[1].meet(Interval(0, max(dtype_bits(dt) - 1, 0)))
    return iv.shift_right_arith(invals[0], sh)


def _t_clz(self, eqn, invals, path):
    bits = dtype_bits(_odtype(eqn)) or 32
    return iv.clz(invals[0], bits)


def _t_sign(self, eqn, invals, path):
    x = invals[0]
    lo = -1 if x.lo < 0 else (0 if x.lo == 0 else 1)
    hi = 1 if x.hi > 0 else (0 if x.hi == 0 else -1)
    return Interval(min(lo, hi), max(lo, hi))


def _mono(fn, guard=None):
    def t(self, eqn, invals, path):
        x = invals[0]
        def g(v, side):
            if guard is not None:
                v = guard(v, side)
            return v
        try:
            lo = g(fn(x.lo) if x.lo not in (-INF, INF) else
                   (0.0 if x.lo == -INF and fn is _exp_like else -INF),
                   "lo")
            hi = g(fn(x.hi) if x.hi not in (-INF, INF) else INF, "hi")
        except (OverflowError, ValueError):
            return TOP
        return Interval(lo, hi)
    return t


_exp_like = object()    # sentinel used by _mono's -inf handling


def _t_exp(self, eqn, invals, path):
    x = invals[0]
    lo = 0.0 if x.lo == -INF else (INF if x.lo > 700 else math.exp(x.lo))
    hi = INF if x.hi > 700 or x.hi == INF else math.exp(x.hi)
    return Interval(lo, hi)


def _t_exp2(self, eqn, invals, path):
    x = invals[0]
    lo = 0.0 if x.lo == -INF else (INF if x.lo > 1000 else 2.0 ** x.lo)
    hi = INF if x.hi > 1000 or x.hi == INF else 2.0 ** x.hi
    return Interval(lo, hi)


def _t_round(self, eqn, invals, path):
    x = invals[0]
    lo = x.lo if x.lo in (-INF, INF) else float(np.round(x.lo))
    hi = x.hi if x.hi in (-INF, INF) else float(np.round(x.hi))
    return Interval(lo, hi)


def _t_floor(self, eqn, invals, path):
    x = invals[0]
    return Interval(x.lo if x.lo in (-INF, INF) else math.floor(x.lo),
                    x.hi if x.hi in (-INF, INF) else math.floor(x.hi))


def _t_ceil(self, eqn, invals, path):
    x = invals[0]
    return Interval(x.lo if x.lo in (-INF, INF) else math.ceil(x.lo),
                    x.hi if x.hi in (-INF, INF) else math.ceil(x.hi))


def _t_integer_pow(self, eqn, invals, path):
    x, y = invals[0], eqn.params["y"]
    if y < 0:
        return TOP
    cands = [x.lo ** y, x.hi ** y]
    if x.lo < 0 < x.hi:
        cands.append(0)
    out = Interval(min(cands), max(cands))
    return self.check_fit("overflow", "integer_pow", path, _odtype(eqn), out)


def _t_sqrt(self, eqn, invals, path):
    x = invals[0]
    lo = math.sqrt(max(x.lo, 0.0)) if x.lo != INF else INF
    hi = INF if x.hi == INF else math.sqrt(max(x.hi, 0.0))
    return Interval(lo, hi)


def _t_logistic(self, eqn, invals, path):
    return Interval(0.0, 1.0)


def _t_tanh(self, eqn, invals, path):
    return Interval(-1.0, 1.0)


def _t_stop_gradient(self, eqn, invals, path):
    return invals[0]


_TRANSFER = {
    "add": _t_add,
    "sub": _t_sub,
    "mul": _t_mul,
    "neg": _t_neg,
    "abs": _t_abs,
    "max": _t_max,
    "min": _t_min,
    "clamp": _t_clamp,
    "div": _t_div,
    "rem": _t_rem,
    "dot_general": _t_dot_general,
    "reduce_sum": _t_reduce_sum,
    "cumsum": _t_cumsum,
    "reduce_max": _t_identity,
    "reduce_min": _t_identity,
    "reduce_and": _t_reduce_bool,
    "reduce_or": _t_reduce_bool,
    "broadcast_in_dim": _t_identity,
    "tile": _t_identity,
    "reshape": _t_identity,
    "transpose": _t_identity,
    "squeeze": _t_identity,
    "slice": _t_identity,
    "rev": _t_identity,
    "copy": _t_identity,
    "multiple_of": _t_identity,
    "dynamic_slice": _t_identity,
    "dynamic_update_slice": _t_dus,
    "gather": _t_identity,
    "pad": _t_pad,
    "concatenate": _t_concat,
    "select_n": _t_select_n,
    "iota": _t_iota,
    "convert_element_type": _t_convert,
    "eq": _t_cmp_factory("eq"),
    "ne": _t_cmp_factory("ne"),
    "lt": _t_cmp_factory("lt"),
    "le": _t_cmp_factory("le"),
    "gt": _t_cmp_factory("gt"),
    "ge": _t_cmp_factory("ge"),
    "and": _t_and,
    "or": _t_or,
    "not": _t_not,
    "xor": _t_xor,
    "shift_left": _t_shift_left,
    "shift_right_logical": _t_shift_right_logical,
    "shift_right_arithmetic": _t_shift_right_arith,
    "clz": _t_clz,
    "sign": _t_sign,
    "exp": _t_exp,
    "exp2": _t_exp2,
    "round": _t_round,
    "floor": _t_floor,
    "ceil": _t_ceil,
    "integer_pow": _t_integer_pow,
    "sqrt": _t_sqrt,
    "rsqrt": _t_sqrt,          # conservative: non-negative, unbounded above
    "logistic": _t_logistic,
    "tanh": _t_tanh,
    "stop_gradient": _t_stop_gradient,
}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def analyze_jaxpr(closed_jaxpr, seeds) -> AnalysisResult:
    """Run the abstract interpreter over ``closed_jaxpr`` with the given
    per-input seed intervals (``None`` entries default to the input's
    dtype range)."""
    interp = Interp()
    invars = closed_jaxpr.jaxpr.invars
    assert len(seeds) == len(invars), (len(seeds), len(invars))
    seeded = []
    for s, v in zip(seeds, invars, strict=True):
        seeded.append(_seed_for(v.aval) if s is None
                      else s.meet(dtype_range(v.aval.dtype)))
    outvals = interp.run_closed(closed_jaxpr, seeded)
    return AnalysisResult(findings=interp.findings, notes=interp.notes,
                          records=interp.records, outvals=outvals)
