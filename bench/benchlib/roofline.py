"""A kernel's share of its roofline over the traced window: the least
time its calls could take (the larger of their required int8 operations
at the int8 peak and their required bytes at the HBM bandwidth, per
step, counted from lengths by ``costs``) over the device time the trace
gives those calls."""

from __future__ import annotations

from benchlib import trace


def share(run, step_kind: str, match):
    """Percent of roofline of the ops ``match`` accepts, whose work is
    that of the traced steps of ``step_kind``; None where the trace or
    the steps have nothing to read."""
    if run.trace is None or not run.steps:
        return None
    seconds, calls = trace.kernel_s(run.trace, match)
    if calls == 0 or seconds <= 0:
        return None
    m, p = run.model, run.peaks
    least = 0.0
    for s in run.steps:
        if s.kind != step_kind or s.q.size == 0:
            continue
        least += max(m.attn_ops(s.q, s.kv) / p["int8_ops"],
                     m.attn_bytes(s.q, s.kv) / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds if least > 0 else None
