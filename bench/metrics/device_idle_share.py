"""Device: share of the traced window in which no op ran on the chip,
in percent (busy = union of the ``XLA Ops`` intervals)."""

from benchlib import trace


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace_window_s)
