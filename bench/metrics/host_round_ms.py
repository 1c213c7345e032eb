"""Scheduler: median host milliseconds per serve round, that is the
``serve.round`` span less its ``serve.wait`` child (scheduling, pool
dispatches, the segment dispatch, readback and bookkeeping)."""

from benchlib import spans


def read(run):
    return spans.host_round_ms(spans.of_run(run))
