"""Scheduler: 95th percentile of the decode steps between a request
becoming due and its admission (``admitted_step - arrival``), over the
requests whose first token fell in the window."""

from benchlib.window import quantile


def read(run):
    waits = [r.admitted_step - r.arrival for r in run.window.first_in]
    return float(quantile(waits, 0.95)) if waits else None
