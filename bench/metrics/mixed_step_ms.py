"""Model step: device milliseconds per mixed (chunk-wide) step. The
``mixed_phase`` loops of the segments whose rounds the trace holds
whole, over those segments' mixed steps (``mixed`` of
``serve.dispatch``)."""

from benchlib import spans


def read(run):
    return spans.phase_step_ms(spans.of_run(run), "mixed_phase")
