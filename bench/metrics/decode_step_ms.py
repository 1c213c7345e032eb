"""Model step: device milliseconds per decode step. The ``decode_phase``
loops of the segments whose rounds the trace holds whole, over those
segments' decode steps (``steps - mixed`` of ``serve.dispatch``)."""

from benchlib import spans


def read(run):
    return spans.phase_step_ms(spans.of_run(run), "decode_phase")
