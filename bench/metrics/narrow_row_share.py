"""Kernels: percent of a mixed step's live rows that stream the ragged
onepass-paged kernel's narrow query tile (the decoding rows, one query
each) and not the chunk-wide tile (the prefilling rows). Σ
``rows_narrow`` / Σ (``rows_narrow`` + ``rows_wide``) over the
``serve.dispatch`` spans of the traced rounds' mixed segments; nothing
from a program whose spans lack those arguments."""

from benchlib import spans


def share(traced):
    if traced is None:
        return None
    narrow = wide = 0
    for rnd in traced.rounds():
        args = rnd.child(spans.SPAN_PREFIX + "dispatch").args
        if "rows_narrow" in args and "rows_wide" in args:
            narrow += int(args["rows_narrow"])
            wide += int(args["rows_wide"])
    if narrow + wide == 0:
        return None
    return 100.0 * narrow / (narrow + wide)


def read(run):
    return share(spans.of_run(run))
