"""``narrow_row_share`` on synthetic traces: the mixed dispatches' row
counts give the share, decode-only dispatches and a dispatch before the
trace's first recorded round count for nothing, and a program whose
dispatches lack the counts reads as nothing."""

import types

from benchlib import spans
from benchlib.spans import Span
from metrics import narrow_row_share

MS = 1e6                                     # ns


def rnd(start, **dispatch):
    return [Span("serve.round", start * MS, 10 * MS, {}),
            Span("serve.dispatch", (start + 2) * MS, 1 * MS,
                 dict(segment=start, step=start, steps=16, **dispatch))]


def test_share_over_the_mixed_dispatches():
    host = [Span("serve.dispatch", 0.0, 1 * MS,          # no round: not read
                 dict(mixed=16, steps=16, rows_narrow=9, rows_wide=0)),
            *rnd(10, mixed=16, rows_narrow=4, rows_wide=8),
            *rnd(30, mixed=0),
            *rnd(50, mixed=8, rows_narrow=6, rows_wide=2)]
    traced = spans.from_events([], host)
    assert narrow_row_share.share(traced) == 100.0 * 10 / 20


def test_nothing_without_the_counts():
    host = [*rnd(10, mixed=16), *rnd(30, mixed=0)]
    assert narrow_row_share.share(spans.from_events([], host)) is None
    assert narrow_row_share.share(None) is None
    assert narrow_row_share.read(types.SimpleNamespace(trace=None)) is None
