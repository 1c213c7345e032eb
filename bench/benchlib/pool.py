"""The serve loop's page ledger in a traced run: each admission round's
``serve.pool`` span carries, as arguments, the pages the host has
reserved (``pages_reserved``), the pages that hold written tokens
(``pages_in_use``) and the pages left unreserved (``pages_free``), read
from the host's own bookkeeping. A program whose spans lack them reads
as nothing.

The spans are read with ``jax.profiler.ProfileData`` alone: the ledger
needs no device op names, so the trace's protobuf is not walked again
(``spans.load`` does that, and at 32 layers it is the slow part of a
traced run's readers)."""

from __future__ import annotations

import pathlib

from benchlib import spans

KEYS = ("pages_reserved", "pages_in_use", "pages_free")
SPAN = spans.SPAN_PREFIX + "pool"

_CACHE: dict = {}


def read_rounds(path) -> list:
    """The arguments of every ``serve.pool`` span in the ``.xplane.pb``
    at ``path``, in the order the host planes list them."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    return [dict(e.stats)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines
            for e in line.events if e.name == SPAN]


def rounds(run, trace_dir=None):
    """The traced run's ``serve.pool`` span arguments (read once per
    run), or None for an untraced run or one that left no trace file.
    ``trace_dir`` defaults to the runner's."""
    if run.trace is None:
        return None
    if trace_dir is None:
        from benchlib.runner import TRACE_DIR as trace_dir
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    key = (str(files[-1]), files[-1].stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = read_rounds(files[-1])
    return _CACHE[key]


def mean_share(rounds_args, key: str):
    """Mean over the traced rounds (``rounds_args``: each ``serve.pool``
    span's arguments) of ``key`` / the pool's usable pages (reserved +
    free), in percent; None without such rounds."""
    if rounds_args is None:
        return None
    shares = []
    for args in rounds_args:
        if not all(k in args for k in KEYS):
            continue
        pages = float(args["pages_reserved"]) + float(args["pages_free"])
        if pages > 0:
            shares.append(100.0 * float(args[key]) / pages)
    return sum(shares) / len(shares) if shares else None
