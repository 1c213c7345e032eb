"""Model step: share of device busy time, in percent, spent in ops whose
``op_name`` carries none of the program's scopes: work that XLA put in
(layout copies and the like), since the program names all of its own."""

from benchlib import spans


def read(run):
    return spans.unscoped_share(spans.of_run(run))
