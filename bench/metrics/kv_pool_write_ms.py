"""Model step: device milliseconds per serve step spent writing the KV
pool and carrying each layer's cache through the layer scan: ops under
the ``kv_write`` or ``layer_carry`` scope (the pool write, its page-table
bookkeeping, each layer's slice and write-back of its cache), in the
segments whose rounds the trace holds whole, over those segments' steps
(``steps`` of ``serve.dispatch``). 0 where neither scope has ops."""

import bisect

from benchlib import spans

SCOPES = frozenset(("kv_write", "layer_carry"))


def per_step_ms(trace):
    """The metric over a ``benchlib.spans.Spans``; None without a
    recorded round."""
    if trace is None:
        return None
    starts = [op.start_ns for op in trace.ops]
    ns, steps = 0.0, 0
    for rnd in trace.rounds():
        lo = bisect.bisect_left(starts, rnd.span.start_ns)
        hi = bisect.bisect_left(starts, rnd.span.end_ns)
        ns += sum(op.dur_ns for op in trace.ops[lo:hi]
                  if not op.container and rnd.span.holds(op)
                  and SCOPES.intersection(op.scopes))
        steps += int(rnd.child(spans.SPAN_PREFIX + "dispatch")
                     .args["steps"])
    if steps == 0:
        return None
    return ns / 1e6 / steps


def read(run):
    return per_step_ms(spans.of_run(run))
