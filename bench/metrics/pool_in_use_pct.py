"""Cache: mean share of the KV pool's pages that hold written tokens
(live rows' prefixes and pinned prefix pages, a shared page once), over
the traced window's admission rounds, in percent (``serve.pool`` span
arguments). Its distance below ``pool_reserved_pct`` is what worst-case
reservation holds back."""

from benchlib import pool


def read(run):
    return pool.mean_share(pool.rounds(run), "pages_in_use")
