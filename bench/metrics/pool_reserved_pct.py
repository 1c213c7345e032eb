"""Cache: mean share of the KV pool's pages that admission holds
reserved (each request's worst-case page need, and index pins), over the
traced window's admission rounds, in percent (``serve.pool`` span
arguments)."""

from benchlib import pool


def read(run):
    return pool.mean_share(pool.rounds(run), "pages_reserved")
