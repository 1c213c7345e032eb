"""``pool_reserved_pct`` and ``pool_in_use_pct``: the ``serve.pool``
spans' ledger arguments averaged over the traced rounds, read from a
profiler trace, and nothing from a program whose spans lack them."""

import types

import jax

from benchlib import pool


def ledger(reserved, in_use, free):
    return {"pages_reserved": reserved, "pages_in_use": in_use,
            "pages_free": free}


def test_means_over_the_rounds():
    rounds = [ledger(100, 40, 100), ledger(150, 90, 50)]
    assert pool.mean_share(rounds, "pages_reserved") == (50 + 75) / 2
    assert pool.mean_share(rounds, "pages_in_use") == (20 + 45) / 2


def test_nothing_without_the_ledger():
    assert pool.mean_share([{}], "pages_reserved") is None
    assert pool.mean_share(None, "pages_in_use") is None


def test_rounds_read_from_a_profiler_trace(tmp_path):
    """The spans as ``serve_continuous`` writes them, into a real trace:
    the ledger's rounds come back in order, other spans and a round
    without arguments are passed over by the mean."""
    jax.profiler.start_trace(str(tmp_path))
    for reserved, in_use in ((100, 40), (150, 90)):
        with jax.profiler.TraceAnnotation("serve.round"):
            with jax.profiler.TraceAnnotation(
                    "serve.pool", pages_reserved=reserved,
                    pages_in_use=in_use, pages_free=200 - reserved):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    with jax.profiler.TraceAnnotation("serve.pool"):
        pass
    jax.profiler.stop_trace()
    run = types.SimpleNamespace(trace=object())
    rounds = pool.rounds(run, tmp_path)
    assert [r.get("pages_reserved") for r in rounds] == [100, 150, None]
    assert pool.mean_share(rounds, "pages_in_use") == (20 + 45) / 2
    assert pool.rounds(types.SimpleNamespace(trace=None), tmp_path) is None
