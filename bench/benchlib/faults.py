"""Faults planted under the served path, for the tests and the
calibration runs that must see ``correct`` turn false.

- ``token``: a token altered where it is produced: the greedy pick of
  the serve segments is bumped by one whenever the argmax id is a
  multiple of 13 (about one token in thirteen).
- ``kv``: the cache write returns its state unchanged: the paged K/V
  appends keep their bookkeeping but drop the bytes.

Plant before the first serve of the process, so the segments are traced
with the fault in them.
"""

from __future__ import annotations

import dataclasses


def plant(kind: str):
    if kind == "token":
        import jax.numpy as jnp

        import repro.launch.steps as steps
        orig = steps.sample_token_rows

        def bumped(logits, keys, temperature, *, sample, advance=None):
            tok, keys = orig(logits, keys, temperature, sample=sample,
                             advance=advance)
            return jnp.where(tok % 13 == 0, tok + 1, tok), keys

        steps.sample_token_rows = bumped
    elif kind == "kv":
        from repro.attention import PagedKVState

        def dropping(orig):
            def append(self, *a, **kw):
                new = orig(self, *a, **kw)
                return dataclasses.replace(new, k=self.k, v=self.v)
            return append

        PagedKVState.append_chunk = dropping(PagedKVState.append_chunk)
        PagedKVState.decode_append = dropping(PagedKVState.decode_append)
    else:
        raise ValueError(f"unknown fault {kind!r}")
