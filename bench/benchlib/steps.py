"""The traced window's steps, rebuilt from what each fused segment
returned about itself: per row and step, the tokens it processed
(``grants``) and emitted (``emits``), and the row's position going in.
A row's context after a step is its position going in plus the tokens
it has processed so far in the segment."""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Step:
    kind: str                 # "mixed" (chunk-wide) or "decode"
    q: np.ndarray             # tokens processed by each live row
    kv: np.ndarray            # each live row's context after the step
    emitted: int              # tokens emitted in the step


def rebuild(calls) -> list[Step]:
    out = []
    for c in calls:
        grants = np.asarray(c.grants, np.int64)
        emits = np.asarray(c.emits).astype(np.int64)
        ctx = np.asarray(c.pos_in, np.int64)[:, None] + np.cumsum(grants, 1)
        for t in range(grants.shape[1]):
            live = grants[:, t] > 0
            out.append(Step("mixed" if t < c.mixed_steps else "decode",
                            grants[live, t], ctx[live, t],
                            int(emits[:, t].sum())))
    return out
