"""Kernels: the ragged onepass-paged ITA attention kernel's share of its
roofline. Its calls are the fused attention custom calls with more than
8 query rows (a chunk); their work is that of the traced mixed steps
(prompt chunks and the decode rows that ride with them)."""

from benchlib.roofline import share
from metrics.decode_attn_roofline import QROWS


def is_chunk_kernel(event) -> bool:
    if "tpu_custom_call" not in event.name:
        return False
    m = QROWS.search(event.name)
    return bool(m) and int(m.group(1)) > 8


def read(run):
    return share(run, "mixed", is_chunk_kernel)
