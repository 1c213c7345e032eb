"""Kernels: the decode-paged ITA attention kernel's share of its
roofline. Its calls are the fused attention custom calls whose query
block is the decode kernel's (at most 8 query rows); their work is that
of the traced decode steps."""

import re

from benchlib.roofline import share

QROWS = re.compile(r"s8\[\d+,(\d+),\d+\]")


def is_decode_kernel(event) -> bool:
    if "tpu_custom_call" not in event.name:
        return False
    m = QROWS.search(event.name)
    return bool(m) and int(m.group(1)) <= 8


def read(run):
    return share(run, "decode", is_decode_kernel)
