"""Bytes a kernel has to move, computed from the shapes it works on."""
from typing import Sequence


def filter_image_bytes(ts: Sequence[int], m: int, w: int) -> int:
    """Image bytes the filter reads for one query whose sets have depths
    ``ts``: a set of depth ``t`` holds ``2**t`` groups of ``m`` images of
    ``w`` bits.  Each input word counts once and padding not at all, so
    a set broadcast to a deeper one's groups is not counted again."""
    return sum((1 << t) * m * w // 8 for t in ts)
