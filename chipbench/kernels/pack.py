"""The block-top-k pack kernel (``efbv_pack_update``): the work its call
needs, whatever implements it.

One call per parameter leaf of a worker: read g and h (float32), write the
new h (float32), write the payload (a float32 value and an int32 index for
each of the k kept entries of every block of b).  Operations: per element
the difference, its magnitude, and h + lam * d (4); the selection is
counted as nothing, so the operation bound is a floor.  At 197 TFLOP/s
against 819 GB/s the bytes decide the bound by some four orders of
magnitude.
"""

from __future__ import annotations

import math
from typing import Iterable, Tuple

#: searched in each trace operation's name and string statistics
TRACE_PATTERN = r"pack_update"

OPS_PER_ELEMENT = 4


def work(leaf_sizes: Iterable[int], block: int, k: int) -> Tuple[float, float]:
    """(bytes, operations) of one worker's calls, one per leaf."""
    nbytes = ops = 0.0
    for size in leaf_sizes:
        kept = math.ceil(size / block) * k
        nbytes += 4 * size * 3 + 8 * kept
        ops += OPS_PER_ELEMENT * size
    return nbytes, ops
