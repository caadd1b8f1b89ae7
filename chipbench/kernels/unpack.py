"""The block-top-k decode-sum kernel (``block_unpack_sum``): the work its
calls need, whatever implements them.

One call per parameter leaf of the master: read each of the n workers'
payloads (a float32 value and an int32 index for each of the k kept entries
of every block of b) and write the dense float32 sum once.  Operations: one
add of each worker's message at every element (n per element); the
matching of positions is counted as nothing, so both counts are floors.
Bytes decide the bound.
"""

from __future__ import annotations

import math
from typing import Iterable, Tuple

#: searched in each trace operation's name and string statistics: the
#: instruction XLA names after the kernel's jitted function, and no other
#: (the pack kernel's ``efbv_pack_update`` holds ``pack`` but not this name)
TRACE_PATTERN = r"(?<![\w.])%?block_unpack_sum(?:\.\d+)? = "


def work(leaf_sizes: Iterable[int], block: int, k: int,
         n: int) -> Tuple[float, float]:
    """(bytes, operations) of one step's calls, one per leaf, decoding the
    payloads of ``n`` workers."""
    nbytes = ops = 0.0
    for size in leaf_sizes:
        kept = math.ceil(size / block) * k
        nbytes += 4 * size + 8 * n * kept
        ops += n * size
    return nbytes, ops
