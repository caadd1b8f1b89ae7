"""What one run of a cell measured: everything a metric reader may read."""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from chipbench.trace import Reduction


@dataclasses.dataclass
class Facts:
    config: dict           # chipbench/configs/<config>.json
    traffic: dict          # chipbench/traffic/<traffic>.json
    chips: int
    peaks: dict            # chipbench/peaks.json entry of the device kind
    setup_s: float         # process start to the first timed step
    steps: int             # train steps in the window
    tokens: int            # trained tokens in the window, all chips
    window_s: float        # window start to the last step's completion
    memory: dict           # the compiled step's memory_analysis(), bytes
    peak_bytes: int        # the devices' peak_bytes_in_use, the fullest chip
    leaf_sizes: List[int]  # parameter leaf sizes, flatten order
    flops_per_token: float
    trace: Optional[Reduction] = None
