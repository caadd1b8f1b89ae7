"""The MoE layer's grouped products (``repro.kernels.gmm``: megablox
``gmm`` and ``tgmm``): the work one step's calls need, whatever implements
them.

Each layer runs three products over the held experts' rows, gate and up
(d -> ff) and down (ff -> d), each a call of (R, K) rows against G expert
matrices (K, N).  A step makes four calls of each: the forward, its
recomputation in the backward pass (the decoder blocks are
rematerialised), the row gradient (a transposed ``gmm``) and the weight
gradient (``tgmm``).  Every call reads the rows and the G matrices once and
writes its (R, N) or (G, K, N) result once, in bfloat16, and does 2 R K N
operations.  R is the held experts' expected load: tokens x k x G / E
assignments a layer.  The compute bound decides at these widths.
"""

from __future__ import annotations

from typing import Tuple

#: searched in each trace operation's name and string statistics: the
#: instructions XLA names after the two kernels' jitted functions
TRACE_PATTERN = r"(?<![\w.])%?t?gmm(?:\.\d+)? = "

CALLS_PER_PRODUCT = 4
BYTES = 2   # bfloat16 rows, matrices and results


def work(config: dict, tokens: int) -> Tuple[float, float]:
    """(bytes, operations) of one step's grouped products on one chip that
    trains ``tokens`` tokens a step."""
    d, ff = config["hidden_size"], config["intermediate_size"]
    held, E = config["num_local_experts"], config["num_experts_total"]
    rows = tokens * config["num_experts_per_tok"] * held / E
    calls = CALLS_PER_PRODUCT * 3 * config["num_hidden_layers"]
    nbytes = calls * BYTES * (rows * (d + ff) + held * d * ff)
    ops = calls * 2.0 * rows * d * ff
    return nbytes, ops
