"""Exact causal self-attention (``repro.kernels.attention``: the Pallas
splash kernels): the work one step's attention needs, whatever implements
it.

Each layer's causal attention over a row of S tokens, H query heads of hd
and K kv heads: the forward's two products (QK^T and PV) and the
backward's four (dV, dP, dQ and dK), each 2 hd operations for every
query-key pair at or below the diagonal of each head, S (S + 1) / 2 pairs.
12 hd H S (S + 1) / 2 operations a layer and row, with no recomputation
counted (the decoder blocks are rematerialised, so the step runs the
forward twice): a floor that no implementation can pass.  Bytes: q, k, v,
the output, its cotangent, dq, dk and dv, each read or written once in
bfloat16.  The compute bound decides at these widths.
"""

from __future__ import annotations

from typing import Tuple

#: searched in each trace operation's name and string statistics: the
#: custom calls splash names after its kernels, ``splash_mha_fwd_*``,
#: ``splash_mha_dq_*`` and ``splash_mha_dkv_*``, and no op that reads them
TRACE_PATTERN = r"(?<![\w.])%?splash_mha_(?:fwd|dq|dkv)_\w+(?:\.\d+)? = "

PRODUCTS = 6   # QK^T and PV forward; dV, dP, dQ and dK backward
BYTES = 2      # bfloat16 activations and their gradients


def work(config: dict, traffic: dict, chips: int) -> Tuple[float, float]:
    """(bytes, operations) of one step's causal attention on one chip."""
    hd, H = config["head_dim"], config["num_attention_heads"]
    K, L = config["num_key_value_heads"], config["num_hidden_layers"]
    S = traffic["seq"]
    rows = traffic["global_batch"] / chips
    ops = PRODUCTS * 2.0 * hd * H * S * (S + 1) / 2 * L * rows
    nbytes = BYTES * 4 * (H + K) * S * hd * L * rows
    return nbytes, ops
