"""The benchmark's yardsticks against hand counts at small shapes: FLOPs
per token of each configuration's model, the pack kernel's bytes and
operations, the peaks table."""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import peaks  # noqa: E402
from chipbench.kernels import pack  # noqa: E402
from chipbench.models import dense_gqa, mamba2_ssd  # noqa: E402


def test_dense_flops_per_token_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
           "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
           "num_hidden_layers": 3}
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, mlp 3*8*16 = 576; head 10*8
    params = 3 * 576 + 80
    attn = 6 * 3 * 2 * 4 * 5            # causal scores and values, seq 5
    assert dense_gqa.flops_per_token(cfg, 5) == 6 * params + attn


def test_mamba2_flops_per_token_by_hand():
    cfg = {"hidden_size": 4, "expand": 2, "state_size": 3, "head_dim": 2,
           "conv_kernel": 4, "vocab_size": 10, "num_hidden_layers": 2}
    # d 4, d_inner 8, 4 heads of 2: in 4*(16+6+4) = 104, out 8*4 = 32
    params = 2 * (104 + 32) + 40
    scan = 2 * 3 * (5 * 4 * 3 * 2 + 2 * 4 * (8 + 6))
    assert mamba2_ssd.flops_per_token(cfg, 7) == 6 * params + scan


def test_pack_kernel_work_by_hand():
    # a leaf of 600 in blocks of 256: 3 blocks, 2 kept each
    nbytes, ops = pack.work([600, 256], block=256, k=2)
    assert nbytes == (12 * 600 + 8 * 3 * 2) + (12 * 256 + 8 * 1 * 2)
    assert ops == pack.OPS_PER_ELEMENT * (600 + 256)


def test_peaks_table():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    import json

    with open(peaks.PATH) as f:
        assert "TPU v5e" in json.load(f)["source"]
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
