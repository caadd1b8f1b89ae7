"""The attention kernels' yardsticks: their trace pattern on the instruction
texts of the three splash kernels as the TPU compiler names them, and on
ops that read them, and the floor of one step's causal attention pinned at
the granite cell's shapes."""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from chipbench.kernels import attn, gmm, pack, unpack  # noqa: E402

# instruction texts of the attention's forward and backward compiled for a
# TPU v5e at granite's shapes, shortened: each kernel's own custom call,
# and the ops that take its outputs apart
FWD = ("%splash_mha_fwd_residuals.1 = (f32[512,128]{1,0:T(8,128)}, "
       "bf16[24,4096,64]{2,1,0:T(8,128)(2,1)}, f32[24,4096,128]{2,1,0:"
       "T(8,128)S(1)}) custom-call(%copy-done.17, %copy-done.13, "
       "%multiply_bitcast_fusion.1), custom_call_target=\"tpu_custom_call\"")
FWD_ROOT = ("ROOT %splash_mha_fwd_no_residuals = (f32[512,128]{1,0}, "
            "bf16[56,512,64]{2,1,0}) custom-call(%a, %b, %c)")
DQ = ("%splash_mha_dq_no_residuals.1 = (f32[512,64]{1,0:T(8,128)}, "
      "bf16[24,4096,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(%copy-done.18, "
      "%copy-done.14, %custom-call), custom_call_target=\"tpu_custom_call\"")
DKV = ("%splash_mha_dkv_no_residuals.1 = (f32[512,64]{1,0:T(8,128)}, "
       "bf16[8,4096,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(%copy-done.16, "
       "%copy-done.15), custom_call_target=\"tpu_custom_call\"")
READS_FWD = ("%pallas_call.19 = f32[24,4096,128]{2,1,0:T(8,128)S(1)} "
             "get-tuple-element(%splash_mha_fwd_residuals.1), index=4, "
             "metadata={op_name=\"jit(f)/jvp(splash_mha_fwd_residuals)/"
             "splash_mha_fwd_residuals/pallas_call\"}")
READS_DKV = ("%fusion.7 = bf16[1,4096,8,64]{3,2,1,0} fusion("
             "%splash_mha_dkv_no_residuals.1), kind=kLoop")
GMM = "%gmm.3 = bf16[32768,512]{1,0} custom-call(%a, %b, %c)"
PACK = "%efbv_pack_update.1 = (f32[16,408576]) custom-call(%g.1, %h.1)"
UNPACK = "%block_unpack_sum.14 = f32[408576,256]{1,0} custom-call(%p, %q)"


def test_trace_pattern_names_the_splash_kernels_only():
    p = re.compile(attn.TRACE_PATTERN)
    for text in (FWD, FWD_ROOT, DQ, DKV):
        assert p.search(text), text
    for text in (READS_FWD, READS_DKV, GMM, PACK, UNPACK):
        assert not p.search(text), text
    for other in (gmm, pack, unpack):
        for text in (FWD, DQ, DKV):
            assert not re.search(other.TRACE_PATTERN, text), (other, text)


def _cell(config, traffic):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        return c, json.load(f)


def test_attention_work_at_granite_shapes():
    # 12 x 64 x 24 x 8 layers x 4,096 x 4,097 / 2 on one 4,096-token row
    c, t = _cell("granite-moe-3b-a800m", "efbv-btk.1x1.b1.s4096")
    nbytes, ops = attn.work(c, t, chips=1)
    assert ops == 12 * 64 * 24 * 8 * 4096 * 4097 // 2
    assert ops == pytest.approx(1.2373e12, rel=1e-4)
    # q, o, dO, dq over 24 heads and k, v, dk, dv over 8, bf16, 8 layers
    assert nbytes == 2 * 4 * (24 + 8) * 4096 * 64 * 8


def test_attention_work_by_hand():
    cfg = {"head_dim": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
           "num_hidden_layers": 3}
    # 4 rows over 2 chips, seq 5: 15 causal pairs a head and row
    nbytes, ops = attn.work(cfg, {"seq": 5, "global_batch": 4}, chips=2)
    assert ops == 6 * 2 * 4 * 2 * 15 * 3 * 2
    assert nbytes == 2 * (4 * 2 + 4 * 1) * 5 * 4 * 3 * 2
