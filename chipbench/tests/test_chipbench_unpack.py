"""The decode-sum kernel's yardsticks: its trace pattern against the pack
kernel's, each on the instruction text of both kernels as the TPU compiler
names them, and its bytes and operations counted by hand."""

from __future__ import annotations

import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.kernels import pack, unpack  # noqa: E402

# instruction texts of a compiled step for a TPU v5e, shortened: each
# kernel's own custom call, and an op that reads its output
PACK = ("%efbv_pack_update.1 = (f32[16,408576]{1,0:T(8,128)}, "
        "s32[16,408576]{1,0:T(8,128)}, f32[408576,256]{1,0:T(8,128)}) "
        "custom-call(%g.1, %h.1), custom_call_target=\"tpu_custom_call\"")
# the decode kernel reads the pack kernel's outputs, which XLA names
# after the pallas_call that made them
UNPACK = ("%block_unpack_sum.14 = f32[408576,256]{1,0:T(8,128)S(1)} "
          "custom-call(%pallas_call.1, %pallas_call.2), "
          "custom_call_target=\"tpu_custom_call\"")
UNPACK_ROOT = "ROOT %block_unpack_sum = f32[4,256]{1,0} custom-call(%a, %b)"
READS_UNPACK = ("%fusion.9 = f32[24,896,4864]{2,1,0:T(8,128)} "
                "fusion(%block_unpack_sum.14, %p.2), kind=kLoop")


@pytest.mark.parametrize("kernel, hits, misses", [
    (unpack, (UNPACK, UNPACK_ROOT), (PACK, READS_UNPACK)),
    (pack, (PACK,), (UNPACK, UNPACK_ROOT, READS_UNPACK)),
], ids=["unpack", "pack"])
def test_trace_patterns_name_their_own_kernel_only(kernel, hits, misses):
    p = re.compile(kernel.TRACE_PATTERN)
    for text in hits:
        assert p.search(text), text
    for text in misses:
        assert not p.search(text), text


def test_unpack_kernel_work_by_hand():
    # a leaf of 600 in blocks of 256 (3 blocks) and one of 256 (1 block),
    # 2 kept a block, from 4 workers: the dense sum written once, the
    # payloads read once
    nbytes, ops = unpack.work([600, 256], block=256, k=2, n=4)
    assert nbytes == (4 * 600 + 8 * 4 * 3 * 2) + (4 * 256 + 8 * 4 * 1 * 2)
    assert ops == 4 * (600 + 256)
