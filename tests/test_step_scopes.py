"""The EF-BV step names its layers: every instruction of the compiled step
carries its layer's ``jax.named_scope`` in its ``op_name``, so a profile
splits the step's device time into forward/backward, compress, decode,
optimizer and step metrics (train/trainer.py, distributed/aggregate.py)."""

from __future__ import annotations

import re
from collections import Counter

import jax
import pytest

from repro.launch import train

LAYERS = ("efbv.fwd_bwd", "efbv.compress", "efbv.decode", "efbv.optimizer",
          "efbv.step_metrics")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%\S+\s+=\s+.*?\s([a-z][\w-]*)\(.*op_name=\"([^\"]*)\"")


def scoped_instructions(hlo_text):
    """(opcode, innermost efbv.* scope or None) of every instruction that
    carries an op_name."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scopes = re.findall(r"efbv\.[a-z_]+", m.group(2))
            out.append((m.group(1), scopes[-1] if scopes else None))
    return out


@pytest.mark.parametrize("agg", ["dense_psum", "sparse_allgather"])
@pytest.mark.parametrize("pipeline", ["off", "depth:1"])
def test_compiled_step_names_its_layers(pipeline, agg):
    args = train.parse_args([
        "--arch", "qwen2-0.5b", "--smoke", "--mesh", "1x1",
        "--global-batch", "2", "--seq", "16", "--steps", "4",
        "--compressor", "block_topk:256,16", "--agg", agg,
        "--pipeline", pipeline])
    job = train.setup(args)
    batch = train.batch_at(job, args, 0)
    hlo = job.step_fn.lower(job.state, batch,
                            jax.random.key(0)).compile().as_text()
    rows = scoped_instructions(hlo)

    dots = [s for op, s in rows if op == "dot"]
    assert dots and all(s == "efbv.fwd_bwd" for s in dots), Counter(dots)
    if agg == "sparse_allgather":
        # each leaf's block-top-k payload is decoded by one scatter-add
        scatters = Counter(s for op, s in rows if op == "scatter")
        leaves = len(jax.tree.leaves(job.state.params))
        assert scatters["efbv.decode"] == leaves, scatters
    held = Counter(s for _, s in rows)
    assert all(held[layer] >= 1 for layer in LAYERS), held
