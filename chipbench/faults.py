"""The readings of a cell's control and of its half-row fault (not part of
a benchmark run).

    python3 chipbench/faults.py --workload <cell> --seeds 1,2,3

For every seed, against the exact reference's three steps
(``chipbench/reference.py``): the control (the reference in the precision
below the configuration's: int8 products, bfloat16 state, as
``chipbench/readings.py`` runs it) and the half-row fault (the reference
given the labels of only the first half of every row: the loss and the
gradient of half the tokens).  The half-batch fault of ``readings.py``
keeps at least one row, so in a cell that trains one row a step it is the
reference itself; this fault is its counterpart there.  One JSON line per
seed on standard output.  Runs the references alone, with no program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run as R  # noqa: E402


class HalfRow:
    """A reference model whose loss sees the labels of the first half of
    each row only."""

    def __init__(self, model):
        self.model = model
        self.init = model.init

    def loss_sum(self, cfg, num, params, tokens, labels):
        half = labels.shape[-1] // 2
        return self.model.loss_sum(cfg, num, params, tokens,
                                   labels.at[..., half:].set(-1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    try:
        cell = R.Cell(args.workload)
        R.use_compile_cache()
        R.require_chips(cell.chips)
    except R.BenchError as e:
        R.log(f"FAIL: {e}")
        return 1
    import jax.numpy as jnp

    from chipbench import numerics, reference as ref

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        exact = ref.trajectory(cell.model, cell.config, cell.traffic, seed)
        ctrl = ref.trajectory(cell.model, cell.config, cell.traffic, seed,
                              num=numerics.INT8, state_dtype=jnp.bfloat16)
        half = ref.trajectory(HalfRow(cell.model), cell.config, cell.traffic,
                              seed)
        print(json.dumps({"seed": seed,
                          "control": ref.compare(ctrl.readings, exact),
                          "half_row": ref.compare(half.readings, exact),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
