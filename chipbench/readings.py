"""The readings a cell's limits are set from (not part of a benchmark run).

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --controls 3 [--small]

For every seed: the program's numbers against the reference (the program's
training step through the three set-up steps, as a run reads them).  For
the first ``--controls`` seeds also the control's (the reference in the
precision below the configuration's: int8 products, bfloat16 state) and
the half-batch fault's (the reference fed half of the rows).
One JSON line per seed on standard output.

``--small`` reads the same numbers on any host, the CPU included, at the
program's small preset of the cell's model (``--smoke``) on 4 rows of 64
tokens (two scan chunks for an SSM): an orientation only, where no chip
reading at the cell's own size exists.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run as R  # noqa: E402


def small_config(program_cfg) -> dict:
    """The reference's keys for one of the program's small presets."""
    c = program_cfg
    if c.family == "dense":
        return {"hidden_size": c.d_model, "num_attention_heads": c.n_heads,
                "num_key_value_heads": c.n_kv_heads, "head_dim": c.hd(),
                "intermediate_size": c.d_ff, "vocab_size": c.vocab,
                "num_hidden_layers": c.n_layers, "rope_theta": c.rope_theta,
                "rms_norm_eps": c.norm_eps}
    return {"hidden_size": c.d_model, "expand": c.ssm_expand,
            "state_size": c.ssm_state, "head_dim": c.ssm_head_dim,
            "conv_kernel": c.ssm_conv, "vocab_size": c.vocab,
            "num_hidden_layers": c.n_layers, "rms_norm_eps": c.norm_eps,
            "chunk_size": c.ssm_chunk, "reference_chunk_size": 2 * c.ssm_chunk}


def make_small(cell: R.Cell) -> None:
    """Turn ``cell`` into its small preset, off the chip (``--small``)."""
    import jax

    R.import_program()
    from repro.configs import get_smoke_config

    c = get_smoke_config(cell.config["arch"])
    cell.config = dict(small_config(c), arch=cell.config["arch"],
                       reference=cell.config["reference"],
                       program={"name": c.name, "n_layers": c.n_layers,
                                "d_model": c.d_model, "vocab": c.vocab})
    cell.traffic = dict(cell.traffic, global_batch=4, seq=2 * c.ssm_chunk
                        if c.family == "ssm" else 64)
    from chipbench import peaks

    v5e = peaks.peaks("TPU v5 lite")
    peaks.peaks = lambda kind: v5e
    R.require_chips = lambda chips: jax.devices()[:chips]
    train_args = R.train_args
    R.train_args = lambda cl, seed: train_args(cl, seed) + ["--smoke"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--dump", default=None,
                    help="a directory to write each seed's leaf-wise norms to")
    args = ap.parse_args(argv)
    cell = R.Cell(args.workload)
    if args.small:
        make_small(cell)
    try:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            pre = R.prepare(cell, seed)
            readings = pre.readings
            del pre
            gc.collect()
            import jax.numpy as jnp

            from chipbench import numerics, reference as ref

            t1 = time.perf_counter()
            exact = ref.trajectory(cell.model, cell.config, cell.traffic, seed)
            t2 = time.perf_counter()
            line = {"seed": seed, "program": ref.compare(readings, exact),
                    "worst": ref.worst_leaves(readings, exact, ref.leaf_names(
                        cell.model, cell.config)),
                    "seconds": {"program": t1 - t0, "reference": t2 - t1}}
            if i < args.controls:
                ctrl = ref.trajectory(cell.model, cell.config, cell.traffic,
                                      seed, num=numerics.INT8,
                                      state_dtype=jnp.bfloat16)
                line["control"] = ref.compare(ctrl.readings, exact)
                half = ref.trajectory(cell.model, cell.config, cell.traffic,
                                      seed, keep_rows=0.5)
                line["half_batch"] = ref.compare(half.readings, exact)
            line["losses"] = {"program": readings.losses,
                              "reference": exact.readings.losses}
            if args.dump:
                os.makedirs(args.dump, exist_ok=True)
                with open(os.path.join(args.dump, f"{cell.name}.{seed}.json"),
                          "w") as f:
                    json.dump({"leaves": ref.leaf_names(cell.model, cell.config),
                               "program": readings._asdict(),
                               "reference": exact.readings._asdict(),
                               "grad_norms": exact.grad_norms}, f)
            print(json.dumps(line), flush=True)
    except R.BenchError as e:
        R.log(f"FAIL: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
