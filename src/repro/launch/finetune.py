"""Spec-driven fine-tuning entry point (the staged harness of
repro/train/loop.py behind a CLI).

    PYTHONPATH=src python -m repro.launch.finetune \
        --spec examples/specs/finetune_moe.json --global-batch 8 --seq 32

Unlike launch/train.py (which also folds a flag namespace into a spec),
this driver is spec-file-ONLY: the experiment identity comes entirely from
the committed :class:`repro.core.ExperimentSpec` JSON; the flags below are
runtime knobs (:class:`repro.train.loop.FinetuneSettings`) that never enter
the fingerprint.  ``--processes`` builds the mesh with the multi-host
process-major layout (simulated on CPU host devices).  See
docs/finetuning.md.
"""

from __future__ import annotations

import math


def parse_args(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True,
                    help="path to the ExperimentSpec JSON driving the run "
                         "(committed examples live in examples/specs/)")
    ap.add_argument("--steps", type=int, default=0,
                    help="train this many steps instead of spec.steps "
                         "(0 = the spec's own budget; a truncated run keeps "
                         "the spec identity -- it is the same experiment, "
                         "stopped early)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--schedule", default="auto",
                    choices=["auto", "cosine", "wsd"])
    ap.add_argument("--eval-every", type=int, default=0,
                    help="held-out eval cadence (0 = final eval only)")
    ap.add_argument("--eval-batches", type=int, default=2)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--heterogeneity", type=float, default=0.5)
    ap.add_argument("--shard-size", type=int, default=64)
    ap.add_argument("--processes", type=int, default=1,
                    help="multi-host-shaped mesh: validate the process-major "
                         "device layout for this many processes "
                         "(launch/mesh.py::make_multihost_mesh; simulated "
                         "with fake host devices on CPU)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--sanitize", action="store_true",
                    help="debug run: jax_debug_nans + Pallas interpret mode "
                         "with out-of-bounds checking "
                         "(repro.analysis.sanitize; see make sanitize-smoke)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.sanitize:
        from repro.analysis import sanitize

        sanitize.enable()
        print("[finetune] sanitize mode: jax_debug_nans + Pallas interpret")

    from repro.core import ExperimentSpec, SpecError
    from repro.launch import runtime
    from repro.train.loop import FinetuneLoop, FinetuneSettings

    settings = FinetuneSettings(
        global_batch=args.global_batch, seq_len=args.seq, lr=args.lr,
        schedule=args.schedule, eval_every=args.eval_every,
        eval_batches=args.eval_batches, log_every=args.log_every,
        heterogeneity=args.heterogeneity, shard_size=args.shard_size,
        num_processes=args.processes, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every)
    try:
        with open(args.spec) as f:
            spec = ExperimentSpec.from_json(f.read())
        # before the first compile or device query: the compile cache, and on
        # the CPU enough host devices for the spec's mesh
        runtime.compile_cache()
        runtime.cpu_devices(math.prod(spec.mesh_dims()))
        loop = FinetuneLoop(spec, settings)
    except (SpecError, ValueError, OSError) as e:
        raise SystemExit(f"[finetune] bad experiment spec: {e}")

    loop.setup()
    loop.build_data()
    loop.train(steps=args.steps or None)
    eval_loss = loop.evaluate()
    print(f"[finetune] done: final loss {loop._final['loss']:.4f} "
          f"eval loss {eval_loss:.4f} "
          f"({loop._steps_per_sec:.3f} steps/s)")
    return eval_loss


if __name__ == "__main__":
    main()
