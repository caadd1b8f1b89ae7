"""End-to-end example: fine-tune a ~100M-parameter LM with EF-BV compressed
gradient aggregation on a data x model mesh, driven by ONE declarative
:class:`repro.core.ExperimentSpec`.

    # few-hundred-step run (~100M params; several hours of CPU -- this is the
    # deployment-shaped entry point; on TPU the same command runs per pod):
    PYTHONPATH=src python examples/train_lm.py

    # quick demo (~8M params, minutes on CPU):
    PYTHONPATH=src python examples/train_lm.py --tiny

Everything routes through the staged fine-tune harness
(repro/train/loop.py::FinetuneLoop, docs/finetuning.md): the spec declares
the EF-BV layer (block-top-k compressor, sparse all-gather wire) and the
harness supplies the four stages -- setup, heterogeneous synthetic LM data,
the compressed train loop, and held-out eval -- plus npz checkpointing.
The custom (non-zoo) model config rides in via ``FinetuneLoop(config=...)``;
the committed zoo specs in examples/specs/ need no config at all.
"""

import argparse
import dataclasses
import math
import sys

sys.path.insert(0, "src")

from repro.models.config import ModelConfig  # noqa: E402


def lm100m() -> ModelConfig:
    """~100M-param llama-style config (qwen2-family reduced)."""
    return ModelConfig(
        name="lm100m", family="dense",
        n_layers=12, d_model=512, n_heads=8, n_kv_heads=2,
        d_ff=2048, vocab=32768, head_dim=64,
        qkv_bias=True, tie_embeddings=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="~8M params demo")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--mesh", default="4x1")
    args = ap.parse_args()

    from repro.core import ExperimentSpec
    from repro.core.spec import mesh_worker_count
    from repro.launch import runtime
    from repro.train.loop import FinetuneLoop, FinetuneSettings

    dims = [int(x) for x in args.mesh.split("x")]
    # before the first compile or device query: the compile cache, and on
    # the CPU enough host devices for the mesh
    runtime.compile_cache()
    runtime.cpu_devices(math.prod(dims))

    cfg = lm100m()
    steps = args.steps or (300 if not args.tiny else 60)
    if args.tiny:
        cfg = dataclasses.replace(cfg, n_layers=4, d_model=256, d_ff=1024,
                                  vocab=4096, name="lm8m")

    spec = ExperimentSpec(
        compressor="block_topk:1024,64", mode="efbv",
        agg="sparse_allgather", backend="shard_map",
        problem="qwen2-0.5b",   # nearest zoo family; the real config rides
        smoke=True,             # in via FinetuneLoop(config=...) below
        mesh=args.mesh, n=mesh_worker_count(dims),
        d=cfg.d_model * cfg.d_ff, steps=steps, seed=0)
    print(f"[train_lm] spec fingerprint={spec.fingerprint()} "
          f"arch={cfg.name} mesh={args.mesh}")

    loop = FinetuneLoop(
        spec,
        FinetuneSettings(global_batch=16, seq_len=256, lr=1e-3,
                         log_every=10, ckpt_dir="runs/lm100m_ckpt",
                         ckpt_every=100),
        config=cfg)
    summary = loop.run()
    print(f"[train_lm] final loss {summary['final_loss']:.4f} "
          f"eval loss {summary['eval_loss']:.4f} "
          f"({summary['steps_per_sec']:.3f} steps/s)")


if __name__ == "__main__":
    main()
