"""End-to-end training driver.

Example (CPU, reduced config):

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --mesh 2x2 --steps 50 --compressor block_topk:256,16 --algo efbv

On a real cluster the same entry point takes --arch <id> (full config) and
--mesh 16x16 / 2x16x16.

Every algorithmic knob is ONE declarative object: the flag namespace is
folded into a :class:`repro.core.ExperimentSpec` (:func:`spec_from_args`)
and the whole run -- EF-BV tuning, the shard_map train step,
federated sampling, bidirectional downlink, wire accounting -- is built via
``repro.core.build(spec)``.  ``--spec path.json`` loads a serialized spec
instead (the individual algorithmic flags are then ignored); the spec JSON
+ fingerprint are embedded in every checkpoint, so a mismatched resume is
refused.  See docs/api.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import time
from typing import Any, Callable, NamedTuple

import jax
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core import ExperimentSpec, SpecError, build
from repro.core.spec import mesh_worker_count
from repro.data import SyntheticLM, make_batch_shardings
from repro.launch import runtime
from repro.launch.mesh import num_workers
from repro.models import build_model
from repro.optim import adamw, cosine, wsd


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default="",
                    help="path to an ExperimentSpec JSON: the declarative "
                         "form of every algorithmic flag below (which are "
                         "then ignored); see docs/api.md and examples/specs/")
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCHS)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--mesh", default="2x2", help="e.g. 2x2, 16x16, 2x16x16")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="auto", choices=["auto", "cosine", "wsd"])
    ap.add_argument("--algo", default="efbv", choices=["efbv", "ef21", "diana", "none"])
    ap.add_argument("--compressor", default="block_topk:256,16")
    ap.add_argument("--agg", default="dense_psum",
                    choices=["dense_psum", "sparse_allgather"])
    ap.add_argument("--wire-dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"],
                    help="value precision of sparse/dense wire payloads "
                         "(quantized and bit-packed codecs ignore it)")
    ap.add_argument("--downlink", default="",
                    help="compressor spec for the master->worker model "
                         "broadcast (bidirectional compression through the "
                         "spec's wire codec, e.g. 'qsgd:16' or "
                         "'block_topk:256,16', optionally '@lam'); empty = "
                         "uncompressed dense broadcast")
    ap.add_argument("--worker-comps", default="",
                    help="heterogeneous fleet: ';'-separated compressor "
                         "specs assigned round-robin to the n workers (or "
                         "an explicit length-n list), e.g. "
                         "'topk:64;randk:64;qsgd:16'.  Overrides "
                         "--compressor; mixed fleets need --agg dense_psum")
    ap.add_argument("--participation", default="full",
                    help="per-round client sampling: full | bernoulli:p | "
                         "fixed:s (federated execution mode; absent workers "
                         "keep stale control variates)")
    ap.add_argument("--local-batch-resample", action="store_true",
                    help="stochastic local gradients: resample each worker's "
                         "minibatch from a FIXED local shard every round "
                         "instead of streaming fresh data")
    ap.add_argument("--shard-size", type=int, default=64,
                    help="sequences per worker shard for "
                         "--local-batch-resample")
    ap.add_argument("--leaf-codecs", default="",
                    help="per-leaf wire codecs: ';'-separated "
                         "'pattern=comp_spec' rules matched against "
                         "'/'-joined parameter paths (fnmatch; first match "
                         "wins; unmatched leaves use --compressor), e.g. "
                         "'*embed*=qsgd:16;*norm*=identity'.  With --spec, "
                         "a non-default value overrides the spec's "
                         "leaf_codecs field")
    ap.add_argument("--pipeline", default="off",
                    help="execution schedule: off | depth:1 (double-buffer "
                         "the compressed payload; the master applies round "
                         "t-1's message while round t's is on the wire).  "
                         "With --spec, a non-default value overrides the "
                         "spec's pipeline field")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--heterogeneity", type=float, default=0.5)
    ap.add_argument("--sanitize", action="store_true",
                    help="debug run: jax_debug_nans + Pallas interpret mode "
                         "with out-of-bounds checking "
                         "(repro.analysis.sanitize; see make sanitize-smoke)")
    return ap.parse_args(argv)


def tuning_dim(cfg) -> int:
    """THE tuning dimension of an arch: its dominant layer size.  Shared by
    spec_from_args and the CI bench's spec keying, so the fingerprint the
    driver embeds and the one the bench rows carry can never drift."""
    return max(cfg.d_model * max(cfg.d_ff, 1), 1)


def spec_from_args(args, n: int) -> ExperimentSpec:
    """Fold the driver's flag namespace into the declarative spec (the
    runtime-only knobs -- batch/seq/lr/schedule/ckpt/logging -- stay flags).

    The tuning dimension d is the arch's dominant layer size, computed from
    the config the run actually uses (smoke or full), so the spec is
    self-contained: re-running it reproduces the identical (lam, nu)."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return ExperimentSpec(
        compressor=args.worker_comps if args.worker_comps else args.compressor,
        mode=args.algo,
        agg=args.agg,
        wire_dtype=args.wire_dtype,
        downlink=args.downlink,
        participation=args.participation,
        resample=args.local_batch_resample,
        backend="shard_map",
        problem=args.arch,
        smoke=args.smoke,
        mesh=args.mesh,
        n=n,
        d=tuning_dim(cfg),
        steps=args.steps,
        seed=args.seed,
        pipeline=args.pipeline,
        leaf_codecs=args.leaf_codecs,
    )


class Job(NamedTuple):
    """Everything a training run holds once it is set up: the spec and its
    :class:`repro.core.Run`, the mesh, the model config, the placed
    TrainState, the data stream and the jitted step."""

    spec: ExperimentSpec
    run: Any
    mesh: Any
    n: int
    cfg: Any
    key: jax.Array
    state: Any
    data: SyntheticLM
    step_fn: Callable


def load_spec(args) -> ExperimentSpec:
    """The run's ExperimentSpec: the ``--spec`` file with the identity-
    changing flag overrides folded in, or the flag namespace itself."""
    if not args.spec:
        return spec_from_args(
            args, mesh_worker_count([int(x) for x in args.mesh.split("x")]))
    with open(args.spec) as f:
        spec = ExperimentSpec.from_json(f.read())
    if args.smoke and not spec.smoke:
        # --smoke changes the MODEL (reduced config), so it is part
        # of the experiment identity: fold it into the spec --
        # including the tuning dimension, which must come from the
        # config the run actually uses -- before anything derives
        # from or embeds the fingerprint
        spec = dataclasses.replace(
            spec, smoke=True,
            d=tuning_dim(get_smoke_config(spec.problem))
            if spec.problem in ARCHS else spec.d)
    if args.pipeline != "off" and spec.pipeline != args.pipeline:
        # like --smoke, the schedule is part of the experiment
        # identity: fold the override in before the fingerprint is
        # derived or embedded anywhere
        spec = dataclasses.replace(spec, pipeline=args.pipeline)
    if args.leaf_codecs and spec.leaf_codecs != args.leaf_codecs:
        # the per-leaf wire is part of the experiment identity too:
        # fold the override in before the fingerprint is derived
        spec = dataclasses.replace(spec, leaf_codecs=args.leaf_codecs)
    if spec.backend == "reference":
        raise SpecError(
            "the train driver runs the distributed trainers; a "
            "backend='reference' spec runs via "
            "repro.core.build(spec).reference()")
    if spec.problem not in ARCHS:
        # valid spec (e.g. a logreg trainer run wired up in user
        # code, like examples/distributed_logreg.py), but this
        # driver only trains the LM arch zoo
        raise SpecError(
            f"this driver trains model archs {sorted(ARCHS)}; "
            f"problem={spec.problem!r} specs supply their own "
            "loss via repro.core.build(spec).train_step(...)")
    return spec


def make_optimizer(args, spec: ExperimentSpec):
    """AdamW under the run's schedule: WSD for minicpm (its assigned
    training recipe), cosine otherwise."""
    sched_kind = args.schedule
    if sched_kind == "auto":
        sched_kind = "wsd" if spec.problem.startswith("minicpm") else "cosine"
    if sched_kind == "wsd":
        sched = wsd(args.lr, warmup_steps=max(spec.steps // 20, 1),
                    stable_steps=int(spec.steps * 0.7),
                    decay_steps=max(int(spec.steps * 0.25), 1))
    else:
        sched = cosine(args.lr, total_steps=spec.steps,
                       warmup_steps=max(spec.steps // 20, 1))
    return adamw(sched, weight_decay=0.01)


def print_wire(spec: ExperimentSpec, run, params, n: int) -> None:
    """Exact wire accounting for the codec payload (docs/wire_format.md),
    and the leaves whose fused Pallas kernel gives way to the jnp oracle;
    every compressor declares a codec, so this always prints."""
    from repro.distributed import wire

    algo, downlink, participation = run.algo, run.downlink, run.participation
    federated = run.federated
    up_fmt = wire.tree_format_for(algo.compressor, params,
                                  wire_dtype=spec.wire_dtype,
                                  rules=algo.leaf_rules) \
        if spec.agg == "sparse_allgather" else None
    if up_fmt is not None:
        up = up_fmt.bits_per_round()
        dense = up_fmt.dense_bits()
        kinds = sorted({l.kind for l in up_fmt.leaves})
        print(f"[train] wire: codec={','.join(kinds)} {up} bits/round/worker "
              f"uplink ({up / 8 / 2**20:.2f} MiB, "
              f"{up / max(dense, 1):.4f}x dense fp32)")
        gaps = wire.kernel_gaps(up_fmt, params)
        if gaps:
            print(f"[train] wire: {len(gaps)} leaves take the jnp oracle "
                  "instead of their Pallas kernel on TPU: "
                  + "; ".join(f"{p} ({why})" for p, why in gaps))
        if federated:
            exp_s = participation.fraction(n) * n
            fed = up_fmt.bits_per_round(n_workers=n, participants=exp_s)
            full = up_fmt.bits_per_round(n_workers=n)
            print(f"[train] wire: federated round (mask bitmap + E|S_t|={exp_s:g}"
                  f" of {n} payloads) ~{fed / 8 / 2**20:.2f} MiB total "
                  f"({fed / max(full, 1):.3f}x the full-participation round)")
    elif algo.fleet is not None:
        fmts = wire.fleet_formats(algo.fleet, params,
                                  wire_dtype=spec.wire_dtype)
        bits = wire.fleet_bits_per_round(fmts)
        per = sorted({f.bits_per_round() for f in fmts})
        print(f"[train] wire: mixed fleet of {len(set(algo.fleet))} member "
              f"kinds, per-worker bits in {per}, {bits} bits/round uplink "
              f"(would-be payload; dense_psum carries dense tensors)")
    if downlink is not None:
        # the downlink accounting prints for EVERY agg mode: the broadcast
        # payload is real regardless of how the uplink travels
        dfmt = downlink.format_for(params, wire_dtype=spec.wire_dtype)
        down = dfmt.downlink_bits_per_round()
        dense = dfmt.dense_bits()
        up = (up_fmt.bits_per_round() if up_fmt is not None else dense)
        total = wire.total_round_bits(
            up_fmt, dfmt, n_workers=n,
            participants=participation.fraction(n) * n if federated
            else None) if up_fmt is not None else n * up + down
        dense_total = n * dense + dense  # fp32 both directions
        print(f"[train] wire: downlink {down} bits/round broadcast "
              f"({down / max(dense, 1):.4f}x dense fp32); total "
              f"{total:g} bits/round up+down "
              f"({total / max(dense_total, 1):.4f}x dense both ways)")


def setup(args) -> Job:
    """Spec, mesh, model, optimizer, placed TrainState, data and the jitted
    step of a run: ``build(spec)`` -> ``run.init_state`` ->
    ``run.state_shardings`` -> ``run.train_step``."""
    try:
        spec = load_spec(args)
        # before the first compile or device query: the compile cache, and on
        # the CPU enough host devices for the spec's mesh
        runtime.compile_cache()
        runtime.cpu_devices(math.prod(spec.mesh_dims()))
        run = build(spec)
    except (SpecError, ValueError, OSError) as e:
        raise SystemExit(f"[train] bad experiment spec: {e}")
    try:
        mesh = run.make_mesh()
    except ValueError as e:
        raise SystemExit(f"[train] {e}")
    n = num_workers(mesh)
    cfg = (get_smoke_config(spec.problem) if spec.smoke
           else get_config(spec.problem))
    model = build_model(cfg)
    opt = make_optimizer(args, spec)

    algo = run.algo
    print(f"[train] arch={cfg.name} family={cfg.family} params~{cfg.param_count():,} "
          f"workers={n} algo={spec.mode} lam={algo.lam:.4g} nu={algo.nu:.4g} "
          f"agg={spec.agg}"
          + (f" pipeline={spec.pipeline}" if not run.pipeline.is_off else "")
          + (f" participation={spec.participation}" if run.federated else "")
          + (f" downlink={spec.downlink}" if run.downlink else "")
          + (f" fleet={spec.compressor}" if algo.fleet is not None else "")
          + (f" leaf_codecs={spec.leaf_codecs}" if spec.leaf_codecs else ""))
    print(f"[train] spec fingerprint={spec.fingerprint()}"
          + (f" (from {args.spec})" if args.spec else ""))

    key = jax.random.key(spec.seed)
    params = jax.eval_shape(model.init, key)
    print_wire(spec, run, params, n)

    # the state is built in place, each leaf straight into its sharding: no
    # device ever holds an unsharded copy (h alone is n x the parameters)
    shardings = run.state_shardings(
        mesh, model.param_specs(),
        jax.eval_shape(lambda p: run.init_state(p, opt, mesh), params))
    state = jax.jit(lambda k: run.init_state(model.init(k), opt, mesh),
                    out_shardings=shardings)(key)

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                       global_batch=args.global_batch, n_workers=n,
                       seed=spec.seed, heterogeneity=args.heterogeneity,
                       resample_from_shard=spec.resample,
                       shard_size=args.shard_size)

    def loss_fn(p, batch):
        return model.loss(p, batch)

    step_fn = run.train_step(loss_fn, opt, mesh)
    return Job(spec, run, mesh, n, cfg, key, state, data, step_fn)


def batch_at(job: Job, args, step: int) -> dict:
    """Step ``step``'s global batch, placed on the mesh."""
    cfg = job.cfg
    batch = make_batch_shardings(job.mesh, job.data.batch(step))
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.device_put(
            np.random.default_rng(step).standard_normal(
                (args.global_batch, cfg.vision_patches, cfg.d_model),
                dtype=np.float32))
    if cfg.family == "encdec":
        batch["frames"] = jax.device_put(
            np.random.default_rng(step).standard_normal(
                (args.global_batch, cfg.encoder_frames, cfg.d_model),
                dtype=np.float32))
    return batch


def main(argv=None):
    args = parse_args(argv)
    if args.sanitize:
        from repro.analysis import sanitize

        sanitize.enable()
        print("[train] sanitize mode: jax_debug_nans + Pallas interpret")
    job = setup(args)
    spec, n, state = job.spec, job.n, job.state

    t_start = time.time()
    for step in range(spec.steps):
        batch = batch_at(job, args, step)
        state, metrics = job.step_fn(state, batch,
                                     jax.random.fold_in(job.key, step))
        if step % args.log_every == 0 or step == spec.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            part_str = f"|S|={int(m['participants'])}/{n} " \
                if "participants" in m else ""
            print(f"[train] step {step:5d} loss={m['loss']:.4f} "
                  f"|g|={m['g_norm']:.3f} |upd|={m['update_norm']:.4f} "
                  f"h_res={m['h_residual']:.3f} {part_str}"
                  f"({(time.time()-t_start)/(step+1):.2f}s/step)")
        if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, {"params": state.params},
                            spec=spec)
            print(f"[train] checkpoint @ {step + 1}")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, spec.steps, {"params": state.params},
                        spec=spec)
    print(f"[train] done: final loss {float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
