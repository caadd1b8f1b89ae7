"""The pinned CI bench: writes BENCH_perf.json and BENCH_bits.json at the
repo root (the bench trajectory that CI uploads as an artifact and commits
on main; `make bench` produces the identical files locally).

    PYTHONPATH=src:. python -m benchmarks.ci_bench [--out-dir .]

Two files, two kinds of signal:

* BENCH_perf.json -- measured on this host (noisy across machines, a
  trajectory within one runner class): steps/sec + compile time of the
  pinned smoke train-step (SMOKE below), us/call of the
  fused-vs-unfused wire pack, and HLO byte counts (compiled train step +
  AOT TPU exports of the three fused kernels) as a code-size trajectory.

* BENCH_bits.json -- exact and machine-independent: measured payload bytes
  == bits/8 for every registered wire codec, and the bidirectional
  up+down accounting (uplink x n + ONE broadcast) for pinned combos,
  including the acceptance row named `qsgd16_both_ways` whose ratio vs
  dense fp32 both ways must stay <= 0.35 (also pinned by
  tests/test_bidirectional.py).  The `serve_delta` table accounts the
  compressed model-push envelope of the serving protocol, gated at
  <= 0.35x a full checkpoint for the committed qsgd:16 downlink; the
  BENCH_perf.json `serve_fleet` row carries the measured fleet tok/s and
  hot-swap latency for the same spec.  The `zoo_scaling` table (both files;
  benchmarks/zoo_scaling.py) carries the model-scale rows: every committed
  fine-tune spec (examples/specs/finetune_moe.json + zoo_qwen2.json +
  zoo_mamba2.json, >=3 model families incl. MoE and mamba2) measured under
  its compressed wire -- exact up+down bits per round in BENCH_bits.json (with the MoE
  expert-sparsity gate: expert-leaf uplink <= 0.5x the dense block-top-k
  budget) and steps/sec through the staged fine-tune harness in
  BENCH_perf.json.

Since schema 2, every row is KEYED by the stable fingerprint of the
canonical repro.core.ExperimentSpec it measures (the human-readable
compressor/downlink specs stay inside the row): within each table, a row
with the same key across commits measures the same experiment by
construction.  The two tables are two MEASUREMENTS -- per-worker codec
payload vs whole bidirectional round -- so the same experiment (e.g. an
uplink codec with the dense broadcast) may legitimately appear in both
under the same key; duplicates WITHIN a table are rejected.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "XLA_FLAGS" not in os.environ:
    # the smoke train-step runs on a 2x2 mesh of fake host devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import argparse      # noqa: E402
import json          # noqa: E402
import platform      # noqa: E402
import time          # noqa: E402


D_BITS = 1 << 16  # codec accounting vector size (matches compressor_bench)
N_WORKERS = 8     # uplink fan-in for the bidirectional combos

# (name, uplink spec, downlink spec or None=dense broadcast) -- pinned; the
# acceptance row is qsgd16_both_ways
BIDIR_COMBOS = [
    ("block_topk_up_dense_down", "block_topk:1024,16", None),
    ("block_topk_up_qsgd16_down", "block_topk:1024,16", "qsgd:16"),
    ("qsgd16_both_ways", "qsgd:16", "qsgd:16"),
    ("sign_up_natural_down", "sign", "natural"),
]

CODECS = ["identity", "topk:655", "randk:655", "comp:655,6553",
          "block_topk:1024,16", "sign", "natural", "qsgd:16"]


def _bench_spec(up_spec: str, down_spec=None):
    """The canonical ExperimentSpec of one bench row.  Its stable
    fingerprint is the row KEY in BENCH_bits.json: a row with the same
    fingerprint across commits measures the same experiment, so the bench
    trajectory survives renames and row reordering."""
    from repro.core import ExperimentSpec

    agg = ("dense_psum"
           if len({s.strip() for s in up_spec.split(";")}) > 1
           else "sparse_allgather")
    return ExperimentSpec(compressor=up_spec, downlink=down_spec or "",
                          agg=agg, backend="reference", problem="quadratic",
                          n=N_WORKERS, d=D_BITS, steps=1, seed=0)


def bits_payload():
    import jax.numpy as jnp

    from repro.core import Downlink, make_compressor
    from repro.distributed import wire

    zeros = jnp.zeros((D_BITS,))
    dense = 32 * D_BITS
    codec_rows = {}
    for spec_str in CODECS:
        spec = _bench_spec(spec_str)
        fmt = wire.format_for(make_compressor(spec_str), zeros)
        bits = fmt.bits_per_round()
        codec_rows[spec.fingerprint()] = {
            "compressor": spec_str,
            "payload_bits": bits,
            "payload_bytes": bits // 8,
            "vs_dense_fp32": round(bits / dense, 6),
        }

    combo_rows = {}
    for name, up_spec, down_spec in BIDIR_COMBOS:
        spec = _bench_spec(up_spec, down_spec)
        assert spec.fingerprint() not in combo_rows, (
            f"combo {name!r} duplicates the spec of "
            f"{combo_rows[spec.fingerprint()]['name']!r}: the trajectory "
            "would silently drop one row")
        up = wire.format_for(make_compressor(up_spec), zeros)
        down = (None if down_spec is None else
                Downlink.parse(down_spec).format_for(zeros))
        total = wire.total_round_bits(up, down, n_workers=N_WORKERS)
        dense_both = N_WORKERS * dense + dense
        combo_rows[spec.fingerprint()] = {
            "name": name,
            "uplink_spec": up_spec,
            "downlink_spec": down_spec or "dense_fp32",
            "up_bits": up.bits_per_round(n_workers=N_WORKERS),
            "down_bits": (dense if down is None
                          else down.downlink_bits_per_round()),
            "total_bits": total,
            "vs_dense_both_ways": round(total / dense_both, 6),
        }
    qs = next(r["vs_dense_both_ways"] for r in combo_rows.values()
              if r["name"] == "qsgd16_both_ways")
    assert qs <= 0.35, f"qsgd:16 both ways regressed past 0.35x dense: {qs}"

    # the pytree-native wire row: the committed mixed per-leaf codec spec
    # (examples/specs/tree_mixed_codecs.json) measured on the real qwen2
    # smoke parameter tree, keyed -- like every other row -- by the spec's
    # stable fingerprint.  Exact and machine-independent, and the composed
    # == sum-of-per-leaf invariant the harness pins is asserted here too so
    # the trajectory can never silently depend on it breaking.
    import jax

    from repro.configs import get_smoke_config
    from repro.core import ExperimentSpec
    from repro.models import build_model

    spec_path = os.path.join(os.path.dirname(__file__), os.pardir,
                             "examples", "specs", "tree_mixed_codecs.json")
    with open(spec_path) as f:
        tree_spec = ExperimentSpec.from_dict(json.load(f))
    params = build_model(get_smoke_config(tree_spec.problem)).init(
        jax.random.key(0))
    fmt = wire.tree_format_for(
        make_compressor(tree_spec.compressor), params,
        wire_dtype=tree_spec.wire_dtype,
        rules=wire.parse_leaf_rules(tree_spec.leaf_codecs))
    by_leaf = fmt.bits_by_leaf()
    tree_bits = fmt.bits_per_round()
    assert tree_bits == sum(by_leaf), (
        f"TreeWire composed bits {tree_bits} != sum of per-leaf bits "
        f"{sum(by_leaf)}")
    dense_tree = 32 * sum(int(l.size) for l in jax.tree_util.tree_leaves(
        params))
    tree_rows = {tree_spec.fingerprint(): {
        "name": "tree_mixed_codecs",
        "uplink_spec": tree_spec.compressor,
        "leaf_codecs": tree_spec.leaf_codecs,
        "problem": tree_spec.problem,
        "n_leaves": len(by_leaf),
        "leaf_kinds": sorted({c.kind for c in fmt.leaves}),
        "payload_bits": tree_bits,
        "payload_bytes": tree_bits // 8,
        "sum_of_leaf_bits": sum(by_leaf),
        "vs_dense_fp32": round(tree_bits / dense_tree, 6),
    }}

    # the serve-delta table: exact envelope accounting of the compressed
    # model-push protocol (launch/serve.py) on the committed serve spec's
    # real smoke parameter tree.  One delta push ships push_bits(fmt) --
    # the versioned envelope header + the downlink payload -- vs
    # checkpoint_push_bits(fmt) for shipping the model densely; the
    # acceptance gate pins the committed qsgd:16 downlink at <= 0.35x the
    # full-checkpoint baseline (also pinned by tests/test_serve_delta.py).
    from repro.core import Downlink

    serve_path = os.path.join(os.path.dirname(__file__), os.pardir,
                              "examples", "specs", "serve_delta.json")
    with open(serve_path) as f:
        serve_spec = ExperimentSpec.from_dict(json.load(f))
    serve_params = build_model(get_smoke_config(serve_spec.problem)).init(
        jax.random.key(0))
    serve_dl = Downlink.parse(serve_spec.downlink)
    serve_fmt = serve_dl.serve_format(serve_params,
                                      wire_dtype=serve_spec.wire_dtype)
    delta_bits = wire.push_bits(serve_fmt)
    ckpt_bits = wire.checkpoint_push_bits(serve_fmt)
    ratio = delta_bits / ckpt_bits
    serve_rows = {serve_spec.fingerprint(): {
        "name": "serve_delta_push",
        "downlink_spec": serve_spec.downlink,
        "problem": serve_spec.problem,
        "push_kind": serve_dl.push_kind(serve_spec.wire_dtype),
        "delta_bits_per_push": delta_bits,
        "checkpoint_bits_per_push": ckpt_bits,
        "vs_full_checkpoint": round(ratio, 6),
    }}
    assert serve_spec.downlink == "qsgd:16" and ratio <= 0.35, (
        f"serve delta push regressed past 0.35x a full checkpoint: "
        f"{ratio} ({serve_spec.downlink})")

    # the model-zoo scaling table (benchmarks/zoo_scaling.py): exact
    # up+down bits of every committed fine-tune spec's round on its real
    # smoke parameter tree, keyed by the committed fingerprints.  The MoE
    # gate pins the expert-sparsity contract: with inactive-expert grads
    # zeroed worker-side and the expert leaves on rescaled topk rules, the
    # expert-leaf uplink must cost <= 0.5x the dense block-top-k budget on
    # those same leaves (exactly a/E = 2/4 for the committed granite spec).
    from benchmarks import zoo_scaling

    zoo_bits = zoo_scaling.zoo_bits_rows()
    for row in zoo_bits.values():
        if row["family"] == "moe":
            assert row["expert_leaf_bits"] <= \
                0.5 * row["dense_expert_leaf_bits"], (
                    f"expert-sparse MoE uplink regressed past 0.5x the "
                    f"dense block-top-k budget: {row['expert_leaf_bits']} "
                    f"vs {row['dense_expert_leaf_bits']} bits "
                    f"({row['spec_file']})")
    assert any(r["family"] == "moe" for r in zoo_bits.values()) and \
        any(r["family"] == "ssm" for r in zoo_bits.values()) and \
        len(zoo_bits) >= 3, "the zoo table needs >=3 families incl. moe+ssm"

    return {
        "schema": 2,  # schema 2: rows keyed by ExperimentSpec fingerprint
        "d": D_BITS,
        "n_workers": N_WORKERS,
        "codec_bits_per_round": codec_rows,
        "bidirectional_rounds": combo_rows,
        "tree_wire": tree_rows,
        "serve_delta": serve_rows,
        "zoo_scaling": zoo_bits,
    }


# the pinned CI/`make bench` configuration -- change it and every later
# BENCH_perf.json entry starts a new trajectory, so don't
SMOKE = dict(arch="qwen2-0.5b", mesh=(2, 2), steps=4, global_batch=8, seq=32,
             compressor="block_topk:256,16", agg="sparse_allgather",
             downlink="qsgd:16")


def smoke_rows(pipeline: str = "off", leaf_codecs: str = ""):
    """Measure the pinned smoke train-step (see SMOKE): steps/sec excluding
    compile and warmup, compile seconds, and compiled-HLO bytes.  Needs >= 4
    XLA host devices (XLA_FLAGS at the top of this module).

    ``pipeline`` ('off' | 'depth:1') selects the execution schedule; the
    depth:1 row lands in BENCH_perf.json next to the sequential baseline
    under its own spec fingerprint.  ``leaf_codecs`` (per-leaf codec rules,
    docs/wire_format.md) switches the wire to the pytree-native TreeWire --
    the smoke_train_step_tree row; '' keeps the flat wire and the existing
    rows byte-compatible."""
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core import Downlink, EFBV, make_compressor
    from repro.core.efbv import Pipeline
    from repro.data import SyntheticLM, make_batch_shardings
    from repro.distributed import wire
    from repro.launch.mesh import make_mesh, num_workers
    from repro.models import build_model
    from repro.optim import adamw, cosine
    from repro.train import (init_train_state, make_train_step,
                             train_state_shardings)

    cfg = get_smoke_config(SMOKE["arch"])
    mesh = make_mesh(SMOKE["mesh"])
    n = num_workers(mesh)
    model = build_model(cfg)
    comp = make_compressor(SMOKE["compressor"])
    pipe = Pipeline.parse(pipeline)
    rules = wire.parse_leaf_rules(leaf_codecs) if leaf_codecs else None
    algo = EFBV.make(comp, d=max(cfg.d_model * max(cfg.d_ff, 1), 1), n=n,
                     pipeline=pipe.depth or None, leaf_rules=rules)
    downlink = Downlink.parse(SMOKE["downlink"])
    opt = adamw(cosine(3e-4, total_steps=SMOKE["steps"], warmup_steps=1))

    params = model.init(jax.random.key(0))
    state = init_train_state(params, opt, mesh, bidirectional=True,
                             algo=algo, agg_mode=SMOKE["agg"], pipeline=pipe)
    sh = train_state_shardings(mesh, model.param_specs(), state)
    state = jax.tree.map(lambda x, s: jax.device_put(x, s), state, sh)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=SMOKE["seq"],
                       global_batch=SMOKE["global_batch"], n_workers=n,
                       seed=0)
    step_fn = make_train_step(model.loss, opt, algo, mesh,
                              agg_mode=SMOKE["agg"], downlink=downlink,
                              pipeline=pipe)

    key = jax.random.key(0)
    batch = make_batch_shardings(mesh, data.batch(0))
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, batch, key).compile()
    compile_s = time.perf_counter() - t0
    hlo_bytes = len(compiled.as_text().encode())

    # drive the AOT-compiled executable directly (calling step_fn again
    # would recompile through jit's separate dispatch cache): one warmup
    # dispatch, then the timed steps.  GSPMD may emit a few output leaves
    # with different shardings than the input layout the step was compiled
    # for (e.g. a small norm param flipping to 'model'), and AOT calls are
    # strict about input shardings -- reshard those leaves back outside the
    # timed region.
    resync = lambda st: jax.tree.map(
        lambda x, s: x if x.sharding == s else jax.device_put(x, s), st, sh)
    state, warm_metrics = compiled(state, batch, key)
    # warmup synchronizes on EVERYTHING it produced, so no async dispatch
    # (or lazy host transfer) bleeds into the first timed step
    jax.block_until_ready((state, warm_metrics))
    times = []
    for i in range(SMOKE["steps"]):
        state = resync(state)
        batch = make_batch_shardings(mesh, data.batch(i + 1))
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch, jax.random.fold_in(key, i))
        # the step isn't done until every output leaf is: blocking only on
        # params used to stop the clock while h / h_avg / w / the in-flight
        # payload / metrics could still be computing
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t0)
    sec_per_step = float(np.median(times))
    return {
        "config": {**{k: (list(v) if isinstance(v, tuple) else v)
                      for k, v in SMOKE.items()}, "pipeline": pipeline,
                   # only a real rule set enters the row (the flat rows stay
                   # byte-compatible with the pre-field trajectory)
                   **({"leaf_codecs": leaf_codecs} if leaf_codecs else {})},
        "steps_per_sec": round(1.0 / sec_per_step, 4),
        "sec_per_step_median": round(sec_per_step, 4),
        "compile_s": round(compile_s, 2),
        "train_step_hlo_bytes": hlo_bytes,
        "final_loss": round(float(metrics["loss"]), 4),
    }


def perf_payload(fast: bool = True):
    import jax

    from benchmarks import compressor_bench

    # key each smoke row by the ACTUAL train-step experiment it measures
    # (same identity scheme as the BENCH_bits.json rows); worker count and
    # tuning dimension come from the canonical shared helpers, so this
    # fingerprint can never drift from the one the train driver embeds
    from repro.configs import get_smoke_config
    from repro.core import ExperimentSpec
    from repro.core.spec import mesh_worker_count
    from repro.launch.train import tuning_dim

    s = SMOKE

    def smoke_fingerprint(pipeline: str = "off",
                          leaf_codecs: str = "") -> str:
        return ExperimentSpec(
            compressor=s["compressor"], agg=s["agg"], downlink=s["downlink"],
            backend="shard_map", problem=s["arch"], smoke=True,
            mesh="x".join(str(x) for x in s["mesh"]),
            n=mesh_worker_count(s["mesh"]),
            d=tuning_dim(get_smoke_config(s["arch"])), steps=s["steps"],
            seed=0, pipeline=pipeline, leaf_codecs=leaf_codecs).fingerprint()

    smoke = smoke_rows()
    # the pipelined smoke row + the perf gate: the depth-1 schedule only
    # removes a data dependence, so its steps/sec must never lose to the
    # sequential row measured in the SAME run.  Both sides re-measure on a
    # losing attempt -- a transiently loaded host slows whichever row it
    # happens to overlap, and one fresh pair beats comparing a noisy row
    # against a stale one.
    smoke_pipe = smoke_rows("depth:1")
    for _ in range(2):
        if smoke_pipe["steps_per_sec"] >= smoke["steps_per_sec"]:
            break
        smoke = smoke_rows()
        smoke_pipe = smoke_rows("depth:1")
    assert smoke_pipe["steps_per_sec"] >= smoke["steps_per_sec"], (
        f"pipelined smoke regressed below the sequential baseline: "
        f"{smoke_pipe['steps_per_sec']} < {smoke['steps_per_sec']} steps/s")
    smoke["spec_fingerprint"] = smoke_fingerprint()
    smoke_pipe["spec_fingerprint"] = smoke_fingerprint("depth:1")

    # the pytree-native wire smoke row + its perf gate: the per-leaf rules
    # swap the big embedding leaf's block top-k for a flat quantizer and
    # stop compressing the tiny norms, so the tree-wire step must never
    # lose to the flat wire measured in the SAME run.  Same re-measure
    # discipline as the pipeline gate above; the flat reference re-measured
    # on a retry travels INSIDE the tree row, leaving the recorded
    # sequential/pipelined pair exactly as gated.
    tree_leaf_codecs = "*embed*=qsgd:16;*norm*=identity"
    flat_ref = smoke
    smoke_tree = smoke_rows(leaf_codecs=tree_leaf_codecs)
    for _ in range(2):
        if smoke_tree["steps_per_sec"] >= flat_ref["steps_per_sec"]:
            break
        flat_ref = smoke_rows()
        smoke_tree = smoke_rows(leaf_codecs=tree_leaf_codecs)
    assert smoke_tree["steps_per_sec"] >= flat_ref["steps_per_sec"], (
        f"per-leaf tree wire regressed below the flat-wire baseline: "
        f"{smoke_tree['steps_per_sec']} < {flat_ref['steps_per_sec']} "
        f"steps/s")
    smoke_tree["spec_fingerprint"] = smoke_fingerprint(
        leaf_codecs=tree_leaf_codecs)
    smoke_tree["flat_steps_per_sec_same_run"] = flat_ref["steps_per_sec"]

    # the replica-fleet serving row: tok/s + hot-swap latency of the
    # committed serve spec (benchmarks/serve_fleet.py), keyed by its
    # fingerprint like every other row.  The bitwise fleet invariant is
    # asserted inside run_fleet, so this row only exists if every replica
    # reconstructed the trainer's w exactly.
    from benchmarks import serve_fleet

    _, sm = serve_fleet.fleet_metrics()
    serve_row = {
        "spec_fingerprint": sm["fingerprint"],
        "replicas": sm["replicas"],
        "pushes": sm["pushes"],
        "requests": sm["requests"],
        "tokens": sm["tokens"],
        "tok_per_s": round(sm["tok_per_s"], 3),
        "swap_ms_max": round(sm["swap_ms_max"], 4),
        "stage_ms_max": round(sm["stage_ms_max"], 4),
    }

    # the model-zoo scaling rows: steps/sec of every committed fine-tune
    # spec through the staged harness under its compressed wire
    # (benchmarks/zoo_scaling.py), keyed by the committed fingerprints --
    # the model-scale leg of the bench trajectory
    from benchmarks import zoo_scaling

    zoo_rows = zoo_scaling.zoo_perf_rows()

    pack_rows = {}
    for row in compressor_bench.packed_vs_dense(fast=fast):
        key = row["name"].split("/", 1)[1]
        pack_rows[key] = {"us_per_call": row["us_per_call"],
                          "derived": row["derived"]}

    import functools

    import jax.numpy as jnp
    from jax import export as jexport

    from repro.kernels.pack import (pack_update_pallas,
                                    qsgd_pack_update_pallas,
                                    randk_update_pallas)

    sds = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    idx = jax.ShapeDtypeStruct((32,), jnp.int32)
    norm = jax.ShapeDtypeStruct((1, 1), jnp.float32)
    exports = {
        "block_topk_pack": jexport.export(
            jax.jit(functools.partial(pack_update_pallas, lam=0.9, kb=16,
                                      interpret=False)),
            platforms=["tpu"])(sds, sds),
        "randk_update": jexport.export(
            jax.jit(functools.partial(randk_update_pallas, scale=75.0,
                                      lam=0.9, interpret=False)),
            platforms=["tpu"])(sds, sds, idx),
        "qsgd_pack": jexport.export(
            jax.jit(functools.partial(qsgd_pack_update_pallas, s=16,
                                      lam=0.9, interpret=False)),
            platforms=["tpu"])(sds, sds, sds, norm),
    }
    kernel_hlo = {k: len(e.mlir_module().encode())
                  for k, e in exports.items()}

    return {
        "schema": 1,
        "host": {"python": platform.python_version(), "jax": jax.__version__,
                 "machine": platform.machine()},
        "smoke_train_step": smoke,
        "smoke_train_step_pipelined": smoke_pipe,
        "smoke_train_step_tree": smoke_tree,
        "serve_fleet": serve_row,
        "zoo_scaling": zoo_rows,
        "wire_pack_us": pack_rows,
        "kernel_hlo_bytes": kernel_hlo,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--skip-perf", action="store_true",
                    help="only write the (deterministic) BENCH_bits.json")
    args = ap.parse_args(argv)

    bits = bits_payload()
    path = os.path.join(args.out_dir, "BENCH_bits.json")
    with open(path, "w") as f:
        json.dump(bits, f, indent=1, sort_keys=True)
        f.write("\n")
    qs = next(r["vs_dense_both_ways"]
              for r in bits["bidirectional_rounds"].values()
              if r["name"] == "qsgd16_both_ways")
    print(f"[bench] wrote {path} (qsgd16_both_ways = {qs}x dense up+down)")

    if not args.skip_perf:
        perf = perf_payload()
        path = os.path.join(args.out_dir, "BENCH_perf.json")
        with open(path, "w") as f:
            json.dump(perf, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[bench] wrote {path} "
              f"(smoke {perf['smoke_train_step']['steps_per_sec']} steps/s, "
              f"pipelined "
              f"{perf['smoke_train_step_pipelined']['steps_per_sec']} "
              f"steps/s, tree "
              f"{perf['smoke_train_step_tree']['steps_per_sec']} steps/s)")


if __name__ == "__main__":
    main()
