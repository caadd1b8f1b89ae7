"""Fine-tuning harness tests (ISSUE 9): multi-host mesh geometry, MoE
expert-gradient sparsity composed with the per-leaf compressed wire, the
committed zoo specs, and the staged FinetuneLoop.

The expert-sparsity contract under test (docs/finetuning.md#expert-sparsity):
capacity dispatch scatters zero buffers to unrouted experts, so their wg/wu/wd
gradient slabs are EXACTLY zero; zero_inactive_expert_grads is then the
bitwise identity, a flat top-k leaf rule's payload only carries routed-expert
entries, and bits_by_leaf accounts for the routed fraction exactly.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, run_with_devices
from repro.configs import get_smoke_config
from repro.core import (BlockTopK, ExperimentSpec, SpecError, TopK,
                        make_compressor)
from repro.data import SyntheticLM
from repro.distributed import wire
from repro.launch.mesh import (make_multihost_mesh, multihost_worker_shape,
                               process_worker_slice)
from repro.models import build_model, moe
from repro.train.loop import (EVAL_SEED_XOR, FinetuneLoop, FinetuneSettings,
                              expert_sparse_rules, family_batch_extras)

SPECS_DIR = os.path.join(REPO, "examples", "specs")

# the committed zoo specs and their pinned fingerprints: these keys are how
# BENCH_perf/BENCH_bits zoo_scaling rows are addressed across the bench
# trajectory -- a fingerprint drift silently orphans every recorded row
ZOO_FINGERPRINTS = {
    "finetune_moe.json": "e9fcd7308f222d20",
    "zoo_qwen2.json": "ebfdbf39c6d3bd29",
    "zoo_mamba2.json": "6dafe51a691e8bb8",
}


# ---------------------------------------------------------------------------
# multi-host mesh geometry
# ---------------------------------------------------------------------------

def test_multihost_worker_shape():
    assert multihost_worker_shape(8, 2) == (2, 4)
    assert multihost_worker_shape(4, 4) == (4, 1)
    assert multihost_worker_shape(6, 1) == (1, 6)


def test_multihost_worker_shape_errors():
    with pytest.raises(ValueError, match="cannot tile"):
        multihost_worker_shape(6, 4)
    with pytest.raises(ValueError, match="num_processes"):
        multihost_worker_shape(4, 0)


def test_process_worker_slice():
    # (4, 1) mesh: 4 workers, trailing model axis does not change numbering
    assert process_worker_slice((4, 1), 2, 0) == range(0, 2)
    assert process_worker_slice((4, 1), 2, 1) == range(2, 4)
    # 1-d mesh is all workers (mesh_worker_count convention)
    assert process_worker_slice((8,), 4, 3) == range(6, 8)
    # 3-d pod mesh: workers = pod * data
    assert process_worker_slice((2, 4, 2), 2, 1) == range(4, 8)
    with pytest.raises(ValueError, match="out of range"):
        process_worker_slice((4, 1), 2, 2)
    with pytest.raises(ValueError, match="cannot tile"):
        process_worker_slice((4, 1), 3, 0)


def test_make_multihost_mesh_single_device():
    mesh = make_multihost_mesh((1, 1))
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_make_multihost_mesh_device_count_mismatch():
    with pytest.raises(ValueError, match="needs 4 devices"):
        make_multihost_mesh((4, 1))  # only 1 real device in tier-1


class _FakeDev:
    def __init__(self, process_index, id):
        self.process_index = process_index
        self.id = id


def test_make_multihost_mesh_rejects_non_process_major():
    # interleaved ownership: device 1 belongs to process 1 but sits in
    # process 0's block -- the check fires before any Mesh is built
    devs = [_FakeDev(0, 0), _FakeDev(1, 0), _FakeDev(0, 1), _FakeDev(1, 1)]
    with pytest.raises(ValueError, match="not process-major"):
        make_multihost_mesh((4, 1), num_processes=2, devices=devs)


def test_make_multihost_mesh_indivisible_leading_axis():
    with pytest.raises(ValueError, match="cannot tile"):
        make_multihost_mesh((4, 1), num_processes=3)


def test_make_multihost_mesh_default_axes_overflow():
    with pytest.raises(ValueError, match="pass axes= explicitly"):
        make_multihost_mesh((1, 1, 1, 1))


@pytest.mark.slow
def test_make_multihost_mesh_simulated_processes_4dev():
    out = run_with_devices("""
        import jax
        from repro.launch.mesh import (make_multihost_mesh, num_workers,
                                       process_worker_slice, worker_axes)

        for procs in (1, 2, 4):
            mesh = make_multihost_mesh((4, 1), num_processes=procs)
            assert mesh.axis_names == ("data", "model")
            assert num_workers(mesh) == 4
            # process-major: the flat device order IS sorted jax.devices()
            flat = list(mesh.devices.reshape(-1))
            want = sorted(jax.devices(),
                          key=lambda d: (d.process_index, d.id))
            assert flat == want, (flat, want)
            # every worker is owned by exactly one simulated process slice
            owned = [w for p in range(procs)
                     for w in process_worker_slice((4, 1), procs, p)]
            assert owned == list(range(4)), owned
        try:
            make_multihost_mesh((4, 1), num_processes=3)
        except ValueError as e:
            assert "cannot tile" in str(e)
        else:
            raise AssertionError("indivisible process count accepted")
        print("MULTIHOST_MESH_OK")
    """, n_devices=4)
    assert "MULTIHOST_MESH_OK" in out


# ---------------------------------------------------------------------------
# MoE expert-gradient sparsity x per-leaf wire
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def granite():
    """Granite smoke model under FIXED routing (zeroed router: every token
    deterministically routes to experts (0, 1)), plus one real backward."""
    cfg = get_smoke_config("granite-moe-3b-a800m")
    model = build_model(cfg)
    params = moe.fixed_routing_params(model.init(jax.random.key(0)))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=16, global_batch=4,
                       n_workers=1, seed=0)
    batch = data.batch(0)
    grads, _aux = jax.grad(model.loss, has_aux=True)(params, batch)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    return {"cfg": cfg, "model": model, "params": params, "batch": batch,
            "grads": grads}


def test_fixed_routing_inactive_slabs_exactly_zero(granite):
    """A real backward under fixed routing: experts (0, 1) active, (2, 3)
    gradient slabs EXACTLY zero -- so zero_inactive_expert_grads is the
    bitwise identity (the dispatch already produced the zeros)."""
    grads = granite["grads"]
    mg = grads["layers"]["moe"]
    mask = np.asarray(moe.expert_activity_mask(mg))
    assert mask.shape == (2, 4)  # (L, E) for the stacked granite smoke
    assert mask[:, :2].all() and not mask[:, 2:].any(), mask
    for name in moe.EXPERT_LEAVES:
        g = np.asarray(mg[name])
        assert np.all(g[:, 2:] == 0.0), name       # inactive: exact zeros
        assert np.any(g[:, :2] != 0.0), name       # routed: real gradient
    assert np.any(np.asarray(mg["router"]) != 0.0)  # router grads are dense
    masked = moe.zero_inactive_expert_grads(grads)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(masked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_inactive_with_explicit_mask(granite):
    """An explicit mask zeroes exactly the deselected slabs and leaves the
    router untouched."""
    grads = granite["grads"]["layers"]["moe"]
    m = jnp.asarray([[True, False, False, False],
                     [False, True, False, False]])
    out = moe.zero_inactive_expert_grads({"moe": grads}, mask=m)["moe"]
    for name in moe.EXPERT_LEAVES:
        g = np.asarray(out[name])
        assert np.all(g[0, 1:] == 0.0) and np.all(g[1, 0] == 0.0)
        assert np.all(g[1, 2:] == 0.0)
        np.testing.assert_array_equal(
            g[0, 0], np.asarray(grads[name][0, 0]))
    np.testing.assert_array_equal(np.asarray(out["router"]),
                                  np.asarray(grads["router"]))


def test_expert_sparse_rules_pinned(granite):
    """The committed granite rule string, and the a/E budget rescale for
    both entry-budget compressors."""
    cfg, params = granite["cfg"], granite["params"]
    rules = expert_sparse_rules(params, BlockTopK(256, 16),
                                n_experts=cfg.n_experts,
                                experts_per_tok=cfg.experts_per_tok)
    assert rules == ("layers/moe/wd=topk:8192;layers/moe/wg=topk:8192;"
                     "layers/moe/wu=topk:8192")
    # flat topk base: K = k * a / E
    rules = expert_sparse_rules(params, TopK(100), n_experts=cfg.n_experts,
                                experts_per_tok=cfg.experts_per_tok)
    assert rules.split(";")[0] == "layers/moe/wd=topk:50"
    with pytest.raises(ValueError, match="entry budget"):
        expert_sparse_rules(params, make_compressor("qsgd:16"),
                            n_experts=4, experts_per_tok=2)
    with pytest.raises(ValueError, match="no MoE subtree"):
        expert_sparse_rules({"w": jnp.zeros((4, 4))}, BlockTopK(256, 16),
                            n_experts=4, experts_per_tok=2)


def _expert_wire(granite_fix):
    cfg = granite_fix["cfg"]
    base = make_compressor("block_topk:256,16")
    rules = expert_sparse_rules(granite_fix["params"], base,
                                n_experts=cfg.n_experts,
                                experts_per_tok=cfg.experts_per_tok)
    fmt = wire.tree_format_for(base, granite_fix["grads"],
                               rules=wire.parse_leaf_rules(rules))
    return base, fmt


def test_masked_payload_decodes_identically_to_dense_then_zero(granite):
    """The satellite pin: the masked-expert payload is bit-identical to the
    raw-gradient payload (masking IS the identity under capacity dispatch),
    and its decode is supported ONLY on routed-expert slabs -- decode equals
    dense-then-zero bitwise."""
    grads = granite["grads"]
    _, fmt = _expert_wire(granite)
    h0 = jax.tree.map(jnp.zeros_like, grads)
    pay_raw, _ = fmt.encode_update(None, grads, h0, 1.0)
    pay_masked, _ = fmt.encode_update(
        None, moe.zero_inactive_expert_grads(grads), h0, 1.0)
    for a, b in zip(jax.tree.leaves(pay_raw), jax.tree.leaves(pay_masked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    decoded = fmt.decode(pay_raw)
    # dense-then-zero: zeroing inactive slabs of the decode changes nothing,
    # because every top-K entry already fell inside a routed slab
    rezeroed = moe.zero_inactive_expert_grads(decoded)
    for a, b in zip(jax.tree.leaves(decoded), jax.tree.leaves(rezeroed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for name in moe.EXPERT_LEAVES:
        d = np.asarray(decoded["layers"]["moe"][name])
        assert np.all(d[:, 2:] == 0.0), name
        assert np.count_nonzero(d) > 0, name


def test_bits_by_leaf_exact_under_routing(granite):
    """Exact accounting: composed bits == sum of per-leaf bits == measured
    payload bytes, and each expert leaf spends exactly a/E = 1/2 of its
    dense block-top-k budget (64 bits/entry on both sides at float32)."""
    grads = granite["grads"]
    base, fmt = _expert_wire(granite)
    by_leaf = fmt.bits_by_leaf()
    assert sum(by_leaf) == fmt.bits_per_round()
    h0 = jax.tree.map(jnp.zeros_like, grads)
    payloads, _ = fmt.encode_update(None, grads, h0, 1.0)
    assert wire.payload_bytes(payloads) * 8 == fmt.bits_per_round()

    dense = wire.tree_format_for(base, grads, rules=(("*", base),))
    dense_by_leaf = dense.bits_by_leaf()
    assert fmt.paths == dense.paths
    expert = [i for i, p in enumerate(fmt.paths)
              if p.split("/")[-1] in moe.EXPERT_LEAVES
              and "moe" in p.split("/")]
    assert len(expert) == 3
    for i in expert:
        assert by_leaf[i] == 8192 * 64            # topk:8192 at fp32
        assert dense_by_leaf[i] == 16384 * 64     # block_topk:256,16 dense
        assert 2 * by_leaf[i] == dense_by_leaf[i]
    for i in range(len(by_leaf)):                 # non-expert leaves: shared
        if i not in expert:
            assert by_leaf[i] == dense_by_leaf[i]


# ---------------------------------------------------------------------------
# fixed-routing fine-tune step: trainer == vmap oracle
# ---------------------------------------------------------------------------

def _oracle_code(mesh_shape, steps):
    """The fixed-routing step pin, parametrized by device count.

    The shard_map trainer is pinned TIGHT against the vmap oracle -- its
    per-worker gradients are the same single-shard computation the oracle
    runs, so compression sees bit-equal inputs.
    """
    return f"""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import get_smoke_config
        from repro.core import ExperimentSpec, build
        from repro.data import SyntheticLM, make_batch_shardings
        from repro.distributed.aggregate import efbv_aggregate_reference
        from repro.launch.mesh import make_mesh
        from repro.models import build_model, moe
        from repro.optim import constant, sgd
        from repro.train import (init_train_state, make_train_step,
                                 train_state_shardings)

        spec = ExperimentSpec.from_json(
            open("examples/specs/finetune_moe.json").read())
        run = build(spec)
        mesh = make_mesh({mesh_shape})
        n, lr, steps = {mesh_shape}[0], 0.05, {steps}
        cfg = get_smoke_config(spec.problem)
        model = build_model(cfg)
        params0 = moe.fixed_routing_params(model.init(jax.random.key(0)))
        params0 = jax.tree.map(np.asarray, params0)  # survives donation
        data = SyntheticLM(vocab=cfg.vocab, seq_len=16, global_batch=4 * n,
                           n_workers=n, seed=0)
        opt = sgd(constant(lr))
        key = jax.random.key(spec.seed)
        loss_fn = model.loss
        # jitted like the trainers' step: an eager, op-by-op gradient rounds
        # differently in the last bits, enough to flip block-top-k ties
        grad_fn = jax.jit(jax.grad(lambda p, b: loss_fn(p, b)[0]))

        st = init_train_state(params0, opt, mesh)
        sh = train_state_shardings(mesh, model.param_specs(), st)
        st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
        step = make_train_step(loss_fn, opt, run.algo, mesh,
                               agg_mode=spec.agg,
                               grad_transform=moe.zero_inactive_expert_grads)
        for i in range(steps):
            batch = make_batch_shardings(mesh, data.batch(i))
            st, m = step(st, batch, jax.random.fold_in(key, i))
        # the expert-sparsity invariant: h only ever accumulates compressed
        # MASKED grads, so inactive-expert slabs of h stay exactly zero.
        # Only checkable on the first step -- the router trains, so routing
        # is no longer pinned to experts (0, 1) afterwards.
        if steps == 1:
            for name in ("wg", "wu", "wd"):
                hh = np.asarray(st.h["layers"]["moe"][name])
                assert np.all(hh[:, :, 2:] == 0.0), name

        # the vmap oracle: per-worker grads on each worker's batch rows,
        # masked exactly as the trainer's grad_transform masks them
        w = jax.tree.map(jnp.asarray, params0)
        h = jax.tree.map(lambda p: jnp.zeros((n,) + p.shape), params0)
        h_avg = jax.tree.map(jnp.zeros_like, params0)
        per = 4  # rows per worker
        for i in range(steps):
            batch = data.batch(i)
            gs = []
            for j in range(n):
                shard_j = {{k: v[j * per:(j + 1) * per]
                           for k, v in batch.items()}}
                gj = grad_fn(w, shard_j)
                gj = jax.tree.map(lambda g: g.astype(jnp.float32), gj)
                gs.append(moe.zero_inactive_expert_grads(gj))
            grads = jax.tree.map(lambda *x: jnp.stack(x), *gs)
            ki = jax.random.fold_in(key, i)
            wkeys = jax.vmap(lambda j: jax.random.fold_in(ki, j))(
                jnp.arange(n))
            g, h, h_avg = efbv_aggregate_reference(
                run.algo, wkeys, grads, h, h_avg, mode=spec.agg)
            w = jax.tree.map(lambda p, gg: p - lr * gg, w, g)

        for a, b in zip(jax.tree.leaves(st.params), jax.tree.leaves(w)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        for a, b in zip(jax.tree.leaves(st.h), jax.tree.leaves(h)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        print("FIXED_ROUTING_ORACLE_MATCH")
    """


def test_fixed_routing_step_matches_oracle_1dev():
    """Single-worker tier-1 leg of the oracle pin: the trainer's fixed-
    routing fine-tune step (expert-sparse leaf rules from the committed
    finetune_moe spec, grad_transform masking) tracks the vmap oracle."""
    import subprocess
    import sys
    import textwrap

    from conftest import SRC

    # run in-process-style but isolated: the module-level fixture already
    # holds jax state; a subprocess keeps the 1-device regime explicit
    prog = textwrap.dedent(_oracle_code((1, 1), 2))
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "FIXED_ROUTING_ORACLE_MATCH" in res.stdout


@pytest.mark.slow
def test_fixed_routing_step_matches_oracle_4dev():
    """4-worker leg: the shard_map trainer == the vmap oracle (tight) under
    fixed routing with per-worker heterogeneous batches."""
    out = run_with_devices(_oracle_code((4, 1), 1) + "\n",
                           n_devices=4)
    assert "FIXED_ROUTING_ORACLE_MATCH" in out


# ---------------------------------------------------------------------------
# committed zoo specs
# ---------------------------------------------------------------------------

def test_committed_zoo_specs_pinned():
    """Byte-equality (file == spec.to_json()) and fingerprint pins for the
    three zoo specs the BENCH zoo_scaling rows are keyed by."""
    for fname, fp in ZOO_FINGERPRINTS.items():
        raw = open(os.path.join(SPECS_DIR, fname)).read()
        spec = ExperimentSpec.from_json(raw)
        assert raw == spec.to_json(), fname
        assert spec.fingerprint() == fp, fname
        assert spec.backend == "shard_map" and spec.mesh == "4x1", fname
        assert spec.compressor == "block_topk:256,16", fname
        assert spec.downlink == "qsgd:16", fname


def test_finetune_moe_spec_leaf_codecs_are_expert_sparse_rules(granite):
    """The committed MoE spec's leaf_codecs string IS the expert_sparse_rules
    output for its own config + base compressor (no hand-maintained drift)."""
    cfg = granite["cfg"]
    spec = ExperimentSpec.from_json(
        open(os.path.join(SPECS_DIR, "finetune_moe.json")).read())
    assert spec.problem == "granite-moe-3b-a800m" and spec.smoke
    want = expert_sparse_rules(granite["params"],
                               make_compressor(spec.compressor),
                               n_experts=cfg.n_experts,
                               experts_per_tok=cfg.experts_per_tok)
    assert spec.leaf_codecs == want


# ---------------------------------------------------------------------------
# the staged FinetuneLoop
# ---------------------------------------------------------------------------

def test_finetune_loop_rejects_reference_backend():
    spec = ExperimentSpec(compressor="topk:4", backend="reference",
                          problem="quadratic", d=32, n=2, steps=2)
    with pytest.raises(SpecError, match="reference"):
        FinetuneLoop(spec)


def test_finetune_loop_needs_config_for_non_zoo_problems():
    spec = ExperimentSpec(compressor="topk:4", backend="shard_map",
                          problem="quadratic", d=32, n=1, mesh="1x1",
                          steps=2)
    with pytest.raises(SpecError, match="config"):
        FinetuneLoop(spec)


def test_finetune_loop_stages_smoke():
    """All four stages on the cheapest zoo family (mamba2 smoke), single
    device: staged prerequisites, decorrelated eval stream, summary schema,
    exact wire accounting in the report."""
    spec = ExperimentSpec.from_json(
        open(os.path.join(SPECS_DIR, "zoo_mamba2.json")).read())
    spec = dataclasses.replace(spec, mesh="1x1", n=1, steps=2)
    # seq_len 32: the mamba2 SSD scan runs in chunks of 32 tokens
    st = FinetuneSettings(global_batch=2, seq_len=32, eval_batches=1,
                          log_every=1)
    loop = FinetuneLoop(spec, st, verbose=False)
    with pytest.raises(RuntimeError, match="setup"):
        loop.wire_report()
    summary = loop.run()
    assert summary["fingerprint"] == spec.fingerprint()
    assert summary["family"] == "ssm"
    assert summary["final_loss"] > 0 and summary["eval_loss"] > 0
    assert summary["steps_per_sec"] > 0
    rb = summary["round_bits"]
    assert 0 < rb["total"] < rb["dense_both_ways"]
    # eval stream is decorrelated from the train stream, same geometry
    assert loop.eval_data.seed == spec.seed ^ EVAL_SEED_XOR
    assert loop.data.seed == spec.seed
    assert loop.history and loop.history[-1]["eval_loss"] > 0


def test_family_batch_extras():
    import types

    vlm = types.SimpleNamespace(family="vlm", vision_patches=3, d_model=8)
    ed = types.SimpleNamespace(family="encdec", encoder_frames=5, d_model=8)
    dense = types.SimpleNamespace(family="dense")
    x = family_batch_extras(vlm, 2, 7)
    assert x["vision_embeds"].shape == (2, 3, 8)
    np.testing.assert_array_equal(
        x["vision_embeds"], family_batch_extras(vlm, 2, 7)["vision_embeds"])
    assert family_batch_extras(ed, 4, 0)["frames"].shape == (4, 5, 8)
    assert family_batch_extras(dense, 4, 0) == {}


def test_finetune_cli_mesh_sniffing(monkeypatch):
    """launch/finetune.py sizes the CPU device count from the spec's mesh
    before the loop touches JAX; a bad spec path is a friendly exit."""
    from repro.launch import finetune, runtime
    from repro.train import loop

    class Stop(Exception):
        pass

    def stop(*_a, **_k):
        raise Stop

    seen = []
    monkeypatch.setattr(runtime, "compile_cache", lambda: "")
    monkeypatch.setattr(runtime, "cpu_devices", seen.append)
    monkeypatch.setattr(loop, "FinetuneLoop", stop)
    p = os.path.join(SPECS_DIR, "finetune_moe.json")
    with pytest.raises(Stop):
        finetune.main(["--spec", p])
    assert seen == [4]                                  # the spec's 4x1 mesh
    with pytest.raises(SystemExit, match="bad experiment spec"):
        finetune.main(["--spec", "/nonexistent"])
    args = finetune.parse_args(["--spec", p, "--steps", "3",
                                "--processes", "2"])
    assert args.spec == p and args.steps == 3 and args.processes == 2
