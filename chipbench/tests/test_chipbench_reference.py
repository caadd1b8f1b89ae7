"""The benchmark's inputs and plain references against the program, at the
program's small presets on the CPU: the traffic generator's batches, the
initial weights, the loss and its gradient; the control (the reference a
precision step down) fails the committed limits of every cell."""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import numerics, reference  # noqa: E402
from chipbench.models import dense_gqa, mamba2_ssd  # noqa: E402
from chipbench.traffic.synthetic_lm import SyntheticLM  # noqa: E402


def small(arch):
    """(program's small preset, the same model in the reference's keys)."""
    from repro.configs import get_smoke_config

    from chipbench.readings import small_config

    c = get_smoke_config(arch)
    return c, (dense_gqa if c.family == "dense" else mamba2_ssd), small_config(c)


@pytest.mark.parametrize("seed,step,het,workers", [
    (0, 0, 0.5, 1), (2 ** 31 + 5, 3, 0.5, 4), (7, 11, 0.0, 2), (9, 2, 1.0, 4)])
def test_batches_match_the_programs_generator(seed, step, het, workers):
    from repro.data import SyntheticLM as Program

    kw = dict(vocab=50280, seq_len=64, global_batch=8, n_workers=workers,
              seed=seed, heterogeneity=het)
    ours, theirs = SyntheticLM(**kw).batch(step), Program(**kw).batch(step)
    for k in ("tokens", "labels"):
        assert ours[k].dtype == theirs[k].dtype
        assert ours[k].tobytes() == theirs[k].tobytes()


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-130m"])
def test_reference_matches_the_program_in_float32(arch):
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    cfg, model, ref_cfg = small(arch)
    prog = build_model(dataclasses.replace(cfg, activation_dtype="float32"))
    key = jax.random.key(2 ** 31 + 3)
    pp = jax.jit(prog.init)(key)
    rp = jax.jit(lambda k: model.init(ref_cfg, k))(key)
    assert jax.tree.structure(pp) == jax.tree.structure(rp)
    for a, b in zip(jax.tree.leaves(pp), jax.tree.leaves(rp)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    rng = np.random.default_rng(1)
    seq = 2 * cfg.ssm_chunk if cfg.family == "ssm" else 64
    tok = rng.integers(0, cfg.vocab, (2, seq)).astype(np.int32)
    lab = np.concatenate([tok[:, 1:], -np.ones((2, 1), np.int32)], 1)
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.jit(jax.value_and_grad(prog.loss, has_aux=True))(
            pp, {"tokens": tok, "labels": lab})

    def mean_loss(p):
        s, c = model.loss_sum(ref_cfg, numerics.EXACT, p, tok, lab)
        return s / c

    lr, gr = jax.jit(jax.value_and_grad(mean_loss))(rp)
    assert float(lr) == pytest.approx(float(lp), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_block_topk_and_tuning():
    import jax.numpy as jnp

    x = jnp.array([[3.0, -5.0, 1.0], [0.5, 2.0, -4.0]])
    # blocks of 4 over the flattened leaf: [3, -5, 1, .5] and [2, -4] padded
    got = reference.compress("block_topk:4,2", x)
    assert got.tolist() == [[3.0, -5.0, 0.0], [0.0, 2.0, -4.0]]
    assert reference.tuning("block_topk:256,16", "efbv") == (1.0, 1.0)
    assert reference.tuning("identity", "none") == (1.0, 1.0)


def test_worst_leaves_name_the_leaf_that_sets_each_number():
    """A leaf that one side leaves at zero reads exactly 1, and is named."""
    ones = [1.0, 1.0, 1.0]
    run = reference.Readings(ones, [1.0, 0.0, 1.0], ones, [ones, ones, [1.0, 1.0, 1.5]])
    ref = reference.Reference(
        reference.Readings(ones, [1.0, 2.0, 1.0], ones, [ones, ones, ones]), ones)
    numbers = reference.compare(run, ref)
    worst = reference.worst_leaves(run, ref, ["a", "b", "c"])
    assert numbers["grad_gap"] == 1.0 and numbers["state_gap"] == 0.5
    assert (worst["grad_gap"]["leaf"], worst["grad_gap"]["run"]) == ("b", 0.0)
    assert (worst["state_gap"]["tree"], worst["state_gap"]["leaf"]) == ("h", "c")
    assert worst["change_gap"]["gap"] == numbers["change_gap"] == 0.0


def test_leaf_names_follow_the_flatten_order():
    _, model, cfg = small("qwen2-0.5b")
    names = reference.leaf_names(model, cfg)
    assert len(names) == len(set(names)) and "embed" in names
    assert any(n.startswith("layers/") for n in names)


def cells():
    """(workload entry, the program's arch id) of every cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = []
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            out.append(pytest.param(w, json.load(f)["arch"], id=w["name"]))
    return out


@pytest.mark.parametrize("entry,arch", cells())
def test_control_fails_the_cells_limits(entry, arch):
    import jax.numpy as jnp

    cell = entry["name"]
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           entry["traffic"] + ".json")) as f:
        job = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "limits", cell + ".json")) as f:
        limits = json.load(f)
    cfg, model, ref_cfg = small(arch)
    job = dict(job, seq=2 * cfg.ssm_chunk if cfg.family == "ssm" else 32,
               global_batch=2 * job["workers"])
    exact = reference.trajectory(model, ref_cfg, job, 5)
    control = reference.trajectory(model, ref_cfg, job, 5, num=numerics.INT8,
                                   state_dtype=jnp.bfloat16)
    numbers = reference.compare(control.readings, exact)
    assert any(numbers[k] > limits[k] for k in limits), numbers


@pytest.mark.parametrize("entry,arch", cells())
def test_a_wrong_second_moment_fails_the_cells_limits(entry, arch):
    """AdamW with b2 = 0.999 for the stated 0.95 changes the parameters
    little in the warm-up's first steps; v shows it."""
    cell = entry["name"]
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           entry["traffic"] + ".json")) as f:
        job = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "limits", cell + ".json")) as f:
        limits = json.load(f)
    cfg, model, ref_cfg = small(arch)
    job = dict(job, seq=2 * cfg.ssm_chunk if cfg.family == "ssm" else 32,
               global_batch=2)
    exact = reference.trajectory(model, ref_cfg, job, 5)
    wrong = reference.trajectory(
        model, ref_cfg, dict(job, optimizer=dict(job["optimizer"], b2=0.999)), 5)
    numbers = reference.compare(wrong.readings, exact)
    assert numbers["state_gap"] > limits["state_gap"], numbers
