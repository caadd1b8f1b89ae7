"""The granite-moe reference (chipbench/models/granite_moe.py) against the
program at the one-chip preset's small size on the CPU: the initial
weights, the loss with its load-balancing term and every leaf's gradient;
the control, the half-row fault and a wrong second moment against the
cell's limits; the counts the benchmark reads for the cell."""

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
from chipbench.faults import HalfRow  # noqa: E402
from chipbench.kernels import gmm  # noqa: E402
from chipbench.models import granite_moe  # noqa: E402

ARCH = "granite-moe-3b-a800m-1chip"
CELL = "granite-moe-3b-a800m.efbv-btk.1chip"


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


def reference_keys(c) -> dict:
    """The reference's keys for one of the program's granite presets."""
    return {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.hd(),
            "vocab_size": c.vocab, "num_hidden_layers": c.n_layers,
            "num_experts_total": c.n_experts,
            "num_local_experts": c.experts_held(),
            "num_experts_per_tok": c.experts_per_tok,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps,
            "embedding_multiplier": c.embedding_multiplier,
            "attention_multiplier": c.attention_multiplier,
            "residual_multiplier": c.residual_multiplier,
            "logits_scaling": c.logits_scaling,
            "router_aux_loss_coef": c.router_aux_weight}


def small():
    from repro.configs import get_smoke_config

    c = get_smoke_config(ARCH)
    return c, reference_keys(c)


def test_the_configuration_file_is_the_programs_preset():
    from repro.configs import get_config

    cfg = load("configs", "granite-moe-3b-a800m.json")
    c = get_config(cfg["arch"])
    keys = reference_keys(c)
    assert {k: cfg[k] for k in keys} == keys
    assert {k: getattr(c, k) for k in cfg["program"]} == cfg["program"]
    assert c.param_count() == 277_346_304


def test_reference_matches_the_program_in_float32():
    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    c, ref_cfg = small()
    prog = build_model(dataclasses.replace(c, activation_dtype="float32"))
    key = jax.random.key(2 ** 31 + 3)
    pp = jax.jit(prog.init)(key)
    rp = jax.jit(lambda k: granite_moe.init(ref_cfg, k))(key)
    assert jax.tree.structure(pp) == jax.tree.structure(rp)
    for a, b in zip(jax.tree.leaves(pp), jax.tree.leaves(rp)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
    rng = np.random.default_rng(1)
    tok = rng.integers(0, c.vocab, (1, 64)).astype(np.int32)
    lab = np.concatenate([tok[:, 1:], -np.ones((1, 1), np.int32)], 1)
    with jax.default_matmul_precision("highest"):
        (lp, aux), gp = jax.jit(jax.value_and_grad(prog.loss, has_aux=True))(
            pp, {"tokens": tok, "labels": lab})

    def mean_loss(p):
        s, n = granite_moe.loss_sum(ref_cfg, numerics.EXACT, p, tok, lab)
        return s / n

    lr, gr = jax.jit(jax.value_and_grad(mean_loss))(rp)
    assert float(aux["aux_loss"]) > 0
    assert float(lr) == pytest.approx(float(lp), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.linalg.norm(a), np.linalg.norm(b),
                                   rtol=1e-4)
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("fault", ["control", "half_row", "second_moment"])
def test_the_faults_fail_the_cells_limits(fault):
    """The control (int8 products, bfloat16 state), the half-row fault and
    AdamW with b2 = 0.999 for the stated 0.95 (v shows it) each fail."""
    import jax.numpy as jnp

    job = dict(load("traffic", "efbv-btk.1x1.b1.s4096.json"), seq=64)
    limits = load("limits", CELL + ".json")
    _, ref_cfg = small()
    exact = reference.trajectory(granite_moe, ref_cfg, job, 5)
    if fault == "control":
        other = reference.trajectory(granite_moe, ref_cfg, job, 5,
                                     num=numerics.INT8,
                                     state_dtype=jnp.bfloat16)
    elif fault == "half_row":
        other = reference.trajectory(HalfRow(granite_moe), ref_cfg, job, 5)
    else:
        job = dict(job, optimizer=dict(job["optimizer"], b2=0.999))
        other = reference.trajectory(granite_moe, ref_cfg, job, 5)
    numbers = reference.compare(other.readings, exact)
    if fault == "second_moment":
        assert numbers["state_gap"] > limits["state_gap"], numbers
    assert any(numbers[k] > limits[k] for k in limits), numbers


def test_flops_and_grouped_product_work():
    cfg = load("configs", "granite-moe-3b-a800m.json")
    d, ff, L = 1536, 512, 8
    attn = 2 * d * d + 2 * d * 512
    per_layer = attn + d * 40 + 1.6 * 3 * d * ff
    want = 6 * (L * per_layer + 49155 * d) + 6 * L * 24 * 64 * 4096
    assert granite_moe.flops_per_token(cfg, 4096) == pytest.approx(want)
    nbytes, ops = gmm.work(cfg, 4096)
    rows = 4096 * 8 * 8 / 40
    assert ops == pytest.approx(4 * 3 * L * 2 * rows * d * ff)
    assert nbytes == pytest.approx(4 * 3 * L * 2 * (rows * (d + ff) + 8 * d * ff))


def test_trace_pattern_names_the_grouped_products_only():
    import re

    p = re.compile(gmm.TRACE_PATTERN)
    for hit in ("%gmm.45 = bf16[32768,512] custom-call(%a, %b)",
                "%tgmm.12 = bf16[8,1536,512] custom-call(%c)", "%gmm = f32[]"):
        assert p.search(hit), hit
    for miss in ("%fusion.3 = bf16[4] fusion(%gmm.45, %x)",
                 "%_efbv_pack_update.3 = f32[2] custom-call()",
                 "%my_gmm.1 = f32[1] add(%a, %b)"):
        assert not p.search(miss), miss
