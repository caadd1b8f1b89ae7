"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates a REDUCED same-family config and runs one forward/train
step on CPU, asserting output shapes and finiteness; plus decode-vs-forward
consistency per family and layer-level unit tests."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import build_model
from repro.models import layers as L
from repro.optim import adamw, constant
from repro.optim.optimizers import apply_updates

KEY = jax.random.key(0)
B, S = 2, 64


def make_batch(cfg, key=KEY, with_labels=True):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if with_labels:
        batch["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab)  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
            key, (B, cfg.vision_patches, cfg.d_model)) * 0.1
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
            key, (B, cfg.encoder_frames, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    """Reduced config: forward shapes + one optimizer step, no NaNs."""
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512 and cfg.n_experts <= 4
    model = build_model(cfg)
    params = model.init(KEY)
    batch = make_batch(cfg)

    logits, aux = jax.jit(model.forward)(params, batch)
    S_total = S + (cfg.vision_patches if cfg.family == "vlm" else 0)
    assert logits.shape == (B, S_total, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    opt = adamw(constant(1e-3))
    opt_state = opt.init(params)
    (loss, _), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True))(params, batch)
    assert bool(jnp.isfinite(loss))
    g_leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in g_leaves)
    updates, opt_state = opt.update(grads, opt_state, params)
    new_params = apply_updates(params, updates)
    loss2, _ = jax.jit(model.loss)(new_params, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    cache = model.init_cache(B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, cache2 = jax.jit(model.decode_step)(params, cache, tok, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-130m", "zamba2-7b",
                                  "granite-moe-3b-a800m", "whisper-medium",
                                  "qwen2-vl-2b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode reproduces the full forward logits (fp32)."""
    S_ = 16
    cfg = get_smoke_config(arch)
    # MoE routing is dropless, so batched prefill and one-token decode route
    # every token alike
    cfg = dataclasses.replace(cfg, remat=False, activation_dtype="float32",
                              ssm_chunk=8)
    model = build_model(cfg)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (B, S_), 0, cfg.vocab)
    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch.pop("vision_embeds", None)  # text-only decode path
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(KEY, (B, cfg.encoder_frames,  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
                                                  cfg.d_model)) * 0.1
    full, _ = jax.jit(model.forward)(params, batch)
    cache = model.init_cache(B, S_)
    if cfg.family == "encdec":
        cache = model.encode_cross_cache(params, batch["frames"], cache)
    dec = jax.jit(model.decode_step)
    outs = []
    for t in range(S_):
        lg, cache = dec(params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(lg[:, 0])
    got = jnp.stack(outs, 1)
    ref = full[:, -S_:] if cfg.family == "vlm" else full
    rel = float(jnp.max(jnp.abs(got - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 2e-2, rel


def test_sliding_window_masks_old_tokens():
    """A token beyond the window must not influence attention output."""
    cfg = dataclasses.replace(get_smoke_config("qwen2-0.5b"), attn_window=8,
                              remat=False, activation_dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (1, 24), 0, cfg.vocab)
    toks2 = toks.at[0, 0].set((toks[0, 0] + 7) % cfg.vocab)  # mutate pos 0
    l1, _ = model.forward(params, {"tokens": toks})
    l2, _ = model.forward(params, {"tokens": toks2})
    # last position is > window away from position 0: logits identical
    np.testing.assert_allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]),
                               atol=1e-5)
    # but an in-window position does change
    assert float(jnp.max(jnp.abs(l1[0, 4] - l2[0, 4]))) > 1e-6


def test_gqa_head_grouping():
    """GQA: with n_kv < n_heads, groups of queries share one kv head."""
    d, H, K, hd = 32, 4, 2, 8
    p, _ = L.attention_init(jax.random.key(1), d, H, K, hd, qkv_bias=False)
    x = jax.random.normal(KEY, (1, 6, d))
    pos = jnp.broadcast_to(jnp.arange(6), (1, 6))
    out = L.attention(p, x, n_heads=H, n_kv=K, hd=hd, positions=pos,
                      theta=1e4)
    assert out.shape == (1, 6, d)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_rope_is_relative():
    """RoPE: q.k depends only on relative offsets."""
    hd = 16
    q = jax.random.normal(KEY, (1, 1, 1, hd))
    k = jax.random.normal(jax.random.key(1), (1, 1, 1, hd))
    def score(pq, pk):
        qr = L.apply_rope(q, jnp.asarray([[pq]]), 1e4)
        kr = L.apply_rope(k, jnp.asarray([[pk]]), 1e4)
        return float(jnp.sum(qr * kr))
    assert abs(score(3, 1) - score(10, 8)) < 1e-4
    assert abs(score(3, 1) - score(5, 1)) > 1e-4


def test_mrope_sections_rotate_independently():
    """M-RoPE: changing only the h-position stream must not affect the
    temporal-section channels."""
    hd = 16
    secs = (3, 3, 2)
    x = jax.random.normal(KEY, (1, 1, 1, hd))
    p1 = jnp.zeros((3, 1, 1), jnp.int32).at[0].set(5)
    p2 = p1.at[1].set(9)
    y1 = L.apply_mrope(x, p1, 1e4, secs)
    y2 = L.apply_mrope(x, p2, 1e4, secs)
    # temporal section channels: 0:3 and 8:11 (paired halves)
    np.testing.assert_allclose(np.asarray(y1[..., 0:3]), np.asarray(y2[..., 0:3]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y1[..., 8:11]), np.asarray(y2[..., 8:11]),
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(y1 - y2))) > 1e-6


def test_moe_router_balance_loss():
    """The load-balancing loss E * sum_e (c_e / T) * mean_t p_te over all k
    choices: k exactly under a uniform router, and the hand computation
    under a random one."""
    from repro.models.moe import moe_apply, moe_init
    p, _ = moe_init(jax.random.key(2), 16, 32, 4)
    x = jax.random.normal(KEY, (2, 8, 16))
    out, st = moe_apply(p, x, n_experts=4, k=2)
    assert out.shape == x.shape
    logits = x.reshape(16, 16) @ p["router"]
    ids = jax.lax.top_k(logits, 2)[1]
    c = jnp.sum(jax.nn.one_hot(ids, 4), axis=(0, 1))
    want = 4 * jnp.sum(c / 16 * jnp.mean(jax.nn.softmax(logits), axis=0))
    assert float(st["aux"]) == pytest.approx(float(want), rel=1e-5)
    _, st0 = moe_apply(dict(p, router=jnp.zeros_like(p["router"])), x,
                       n_experts=4, k=2)
    assert float(st0["aux"]) == pytest.approx(2.0, rel=1e-6)


def _swiglu_expert(p, e, x):
    return (jax.nn.silu(x @ p["wg"][e]) * (x @ p["wu"][e])) @ p["wd"][e]


def test_moe_shares_sum_to_the_uncut_layer():
    """Expert parallelism over 5 chips: each share routes over all 10
    experts and computes its own 2; the five partial outputs add up to the
    layer that holds all 10, and every share reads the same routing loss."""
    from repro.models.moe import moe_apply, moe_init
    E, k, held = 10, 4, 2
    p, _ = moe_init(jax.random.key(5), 32, 16, E)
    x = jax.random.normal(KEY, (2, 12, 32))
    whole, st = moe_apply(p, x, n_experts=E, k=k)
    parts = []
    for s in range(E // held):
        mine = list(range(s * held, (s + 1) * held))
        order = jnp.asarray(mine + [e for e in range(E) if e not in mine])
        share = {"router": p["router"][:, order],
                 **{n: p[n][order[:held]] for n in ("wg", "wu", "wd")}}
        out, st_s = moe_apply(share, x, n_experts=E, k=k)
        assert float(st_s["aux"]) == pytest.approx(float(st["aux"]), rel=1e-6)
        parts.append(out)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def test_moe_dropless_under_skewed_router():
    """Every token routed to the same 8 experts (a zeroed router ties all
    logits, so top-8 takes ids 0..7): nothing is dropped however many
    tokens pile onto them, and the layer equals a dense per-expert sum."""
    from repro.models.moe import moe_apply, moe_init
    E, k, held = 40, 8, 10
    p, _ = moe_init(jax.random.key(6), 16, 8, E, held)
    p = dict(p, router=jnp.zeros_like(p["router"]))
    x = jax.random.normal(KEY, (2, 24, 16))
    out, st = moe_apply(p, x, n_experts=E, k=k)
    want = sum(_swiglu_expert(p, e, x) for e in range(k)) / k
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert int(st["held_rows"]) == 2 * 24 * k      # every assignment ran
    assert float(st["load"]) == pytest.approx(48 / (48 * k / held))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_kernel_matches_oracle(dtype):
    """The grouped-product kernel (interpret mode) against its jnp oracle,
    forward and gradient, with an empty group and rows of no held group;
    the empty group's weight gradient is exactly zero in both."""
    from repro.kernels.gmm import gmm
    sizes = jnp.asarray([10, 0, 25, 7, 22], jnp.int32)   # 4 groups + none
    ka, kb, kc = jax.random.split(jax.random.key(7), 3)
    lhs = jax.random.normal(ka, (64, 128)).astype(dtype)
    rhs = jax.random.normal(kb, (4, 128, 256)).astype(dtype)
    ct = jax.random.normal(kc, (64, 256)).astype(dtype)

    def run(impl):
        f = lambda a, b: jnp.sum((gmm(a, b, sizes, impl=impl) * ct)
                                 .astype(jnp.float32))
        return gmm(lhs, rhs, sizes, impl=impl), jax.grad(f, (0, 1))(lhs, rhs)

    (yk, (dak, dbk)), (yo, (dao, dbo)) = run("interpret"), run("oracle")
    tol = dict(rtol=2e-2, atol=2e-1) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-4)
    for a, b in ((yk, yo), (dak, dao), (dbk, dbo)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)
    for y, da, db in ((yk, dak, dbk), (yo, dao, dbo)):
        assert not np.any(np.asarray(y[42:], np.float32))    # no held group
        assert not np.any(np.asarray(da[42:], np.float32))
        assert not np.any(np.asarray(db[1], np.float32))     # empty group
        assert np.all(np.any(np.asarray(db, np.float32)[[0, 2, 3]] != 0,
                             axis=(1, 2)))


def test_gmm_expert_parallel_over_model_matches_oracle():
    """On a 2 x 2 ('data', 'model') mesh of GSPMD-auto axes the kernel
    (interpret mode) splits the 4 groups over the 2-way 'model' axis and
    matches the oracle, forward and gradient, with no all-gather of the
    model-sharded weights and their gradient sharded like them."""
    from conftest import run_with_devices
    out = run_with_devices("""
        import re
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.kernels import gmm as G

        mesh = compat.make_mesh((2, 2), ("data", "model"))
        sizes = jnp.asarray([10, 0, 25, 7, 22], jnp.int32)   # 4 groups + none
        ka, kb, kc = jax.random.split(jax.random.key(7), 3)
        lhs = jax.random.normal(ka, (64, 128))
        rhs = jax.random.normal(kb, (4, 128, 256))
        ct = jax.random.normal(kc, (64, 256))

        def run(impl, a, b):
            loss = lambda a, b: jnp.sum(G.gmm(a, b, sizes, impl=impl) * ct)
            return G.gmm(a, b, sizes, impl=impl), jax.grad(loss, (0, 1))(a, b)

        with jax.set_mesh(mesh):
            assert G._expert_shards(4) == 2 and G._expert_shards(3) == 1
            step = jax.jit(lambda a, b: run("interpret", a, b))
            args = (lhs, jax.device_put(rhs, NamedSharding(mesh, P("model"))))
            text = step.lower(*args).compile().as_text()
            y, (da, db) = step(*args)
        assert tuple(db.sharding.spec)[:1] == ("model",), db.sharding
        gathers = [l for l in text.splitlines()
                   if re.search(r"all-gather(-start)?\\(", l)
                   and "[4,128,256]" in l.split("=")[1]]
        assert not gathers, gathers
        yo, (dao, dbo) = run("oracle", lhs, rhs)
        for a, b in ((y, yo), (da, dao), (db, dbo)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-4)
        print("OK")
    """, 4)
    assert "OK" in out


def test_granite_multipliers_and_tied_head():
    """Granite's paths against a hand-written one-layer model: embedding x
    12, attention scores x 1/64, each branch x 0.22 before its residual add,
    logits / 6 from the tied embedding."""
    from repro.models.moe import moe_apply
    cfg = dataclasses.replace(get_smoke_config("granite-moe-3b-a800m"),
                              n_layers=1, remat=False,
                              activation_dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    assert "lm_head" not in params
    toks = jax.random.randint(KEY, (B, 16), 0, cfg.vocab)
    got, _ = model.forward(params, {"tokens": toks})

    lp = jax.tree.map(lambda a: a[0], params["layers"])
    a, H, K, hd, eps = lp["attn"], cfg.n_heads, cfg.n_kv_heads, 64, 1e-6
    rms = lambda v, w: v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w
    h = params["embed"][toks] * 12.0
    x = rms(h, lp["ln1"])
    pos = jnp.broadcast_to(jnp.arange(16), (B, 16))
    q = L.apply_rope((x @ a["wq"]).reshape(B, 16, H, hd), pos, 1e4)
    kk = L.apply_rope((x @ a["wk"]).reshape(B, 16, K, hd), pos, 1e4)
    v = (x @ a["wv"]).reshape(B, 16, K, hd)
    kk, v = jnp.repeat(kk, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, kk) * 0.015625
    s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v)
    h = h + 0.22 * (o.reshape(B, 16, H * hd) @ a["wo"])
    y, _ = moe_apply(lp["moe"], rms(h, lp["ln2"]), n_experts=cfg.n_experts,
                     k=cfg.experts_per_tok)
    h = h + 0.22 * y
    want = rms(h, params["final_norm"]) @ params["embed"].T / 6.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_unrouted_held_expert_gradient_is_exactly_zero():
    """A held expert the router never picks gets exactly-zero gradient
    slabs in all three expert leaves; the routed ones get real gradients."""
    from repro.models.moe import expert_activity_mask, moe_apply, moe_init
    E, held = 8, 4
    p, _ = moe_init(jax.random.key(8), 16, 8, E, held)
    p = dict(p, router=p["router"].at[:, 2].set(-1e3))   # expert 2 never
    x = jnp.abs(jax.random.normal(KEY, (2, 16, 16)))     # positive inputs

    def loss(q):
        out, st = moe_apply(q, x, n_experts=E, k=2)
        return jnp.sum(out ** 2) + st["aux"]

    g = jax.grad(loss)(p)
    for name in ("wg", "wu", "wd"):
        assert not np.any(np.asarray(g[name][2])), name
    assert np.asarray(expert_activity_mask(g)).tolist() == [True, True,
                                                           False, True]


def test_moe_counters_in_the_loss_metrics():
    """An MoE model's loss reports its routing counters; a dense model's
    reports what it did before."""
    moe_cfg = get_smoke_config("granite-moe-3b-a800m-1chip")
    model = build_model(moe_cfg)
    _, m = model.loss(model.init(KEY), make_batch(moe_cfg))
    assert set(m) == {"ce", "aux_loss", "moe_held_rows",
                      "moe_load_max_over_mean"}
    assert 0 < float(m["moe_held_rows"]) <= B * S * 2 * moe_cfg.n_layers
    assert float(m["moe_load_max_over_mean"]) >= 1.0
    dense = get_smoke_config("qwen2-0.5b")
    dm = build_model(dense)
    assert set(dm.loss(dm.init(KEY), make_batch(dense))[1]) == {"ce",
                                                                "aux_loss"}


def test_mamba2_chunk_invariance():
    """SSD output must not depend on the chunk size (pure algebra identity)."""
    from repro.models.mamba2 import mamba2_apply, mamba2_init
    d, di, st, nh = 16, 32, 8, 4
    p, _ = mamba2_init(jax.random.key(3), d, d_inner=di, d_state=st,
                       n_heads=nh, d_conv=4)
    x = jax.random.normal(KEY, (2, 32, d))
    y1 = mamba2_apply(p, x, d_inner=di, d_state=st, n_heads=nh, chunk=8)
    y2 = mamba2_apply(p, x, d_inner=di, d_state=st, n_heads=nh, chunk=32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3,
                               atol=2e-4)


def test_param_specs_structure_matches_params():
    for arch in ARCHS:
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        params = jax.eval_shape(model.init, KEY)
        specs = model.param_specs()
        # tree structures must match leaf-for-leaf
        jax.tree.map(lambda p, s: None, params, specs,
                     is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def test_full_config_param_counts():
    """Full (non-smoke) configs match their published parameter scale."""
    expect = {
        "minitron-8b": (7e9, 10e9),
        "phi3-medium-14b": (12e9, 15.5e9),
        "dbrx-132b": (120e9, 140e9),
        "mamba2-130m": (0.1e9, 0.2e9),
        "qwen2-0.5b": (0.4e9, 0.7e9),
        "minicpm-2b": (2.2e9, 3.3e9),
        "zamba2-7b": (6e9, 8.5e9),
        "qwen2-vl-2b": (1.2e9, 2.3e9),
        "whisper-medium": (0.6e9, 1.1e9),  # SwiGLU MLP (3 mats) vs GELU (2)
        "granite-moe-3b-a800m": (2.5e9, 4e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, f"{n:,}", lo, hi)
