"""Distributed-runtime tests.

Single-process tests cover the reference aggregation path (vmap semantics);
multi-device behavior (shard_map trainer, wire-mode equivalence, per-worker
gradient semantics, mini dry-run lowering) runs in subprocesses with forced
XLA host devices -- never globally (smoke tests must see 1 device).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.core import BlockTopK, EFBV, TopK
from repro.distributed.aggregate import efbv_aggregate_reference

KEY = jax.random.key(0)


def test_reference_agg_modes_identical():
    """dense_psum and sparse_allgather wire formats are bit-equivalent."""
    n, shape = 4, (32, 16)
    algo = EFBV(BlockTopK(64, 8), lam=0.8, nu=0.9)
    grads = {"w": jax.random.normal(KEY, (n,) + shape)}
    h = {"w": jnp.zeros((n,) + shape)}
    h_avg = {"w": jnp.zeros(shape)}
    keys = jax.random.split(KEY, n)  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
    outs = {}
    for mode in ["dense_psum", "sparse_allgather"]:
        outs[mode] = efbv_aggregate_reference(algo, keys, grads, h, h_avg,
                                              mode=mode)
    for a, b in zip(jax.tree.leaves(outs["dense_psum"]),
                    jax.tree.leaves(outs["sparse_allgather"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_reference_agg_matches_core_step():
    """The distributed-decomposed path == the core EFBV.step reference."""
    n, d = 4, 50
    algo = EFBV(TopK(5), lam=0.6, nu=0.8)
    grads = jax.random.normal(KEY, (n, d))
    st = algo.init(jnp.zeros(d), n)
    g_core, st2 = algo.step(KEY, grads, st)

    keys = jax.random.split(KEY, n)  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
    g_dist, h_new, h_avg_new = efbv_aggregate_reference(
        algo, keys, grads, st.h, st.h_avg, mode="dense_psum")
    np.testing.assert_allclose(np.asarray(g_core), np.asarray(g_dist),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(st2.h), np.asarray(h_new),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_trainer_modes_and_convergence_8dev():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EFBV, BlockTopK
        from repro.optim import sgd, constant
        from repro.train import make_train_step, init_train_state, train_state_shardings
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2))
        key = jax.random.key(0)
        D, H = 16, 32
        params = {"w1": jax.random.normal(key, (D, H)) * 0.1,
                  "w2": jax.random.normal(key, (H, D)) * 0.1}
        specs = {"w1": P(None, "model"), "w2": P("model", None)}

        def loss_fn(p, batch):
            pred = jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]
            l = jnp.mean((pred - batch["y"]) ** 2)
            return l, {}

        algo = EFBV.make(BlockTopK(16, 4), d=D * H, n=4)
        opt = sgd(constant(0.05))
        finals = {}
        for mode in ["dense_psum", "sparse_allgather"]:
            st = init_train_state(params, opt, mesh)
            sh = train_state_shardings(mesh, specs, st)
            st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
            step = make_train_step(loss_fn, opt, algo, mesh, agg_mode=mode)
            for i in range(120):
                kb = jax.random.fold_in(jax.random.key(42), i)
                x = jax.random.normal(kb, (16, D)); y = x * 0.3
                batch = {"x": jax.device_put(x, NamedSharding(mesh, P("data"))),
                         "y": jax.device_put(y, NamedSharding(mesh, P("data")))}
                st, m = step(st, batch, jax.random.fold_in(key, i))
            finals[mode] = float(m["loss"])
            print(mode, finals[mode])
        assert finals["dense_psum"] < 0.2, finals
        assert abs(finals["dense_psum"] - finals["sparse_allgather"]) < 1e-5, finals
        print("MODES_MATCH")
    """, n_devices=8)
    assert "MODES_MATCH" in out


@pytest.mark.slow
def test_per_worker_gradients_8dev():
    """The trainer's phase-1 gradient is this worker's nabla f_i, not the sum
    (regression test for the VMA psum-of-invariant-cotangent pitfall)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EFBV, Identity
        from repro.optim import sgd, constant
        from repro.train import make_train_step, init_train_state, train_state_shardings
        from repro.launch.mesh import make_mesh

        mesh = jax.make_mesh((4, 1), ("data", "model"))
        params = {"w": jnp.zeros((4,))}
        specs = {"w": P(None)}

        def loss_fn(p, batch):
            # worker i's loss: <w, x_i>; grad = x_i
            return jnp.sum(p["w"] * batch["x"][0]), {}

        algo = EFBV(Identity(), lam=1.0, nu=1.0)   # no compression
        opt = sgd(constant(1.0))
        st = init_train_state(params, opt, mesh)
        sh = train_state_shardings(mesh, specs, st)
        st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
        step = make_train_step(loss_fn, opt, algo, mesh)
        x = jnp.arange(16.0).reshape(4, 4)  # worker i sees row i
        batch = {"x": jax.device_put(x, NamedSharding(mesh, P("data")))}
        st2, m = step(st, batch, jax.random.key(0))
        # with identity compressor + zero h: g = mean_i x_i; w' = -g
        import numpy as np
        np.testing.assert_allclose(np.asarray(st2.params["w"]),
                                   -np.asarray(x.mean(0)), rtol=1e-6)
        # h_i must equal worker i's own gradient x_i (lam=1)
        np.testing.assert_allclose(np.asarray(st2.h["w"]), np.asarray(x),
                                   rtol=1e-6)
        print("PER_WORKER_OK")
    """, n_devices=8)
    assert "PER_WORKER_OK" in out


@pytest.mark.slow
def test_federated_trainer_matches_oracle_8dev():
    """The federated differential leg: the shard_map trainer under a
    RANDOMIZED bernoulli:0.5 participation trajectory matches the vmap
    oracle (efbv_aggregate_reference with the same masks/keys) step for
    step, in both wire modes -- and the sampled subsets are genuinely
    partial."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EFBV, BlockTopK, Participation
        from repro.core.efbv import participation_key
        from repro.distributed.aggregate import efbv_aggregate_reference
        from repro.optim import sgd, constant
        from repro.train import make_train_step, init_train_state, train_state_shardings

        mesh = jax.make_mesh((4, 1), ("data", "model"))
        n, D, lr = 4, 32, 0.2
        key = jax.random.key(0)
        # numpy-held so the train step's donated buffers can't delete the
        # oracle's copy of the initial point
        params = {"w": np.asarray(jax.random.normal(key, (D,)) * 0.1)}
        specs = {"w": P(None)}

        def loss_fn(p, batch):
            # worker i's local objective: 0.5||w - mean_rows(x_i)||^2,
            # grad = w - xbar_i (exactly computable for the oracle)
            xbar = jnp.mean(batch["x"], 0)
            return 0.5 * jnp.sum((p["w"] - xbar) ** 2), {}

        algo = EFBV(BlockTopK(8, 2), lam=0.6, nu=0.9)
        part = Participation.parse("bernoulli:0.5")
        opt = sgd(constant(lr))
        for mode in ["dense_psum", "sparse_allgather"]:
            st = init_train_state(params, opt, mesh)
            sh = train_state_shardings(mesh, specs, st)
            st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
            step = make_train_step(loss_fn, opt, algo, mesh, agg_mode=mode,
                                   participation=part)
            w = jnp.asarray(params["w"])
            h = jnp.zeros((n, D)); h_avg = jnp.zeros(D)
            sampled = 0
            for i in range(20):
                kb = jax.random.fold_in(jax.random.key(42), i)
                x = jax.random.normal(kb, (16, D))
                batch = {"x": jax.device_put(x, NamedSharding(mesh, P("data")))}
                ki = jax.random.fold_in(key, i)
                st, m = step(st, batch, ki)
                # the oracle redraws the SAME mask and worker keys
                mask = part.sample_mask(participation_key(ki), n)
                sampled += int(mask.sum())
                assert int(m["participants"]) == int(mask.sum())
                grads = w[None] - x.reshape(n, 4, D).mean(1)
                wkeys = jax.vmap(lambda j: jax.random.fold_in(ki, j))(
                    jnp.arange(n))
                g, h, h_avg = efbv_aggregate_reference(
                    algo, wkeys, grads, h, h_avg, mode=mode, masks=mask)
                w = w - lr * g
                np.testing.assert_allclose(np.asarray(st.params["w"]),
                                           np.asarray(w), rtol=1e-6, atol=1e-6)
                np.testing.assert_allclose(np.asarray(st.h["w"]),
                                           np.asarray(h), rtol=1e-6, atol=1e-6)
                np.testing.assert_allclose(np.asarray(st.h_avg["w"]),
                                           np.asarray(h_avg), rtol=1e-6,
                                           atol=1e-6)
            assert 0 < sampled < 20 * n, sampled  # genuinely partial rounds
            print(mode, "ok, sampled", sampled, "/", 20 * n)
        print("FED_ORACLE_MATCH")
    """, n_devices=8)
    assert "FED_ORACLE_MATCH" in out


@pytest.mark.slow
def test_federated_full_participation_bit_identical_8dev():
    """participation=bernoulli:1.0 (and fixed:n) must leave the trainer on
    the unmasked code path: params/h after several steps are BIT-identical
    to a participation=None run."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EFBV, BlockTopK, Participation
        from repro.optim import sgd, constant
        from repro.train import make_train_step, init_train_state, train_state_shardings

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        key = jax.random.key(0)
        D, H = 16, 32
        params = {"w1": jax.random.normal(key, (D, H)) * 0.1,
                  "w2": jax.random.normal(key, (H, D)) * 0.1}
        specs = {"w1": P(None, "model"), "w2": P("model", None)}

        def loss_fn(p, batch):
            pred = jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]
            return jnp.mean((pred - batch["y"]) ** 2), {}

        algo = EFBV.make(BlockTopK(16, 4), d=D * H, n=4)
        opt = sgd(constant(0.05))
        finals = {}
        for part in [None, Participation.parse("bernoulli:1.0"),
                     Participation.parse("fixed:4")]:
            st = init_train_state(params, opt, mesh)
            sh = train_state_shardings(mesh, specs, st)
            st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
            step = make_train_step(loss_fn, opt, algo, mesh,
                                   agg_mode="sparse_allgather",
                                   participation=part)
            for i in range(10):
                kb = jax.random.fold_in(jax.random.key(42), i)
                x = jax.random.normal(kb, (16, D)); y = x * 0.3
                batch = {"x": jax.device_put(x, NamedSharding(mesh, P("data"))),
                         "y": jax.device_put(y, NamedSharding(mesh, P("data")))}
                st, m = step(st, batch, jax.random.fold_in(key, i))
            finals[str(part)] = (np.asarray(st.params["w1"]),
                                 np.asarray(st.h["w1"]))
        ref = finals["None"]
        for name, got in finals.items():
            np.testing.assert_array_equal(got[0], ref[0], err_msg=name)
            np.testing.assert_array_equal(got[1], ref[1], err_msg=name)
        print("FED_FULL_BITWISE")
    """, n_devices=8)
    assert "FED_FULL_BITWISE" in out


@pytest.mark.slow
def test_mini_dryrun_lowering_16dev():
    """AOT lower+compile on a 2x2x4 mini-mesh with a smoke config:
    proves the (pod,data,model) sharding machinery end to end, cheaply."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.core import EFBV, BlockTopK
        from repro.optim import adamw, cosine
        from repro.train import init_train_state, make_train_step, train_state_shardings
        from repro.launch.mesh import make_mesh, num_workers
        SDS = jax.ShapeDtypeStruct

        mesh = make_mesh((2, 2, 4))  # pod x data x model
        cfg = get_smoke_config("granite-moe-3b-a800m")
        model = build_model(cfg)
        algo = EFBV.make(BlockTopK(128, 16), d=4096, n=num_workers(mesh))
        opt = adamw(cosine(1e-3, 100, 10))
        specs = model.param_specs()
        params_sds = model.init_abstract()
        shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda s: isinstance(s, P))
        params_sds = jax.tree.map(lambda s, h: SDS(s.shape, s.dtype, sharding=h),
                                  params_sds, shard)
        state = jax.eval_shape(lambda p: init_train_state(p, opt, mesh), params_sds)
        sh = train_state_shardings(mesh, specs, state)
        state = jax.tree.map(lambda s, h: SDS(s.shape, s.dtype, sharding=h), state, sh)
        bsh = NamedSharding(mesh, P(("pod", "data")))
        batch = {"tokens": SDS((8, 64), jnp.int32, sharding=bsh),
                 "labels": SDS((8, 64), jnp.int32, sharding=bsh)}
        key = jax.eval_shape(lambda: jax.random.key(0))
        step = make_train_step(model.loss, opt, algo, mesh)
        compiled = step.lower(state, batch, key).compile()
        from repro.compat import cost_analysis
        assert cost_analysis(compiled)["flops"] > 0
        txt = compiled.as_text()
        assert any(op in txt for op in ("all-reduce", "reduce-scatter")), "no worker collective found"
        print("MINI_DRYRUN_OK")
    """, n_devices=16)
    assert "MINI_DRYRUN_OK" in out
