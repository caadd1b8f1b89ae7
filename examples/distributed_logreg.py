"""The paper's experiment on an actual device mesh, declared as a spec:
EF-BV vs EF21 vs DIANA on heterogeneous logistic regression, with the
compressed aggregation running through the SAME shard_map trainer used for
LM training (not the vmap reference).  8 fake XLA devices; bits-on-the-wire
accounting included.

The whole cross-product -- compressor, algorithm mode, backend, mesh --
lives in ONE :class:`repro.core.ExperimentSpec`; ``build(spec)`` hands back
the shard_map trainer (``run.train_step``), the state
init/shardings, and the exact wire accounting.

    PYTHONPATH=src python examples/distributed_logreg.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import ExperimentSpec, build  # noqa: E402
from repro.optim import sgd, constant  # noqa: E402
from repro.problems import LogReg, make_synthetic  # noqa: E402


def main():
    d = 64
    spec = ExperimentSpec(compressor="comp:1,32", backend="shard_map",
                          problem="logreg", mesh="8x1", n=8, d=d,
                          steps=2000, seed=0)

    A, b = make_synthetic(jax.random.key(0), N=800, d=d)
    prob = LogReg.split(A, b, n=spec.n, mu_reg=0.1)
    x_star, f_star = prob.solve()
    rounds = spec.steps
    bits_per_round = 32 * 2 * 1  # k=1: one (index, value) pair per worker
    for mode in ["efbv", "ef21", "diana"]:
        run = build(dataclasses.replace(spec, mode=mode))
        mesh = run.make_mesh()
        # run.algo carries the auto-tuned (lam*, nu*); the stepsize needs the
        # problem's smoothness constants on top (Thm 1)
        from repro.core import tune_for
        t = tune_for(run.compressor, d, run.n, mode=mode, L=prob.L(),
                     Ltilde=prob.L_tilde())
        opt = sgd(constant(t.gamma))

        def loss_fn(params, batch):
            x = params["x"]
            z = -batch["b"][0] * (batch["A"][0] @ x)
            loss = jnp.mean(jnp.logaddexp(0.0, z)) + 0.05 * jnp.sum(x * x) * 2
            return loss, {}

        params = {"x": jnp.zeros(d)}
        state = run.init_state(params, opt, mesh)
        sh = run.state_shardings(mesh, {"x": P(None)}, state)
        state = jax.tree.map(lambda a, s: jax.device_put(a, s), state, sh)
        batch = {
            "A": jax.device_put(prob.A[:, None], NamedSharding(mesh, P("data"))),
            "b": jax.device_put(prob.b[:, None], NamedSharding(mesh, P("data"))),
        }
        step = run.train_step(loss_fn, opt, mesh)
        key = jax.random.key(1)
        for i in range(rounds):
            state, metrics = step(state, batch, jax.random.fold_in(key, i))
        gap = float(prob.f(state.params["x"]) - f_star)
        print(f"{mode:6s} lam={run.algo.lam:.4f} nu={run.algo.nu:.4f} "
              f"gamma={t.gamma:.2e} f-f*={gap:.3e} after "
              f"{rounds * bits_per_round} bits/worker")


if __name__ == "__main__":
    main()
