"""The plain reference of a cell's first training steps, and the comparison
that decides ``correct``.

The reference trains the configuration's own model (``chipbench/models``)
from the run's seed on the same rows the program is fed, for the first
three steps of the job the traffic file states, on the one worker every
cell runs: the gradient of the mean next-token loss, EF-BV's worker and
master updates
(Condat, Yi, Richtarik 2022, Algorithm 1:
d_i = C(grad_i - h_i), h_i += lam d_i, g = h_avg + nu mean_i d_i,
h_avg += lam mean_i d_i), then AdamW on g under the job's schedule.  It
imports nothing of the program and takes nothing the program made.

Four numbers compare a run with it (:func:`compare`), each with its own
limit (``chipbench/limits/<cell>.json``):

- ``loss_gap``: the largest relative gap of the three steps' losses;
- ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient as the optimizer gets it (g of step 0), relative to the larger
  of the reference's norm of that leaf and of the median leaf;
- ``change_gap``: the same for each leaf's change over the three steps,
  leaving out the leaves whose reference gradient is under a thousandth of
  the median leaf's (their moves are round-off that Adam blows up);
- ``state_gap``: the same for the norms of AdamW's m and v and of EF-BV's
  h after the three steps, the worst leaf of the three trees.  Adam's first
  updates are close to lr per element whatever the gradient, so the change
  alone cannot see a wrong second moment; v and h can.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import numerics as N
from chipbench.traffic.synthetic_lm import SyntheticLM

CHECK_STEPS = 3
#: leaves whose reference gradient norm is under this share of the median
#: leaf's are left out of ``change_gap``
NOUGHT = 1e-3


class Readings(NamedTuple):
    """What the comparison reads of a run: the three steps' losses, each
    leaf's norm of step 0's g, each leaf's norm of its change over the
    three steps, and after them each leaf's norm of m, v and h (three lists),
    leaves in flatten order."""

    losses: List[float]
    g0_norms: List[float]
    change_norms: List[float]
    state_norms: List[List[float]]


class Reference(NamedTuple):
    readings: Readings
    grad_norms: List[float]   # each leaf's norm of step 0's raw gradient


# ---------------------------------------------------------------------------
# the algorithm's pieces, stated plainly
# ---------------------------------------------------------------------------

def tuning(compressor: str, algo: str):
    """(lam, nu) of Remark 1 for the cell's compressor:
    lam* = min((1 - eta) / ((1 - eta)^2 + omega), 1), nu likewise with
    omega_av.  Block-top-k (k of every b) is biased with eta =
    sqrt(1 - k/b) and omega = 0, and the identity has eta = omega = 0, so
    both come to lam = nu = 1; so does ``algo: none``."""
    name = compressor.split(":")[0]
    if name not in ("identity", "block_topk"):
        raise ValueError(f"the reference has no compressor {compressor!r}")
    if algo not in ("efbv", "none"):
        raise ValueError(f"the reference has no algorithm {algo!r}")
    if algo == "none" or name == "identity":
        return 1.0, 1.0
    b, k = (int(x) for x in compressor.split(":")[1].split(","))
    eta, omega = math.sqrt(1.0 - k / b), 0.0
    lam = min((1.0 - eta) / ((1.0 - eta) ** 2 + omega), 1.0)
    return lam, lam


def compress(compressor: str, x):
    """C(x) for one leaf: identity, or block-top-k over the flattened leaf
    (blocks of b, zero-padded at the end; the k largest magnitudes of each
    block kept)."""
    name = compressor.split(":")[0]
    if name == "identity":
        return x
    b, k = (int(v) for v in compressor.split(":")[1].split(","))
    flat = x.reshape(-1)
    nb = -(-flat.size // b)
    blocks = jnp.pad(flat, (0, nb * b - flat.size)).reshape(nb, b)
    _, idx = jax.lax.top_k(jnp.abs(blocks), k)
    keep = jnp.zeros(blocks.shape, bool).at[jnp.arange(nb)[:, None], idx].set(True)
    return jnp.where(keep, blocks, 0.0).reshape(-1)[:flat.size].reshape(x.shape)


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up from 0, then cosine decay to ``final_frac``."""
    warm = min(step / opt["warmup_steps"], 1.0) if opt["warmup_steps"] else 1.0
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    ff = opt["final_frac"]
    return opt["lr"] * warm * (ff + (1.0 - ff) * 0.5 * (1.0 + math.cos(math.pi * t)))


@jax.jit
def device_norms(tree):
    """Each leaf's norm, in float32, on the device."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> List[float]:
    return [float(x) for x in jax.device_get(device_norms(tree))]


def host_change_norms(before, after) -> List[float]:
    """Each leaf's ||after - before|| from host copies, in float64."""
    return [float(np.linalg.norm((np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64)).ravel()))
            for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after))]


# ---------------------------------------------------------------------------
# the trajectory
# ---------------------------------------------------------------------------

def _worker_grad(model, cfg, num, rows, params, tokens, labels):
    """(loss sum, label count, gradient of the mean loss) over a worker's
    rows, ``rows`` at a time, gradients summed in float32."""
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    blocks = tokens.shape[0] // rows
    tok = tokens.reshape(blocks, rows, -1)
    lab = labels.reshape(blocks, rows, -1)

    def one(carry, xs):
        (s, c), g = jax.value_and_grad(
            lambda p: model.loss_sum(cfg, num, p, xs[0], xs[1]),
            has_aux=True)(p32)
        return (carry[0] + s, carry[1] + c,
                jax.tree.map(jnp.add, carry[2], g)), None

    zero = jnp.zeros((), jnp.float32)
    init = (zero, zero, jax.tree.map(jnp.zeros_like, p32))
    (s, c, g), _ = jax.lax.scan(one, init, (tok, lab))
    return s, c, jax.tree.map(lambda x: x / c, g)


def trajectory(model, cfg: dict, job: dict, seed: int, *,
               num: N.Numerics = N.EXACT, state_dtype=jnp.float32,
               keep_rows: float = 1.0) -> Reference:
    """The first three steps of the cell's job, from ``seed``, on one worker.

    ``num`` and ``state_dtype`` step the precision down for the control;
    ``keep_rows`` < 1 feeds the worker only the first share of its rows
    (the half-batch fault).  With one worker the master's h_avg is the
    worker's h itself (h_avg = mean_i h_i, and both take the same update),
    so it is held once."""
    if job["workers"] != 1:
        raise ValueError("the reference trains one worker; the cell has "
                         f"{job['workers']}")
    B = job["global_batch"]
    kept = max(int(B * keep_rows), 1)
    rows = min(job["reference_rows"], kept)
    if kept % rows:
        raise ValueError(f"{kept} rows do not split into blocks of {rows}")
    lam, nu = tuning(job["compressor"], job["algo"])
    opt = job["optimizer"]
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    sd = jnp.dtype(state_dtype)
    cast = lambda t: jax.tree.map(lambda x: x.astype(sd), t)  # noqa: E731

    data = SyntheticLM(vocab=cfg["vocab_size"], seq_len=job["seq"],
                       global_batch=B, n_workers=1, seed=seed,
                       heterogeneity=job["heterogeneity"])
    params = jax.jit(lambda k: cast(model.init(cfg, k)))(jax.random.key(seed))
    p0 = jax.device_get(params)
    zeros = jax.jit(lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, sd), p0))
    m, v, h = zeros(), zeros(), zeros()

    grad_fn = jax.jit(functools.partial(_worker_grad, model, cfg, num, rows))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def efbv(g, h_):
        """d = C(grad - h); g = h + nu d; h += lam d."""
        d = jax.tree.map(lambda a, b: compress(
            job["compressor"], a - b.astype(jnp.float32)), g, h_)
        g = jax.tree.map(lambda a, b: a.astype(jnp.float32) + nu * b, h_, d)
        return g, cast(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) + lam * b, h_, d))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(p, m_, v_, g, lr, count):
        m_ = jax.tree.map(lambda a, b: b1 * a.astype(jnp.float32)
                          + (1 - b1) * b, m_, g)
        v_ = jax.tree.map(lambda a, b: b2 * a.astype(jnp.float32)
                          + (1 - b2) * b * b, v_, g)
        c1, c2 = 1.0 - b1 ** count, 1.0 - b2 ** count
        p = jax.tree.map(
            lambda w, a, b: w.astype(jnp.float32) - lr * (
                (a / c1) / (jnp.sqrt(b / c2) + eps)
                + wd * w.astype(jnp.float32)), p, m_, v_)
        return cast(p), cast(m_), cast(v_)

    losses, g0_norms, grad_norms = [], None, None
    for t in range(CHECK_STEPS):
        batch = data.batch(t)
        s, c, grad = grad_fn(params, batch["tokens"][:kept],
                             batch["labels"][:kept])
        losses.append(float(s) / float(c))
        if t == 0:
            grad_norms = leaf_norms(grad)
        g, h = efbv(grad, h)
        if t == 0:
            g0_norms = leaf_norms(g)
        params, m, v = adamw(params, m, v, g, learning_rate(opt, t),
                             float(t + 1))
    change = host_change_norms(p0, jax.device_get(params))
    state = [leaf_norms(m), leaf_norms(v), leaf_norms(h)]
    del params, m, v, h
    return Reference(Readings(losses, g0_norms, change, state), grad_norms)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _leaf_gaps(got: List[float], ref: List[float], use: List[bool]) -> List[float]:
    """|got - ref| / max(ref, median of ref) for each used leaf, -1 for the
    others."""
    med = float(np.median([r for r, u in zip(ref, use) if u]))
    return [abs(a - b) / max(b, med) if u else -1.0
            for a, b, u in zip(got, ref, use)]


def _pairs(run: Readings, ref: Reference):
    """(number, tree, the run's norms, the reference's, leaves used) for
    every leaf-wise comparison."""
    med_grad = float(np.median(ref.grad_norms))
    moved = [g >= NOUGHT * med_grad for g in ref.grad_norms]
    every = [True] * len(ref.grad_norms)
    r = ref.readings
    yield "grad_gap", "g0", run.g0_norms, r.g0_norms, every
    yield "change_gap", "change", run.change_norms, r.change_norms, moved
    for tree, a, b in zip(("m", "v", "h"), run.state_norms, r.state_norms):
        yield "state_gap", tree, a, b, every


def compare(run: Readings, ref: Reference) -> Dict[str, float]:
    """The four numbers that decide ``correct``, from a run's readings
    and the reference's."""
    out = {"loss_gap": max(abs(a - b) / abs(b)
                           for a, b in zip(run.losses, ref.readings.losses))}
    for name, _, got, want, use in _pairs(run, ref):
        out[name] = max(out.get(name, -1.0), max(_leaf_gaps(got, want, use)))
    return out


def worst_leaves(run: Readings, ref: Reference, names: List[str]) -> Dict[str, dict]:
    """For each leaf-wise number, the leaf that sets it: its tree, its name
    (``names``, in flatten order), the run's norm, the reference's and the
    median of the reference's."""
    out = {}
    for name, tree, got, want, use in _pairs(run, ref):
        gaps = _leaf_gaps(got, want, use)
        i = int(np.argmax(gaps))
        if name not in out or gaps[i] > out[name]["gap"]:
            out[name] = {"gap": gaps[i], "tree": tree, "leaf": names[i],
                         "run": got[i], "reference": want[i],
                         "median": float(np.median(
                             [w for w, u in zip(want, use) if u]))}
    return out


def leaf_names(model, cfg: dict) -> List[str]:
    """The configuration's parameter leaves by path, in flatten order."""
    shapes = jax.eval_shape(lambda k: model.init(cfg, k), jax.random.key(0))
    return [jax.tree_util.keystr(p, simple=True, separator="/")
            for p, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]]
