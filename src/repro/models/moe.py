"""Mixture-of-Experts layer holding a share of its experts: a router over
all experts, dropless routing sorted by expert, grouped products over the
experts held here, and the load-balancing loss.

A layer whose experts are divided over several chips (expert parallelism)
holds ``held`` of its ``n_experts`` here, ids 0..held-1 of the router's
outputs; with ``held == n_experts`` it is the whole layer.  Expert weights
are stacked on a leading axis of the held experts.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.gmm import gmm
from repro.models.layers import _init, auto_spec

Array = jax.Array


def moe_init(key, d: int, ff: int, n_experts: int,
             held: int = 0) -> Tuple[Dict, Dict]:
    """The router over all ``n_experts`` and the ``held`` experts of this
    chip (0: all of them): matrices N(0, 1) / sqrt(fan_in), the router
    N(0, 0.02^2)."""
    held = held or n_experts
    ks = jax.random.split(key, 4)
    params = {
        "router": _init(ks[0], (d, n_experts), scale=0.02),
        "wg": _init(ks[1], (held, d, ff), scale=1.0 / math.sqrt(d)),
        "wu": _init(ks[2], (held, d, ff), scale=1.0 / math.sqrt(d)),
        "wd": _init(ks[3], (held, ff, d), scale=1.0 / math.sqrt(ff)),
    }
    # stored over 'model' where the held experts divide the production axis,
    # else replicated; the grouped products (kernels/gmm.py) run each model
    # shard's own experts wherever the mesh's 'model' axis divides them, so
    # stored shards are never gathered
    specs = {
        "router": P(None, None),
        "wg": auto_spec((held, d, ff), prefer=(0,)),
        "wu": auto_spec((held, d, ff), prefer=(0,)),
        "wd": auto_spec((held, ff, d), prefer=(0,)),
    }
    return params, specs


EXPERT_LEAVES = ("wg", "wu", "wd")


def _is_moe_subtree(node) -> bool:
    return (isinstance(node, dict)
            and "router" in node
            and all(k in node for k in EXPERT_LEAVES))


def expert_activity_mask(moe_grads: Dict) -> Array:
    """Which experts this round's gradients actually touched.

    The grouped products give an expert with no rows an exactly-zero weight
    gradient (see :mod:`repro.kernels.gmm`), so an unrouted expert's
    wg/wu/wd gradient slab is exactly zero -- its activity is readable off
    the gradients with no routing side-channel.  Returns a boolean mask of
    shape ``(..., E)``, E the held experts (leading dims = any stacked-layer
    axes of the expert leaves, e.g. ``(L, E)`` for a stacked transformer):
    True where ANY of the three expert slabs carries a nonzero entry.
    Router gradients are dense (every token differentiates through the
    softmax) and do not enter the mask."""
    masks = []
    for name in EXPERT_LEAVES:
        g = moe_grads[name]
        # (..., E, a, b) -> (..., E): any nonzero in the per-expert slab
        masks.append(jnp.any(g != 0, axis=(-2, -1)))
    return jnp.logical_or(jnp.logical_or(masks[0], masks[1]), masks[2])


def zero_inactive_expert_grads(grads, mask=None):
    """Zero the wg/wu/wd gradient slabs of inactive experts, worker-side.

    This is the enforcement half of the expert-sparsity contract the
    compressed wire relies on (docs/finetuning.md#expert-sparsity): leaves
    under any MoE subtree keep only the slabs of experts in ``mask``
    (default: :func:`expert_activity_mask` derived from the gradients
    themselves, under which this is mathematically the identity -- the
    dispatch already produced exact zeros).  Composed with a top-k leaf
    codec on the expert leaves, the masked gradient's payload carries only
    routed-expert entries.  Non-MoE subtrees pass through untouched."""
    def walk(node):
        if _is_moe_subtree(node):
            m = expert_activity_mask(node) if mask is None else mask
            out = dict(node)
            for name in EXPERT_LEAVES:
                g = node[name]
                out[name] = g * m[..., None, None].astype(g.dtype)
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(grads)


def fixed_routing_params(params):
    """Pin the router: zero every MoE router leaf, so all logits tie and
    ``jax.lax.top_k`` deterministically routes every token to experts
    ``(0, .., k-1)`` (ties break by lowest index).  The deterministic-routing
    regime the expert-sparsity wire tests pin oracle == shard_map under."""
    def walk(node):
        if _is_moe_subtree(node):
            out = dict(node)
            out["router"] = jnp.zeros_like(node["router"])
            return out
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _permute(x: Array, rows: Array, inverse: Array, k: int) -> Array:
    """``x[rows // k]``: row i of the result is token ``rows[i] // k`` of x,
    where ``rows`` is a permutation of the T * k (token, choice) assignments
    and ``inverse`` its inverse.  The gradient gathers by ``inverse`` and sums
    each token's k choices, so neither direction scatters."""
    return x[rows // k]


def _permute_fwd(x, rows, inverse, k):
    return x[rows // k], (rows, inverse)


def _permute_bwd(k, res, g):
    rows, inverse = res
    return (jnp.sum(g[inverse].reshape(-1, k, g.shape[-1]), axis=1),
            None, None)


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _unpermute(y: Array, rows: Array, inverse: Array) -> Array:
    """``y[inverse]``: the sorted rows back in (token, choice) order; the
    gradient gathers by ``rows``."""
    return y[inverse]


def _unpermute_fwd(y, rows, inverse):
    return y[inverse], (rows, inverse)


def _unpermute_bwd(res, g):
    rows, _ = res
    return g[rows], None, None


_unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


def moe_apply(p, x: Array, *, n_experts: int, k: int
              ) -> Tuple[Array, Dict[str, Array]]:
    """x: (B, S, d) -> (this chip's part of the layer's output (B, S, d),
    routing statistics).

    The router keeps all ``n_experts`` outputs: float32 logits, the top k,
    gates by a softmax over those k.  The layer holds the first
    ``p["wg"].shape[0]`` experts (ids 0..held-1) and computes only their
    SwiGLU, each weighted by its gate: the experts held on other chips add
    the rest of the sum there.  Routing is dropless: the T * k assignments
    are sorted by expert id (those of experts not held last) and the held
    experts run as grouped products over their own rows (``kernels.gmm``),
    however unevenly the tokens fall.

    Statistics: ``aux``, the load-balancing loss over every expert and all
    k choices, E * sum_e (c_e / T) * mean_t p_te with c_e the assignments to
    expert e and p the softmax over all E logits (k at perfect balance);
    ``held_rows``, the assignments that reached a held expert; ``load``, the
    busiest held expert's rows over the held experts' mean (0 with none).
    """
    B, S, d = x.shape
    T, held = B * S, p["wg"].shape[0]
    xt = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)       # (T, E)
        top, ids = jax.lax.top_k(logits, k)                          # (T, k)
        gates = jax.nn.softmax(top, axis=-1)
        flat = ids.reshape(-1)
        counts = jnp.sum(jax.nn.one_hot(flat, n_experts, dtype=jnp.int32),
                         axis=0)                                     # (E,)
        probs = jax.nn.softmax(logits, axis=-1)
        aux = n_experts * jnp.sum(counts.astype(jnp.float32) / T
                                  * jnp.mean(probs, axis=0))
        rows = jnp.argsort(jnp.minimum(flat, held), stable=True)
        inverse = jnp.argsort(rows)
        sizes = jnp.concatenate([counts[:held],
                                 (T * k - jnp.sum(counts[:held]))[None]])
        xs = _permute(xt, rows, inverse, k)                          # (T*k, d)
    with jax.named_scope("moe.experts"):
        dt = x.dtype
        h = jax.nn.silu(gmm(xs, p["wg"].astype(dt), sizes)) \
            * gmm(xs, p["wu"].astype(dt), sizes)
        ys = gmm(h, p["wd"].astype(dt), sizes)
    with jax.named_scope("moe.route"):
        yk = _unpermute(ys, rows, inverse).reshape(T, k, d)
        w = jnp.where(ids < held, gates, 0.0)
        out = jnp.sum(yk.astype(jnp.float32) * w[..., None], axis=1).astype(dt)
    held_rows = jnp.sum(counts[:held])
    load = held * jnp.max(counts[:held]) / jnp.maximum(held_rows, 1)
    return out.reshape(B, S, d), {"aux": aux, "held_rows": held_rows,
                                  "load": load}
