"""Plain reference of IBM Granite 3.0's MoE decoder (``granitemoe``) on one
chip's share of its experts.

Pre-norm blocks of grouped-query attention (rotary positions by halves,
causal softmax, scores times ``attention_multiplier``) and a mixture of
experts, RMSNorm, a head tied to the embedding; the embedding times
``embedding_multiplier``, each branch times ``residual_multiplier`` before
its residual add, the logits over ``logits_scaling``.

The MoE: the router spans all ``num_experts_total`` experts; each token
takes its top ``num_experts_per_tok`` logits, gates by a softmax over those.
This chip holds experts 0..``num_local_experts``-1.  Each held expert's
SwiGLU is computed densely on every token and weighted by the token's gate
for it (0 where the token did not pick it): the held experts' part of the
layer's result, which is what the layer carries on.  The load-balancing
loss, E * sum_e (c_e / T) * mean_t p_te over all experts and all k choices
(c_e the assignments to e, p the softmax over all logits), is averaged over
the layers and added at ``router_aux_loss_coef``.

Everything is float32; contractions go through a
:class:`chipbench.numerics.Numerics`, the router's too.  Nothing here comes
from the program under test: the parameter tree's layout (leaves stacked
over layers) and the initialisation are the configuration's, written out
below.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["num_experts_total"],
            cfg["num_local_experts"], cfg["num_experts_per_tok"])


def init(cfg, key):
    """The configuration's initialisation from one key: attention matrices
    N(0, 1) / sqrt(fan_in), each held expert's gate and up N(0, 1) / sqrt(d)
    and down N(0, 1) / sqrt(ff), the router and the embedding N(0, 0.02^2),
    norms 1.  Keys: split(key, 8)[0] is split over the layers, each layer's
    key into (attention, moe), those into 4 each (q, k, v, o; router, gate,
    up, down); split(key, 8)[1] is the embedding."""
    d, H, K, hd, ff, V, L, E, held, _ = _dims(cfg)
    keys = jax.random.split(key, 8)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape) * scale

    def layer(k):
        k_attn, k_moe = jax.random.split(k)
        a = jax.random.split(k_attn, 4)
        m = jax.random.split(k_moe, 4)
        return {
            "attn": {
                "wq": normal(a[0], (d, H * hd), 1.0 / math.sqrt(d)),
                "wk": normal(a[1], (d, K * hd), 1.0 / math.sqrt(d)),
                "wv": normal(a[2], (d, K * hd), 1.0 / math.sqrt(d)),
                "wo": normal(a[3], (H * hd, d), 1.0 / math.sqrt(H * hd)),
            },
            "moe": {
                "router": normal(m[0], (d, E), 0.02),
                "wg": normal(m[1], (held, d, ff), 1.0 / math.sqrt(d)),
                "wu": normal(m[2], (held, d, ff), 1.0 / math.sqrt(d)),
                "wd": normal(m[3], (held, ff, d), 1.0 / math.sqrt(ff)),
            },
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
        }

    return {
        "embed": normal(keys[1], (V, d), 0.02),
        "layers": jax.vmap(layer)(jax.random.split(keys[0], L)),
        "final_norm": jnp.ones((d,), jnp.float32),
    }


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary positions by halves.  x: (B, S, heads, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg, num, x, a):
    d, H, K, hd = _dims(cfg)[:4]
    B, S, _ = x.shape
    q = num.mm(x, a["wq"]).reshape(B, S, H, hd)
    k = rope(num.mm(x, a["wk"]).reshape(B, S, K, hd), cfg["rope_theta"])
    v = num.mm(x, a["wv"]).reshape(B, S, K, hd)
    q = rope(q, cfg["rope_theta"]).reshape(B, S, K, H // K, hd)
    s = num.einsum("bqkgh,bskh->bkgqs", q, k) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = num.einsum("bkgqs,bskh->bqkgh", p, v).reshape(B, S, H * hd)
    return num.mm(o, a["wo"])


def experts(cfg, num, x, m):
    """(the held experts' part of the MoE output, the layer's
    load-balancing loss).  x: (B, S, d)."""
    E, held, k = _dims(cfg)[7:]
    logits = num.mm(x, m["router"])                            # (B, S, E)
    top, ids = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)                       # (B, S, k)
    picked = jax.nn.one_hot(ids, E)                            # (B, S, k, E)
    weight = jnp.einsum("bsk,bske->bse", gates, picked)[..., :held]
    share = jnp.mean(jnp.sum(picked, axis=2), axis=(0, 1))     # c_e / T
    aux = E * jnp.sum(share * jnp.mean(jax.nn.softmax(logits, -1), axis=(0, 1)))
    g = jax.nn.silu(num.einsum("bsd,edf->bsef", x, m["wg"]))
    u = num.einsum("bsd,edf->bsef", x, m["wu"])
    y = num.einsum("bsef,efd->bsed", g * u, m["wd"])           # (B, S, held, d)
    return jnp.einsum("bse,bsed->bsd", weight, y), aux


def block(cfg, num, h, lp):
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    h = h + r * attention(cfg, num, rmsnorm(h, lp["ln1"], eps), lp["attn"])
    y, aux = experts(cfg, num, rmsnorm(h, lp["ln2"], eps), lp["moe"])
    return h + r * y, aux


def loss_sum(cfg, num, params, tokens, labels):
    """(sum of next-token cross-entropies over labels >= 0 plus their count
    times the weighted load-balancing loss, their count) for a block of
    rows: over one row, its mean is the program's loss."""
    h = params["embed"][tokens] * cfg["embedding_multiplier"]

    def body(h, lp):
        return block(cfg, num, h, lp)

    h, aux = jax.lax.scan(jax.checkpoint(body), h, params["layers"])
    h = rmsnorm(h, params["final_norm"], cfg["rms_norm_eps"])
    logits = num.einsum("bsd,vd->bsv", h, params["embed"]) / cfg["logits_scaling"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    count = jnp.sum(mask)
    return (jnp.sum((lse - gold) * mask)
            + count * cfg["router_aux_loss_coef"] * jnp.mean(aux)), count


def flops_per_token(cfg, seq: int) -> float:
    """Forward and backward operations one trained token needs: 6 per
    parameter that enters a matrix product, the held experts counted at
    their expected load, k * held / E experts a token (1.6 of 8 here); the
    router and the tied head once; and 6 * layers * heads * hd * seq for
    causal scores and values (half of the square).  Recomputation is not
    counted."""
    d, H, K, hd, ff, V, L, E, held, k = _dims(cfg)
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    per_layer = attn + d * E + (k * held / E) * 3 * d * ff
    return 6.0 * (L * per_layer + V * d) + 6.0 * L * H * hd * seq
