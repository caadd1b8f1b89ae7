"""Plain reference of a Qwen2-style dense decoder (arXiv:2407.10671).

Pre-norm blocks of grouped-query attention (QKV bias, rotary positions by
halves, causal softmax) and a SwiGLU MLP, RMSNorm, a head tied to the
embedding.  Everything is float32; contractions go through a
:class:`chipbench.numerics.Numerics`.  Nothing here comes from the program
under test: the parameter tree's layout (leaves stacked over layers) and the
initialisation are the configuration's, written out below.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def init(cfg, key):
    """The configuration's initialisation from one key: every matrix
    N(0, 1) / sqrt(fan_in), the embedding N(0, 0.02^2), biases 0, norms 1.
    Keys: split(key, 8)[0] is split over the layers, each layer's key into
    (attention, mlp), those into 4 and 3; split(key, 8)[1] is the embedding."""
    d, H, K, hd, ff, V, L = _dims(cfg)
    keys = jax.random.split(key, 8)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape) * scale

    def layer(k):
        k_attn, k_mlp = jax.random.split(k)
        a = jax.random.split(k_attn, 4)
        m = jax.random.split(k_mlp, 3)
        return {
            "attn": {
                "wq": normal(a[0], (d, H * hd), 1.0 / math.sqrt(d)),
                "wk": normal(a[1], (d, K * hd), 1.0 / math.sqrt(d)),
                "wv": normal(a[2], (d, K * hd), 1.0 / math.sqrt(d)),
                "wo": normal(a[3], (H * hd, d), 1.0 / math.sqrt(H * hd)),
                "bq": jnp.zeros((H * hd,), jnp.float32),
                "bk": jnp.zeros((K * hd,), jnp.float32),
                "bv": jnp.zeros((K * hd,), jnp.float32),
            },
            "mlp": {
                "wg": normal(m[0], (d, ff), 1.0 / math.sqrt(d)),
                "wu": normal(m[1], (d, ff), 1.0 / math.sqrt(d)),
                "wd": normal(m[2], (ff, d), 1.0 / math.sqrt(ff)),
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


def block(cfg, num, h, lp):
    d, H, K, hd, _, _, _ = _dims(cfg)
    B, S, _ = h.shape
    a = lp["attn"]
    x = rmsnorm(h, lp["ln1"], cfg["rms_norm_eps"])
    q = (num.mm(x, a["wq"]) + a["bq"]).reshape(B, S, K, H // K, hd)
    k = (num.mm(x, a["wk"]) + a["bk"]).reshape(B, S, K, hd)
    v = (num.mm(x, a["wv"]) + a["bv"]).reshape(B, S, K, hd)
    q = rope(q.reshape(B, S, H, hd), cfg["rope_theta"]).reshape(B, S, K, H // K, hd)
    k = rope(k, cfg["rope_theta"])
    s = num.einsum("bqkgh,bskh->bkgqs", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = num.einsum("bkgqs,bskh->bqkgh", p, v).reshape(B, S, H * hd)
    h = h + num.mm(o, a["wo"])
    x = rmsnorm(h, lp["ln2"], cfg["rms_norm_eps"])
    m = lp["mlp"]
    return h + num.mm(jax.nn.silu(num.mm(x, m["wg"])) * num.mm(x, m["wu"]),
                      m["wd"])


def loss_sum(cfg, num, params, tokens, labels):
    """(sum of next-token cross-entropies over labels >= 0, their count)
    for a block of rows."""
    h = params["embed"][tokens]

    def body(h, lp):
        return block(cfg, num, h, lp), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, params["layers"])
    h = rmsnorm(h, params["final_norm"], cfg["rms_norm_eps"])
    logits = num.einsum("bsd,vd->bsv", h, params["embed"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask), jnp.sum(mask)


def flops_per_token(cfg, seq: int) -> float:
    """Forward and backward operations one trained token needs: 6 per
    parameter that enters a matrix product (the tied head counted once, as
    the product it is), and 6 * layers * heads * hd * seq for causal scores
    and values (half of the square).  Recomputation is not counted."""
    d, H, K, hd, ff, V, L = _dims(cfg)
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    matmul_params = L * per_layer + V * d
    return 6.0 * matmul_params + 6.0 * L * H * hd * seq
