"""Plain reference of a Mamba-2 language model (SSD, arXiv:2405.21060).

Pre-norm blocks: one input projection to (z, x, B, C, dt), a depthwise
causal convolution with SiLU over (x, B, C), the selective state-space
scan with scalar decay per head and one group of B and C shared by the
heads, a skip D, a gated RMSNorm over y * SiLU(z), the output projection.
Final RMSNorm, a head tied to the embedding.  The scan is the paper's
chunked form (its ``ssd_minimal_discrete``, segment sums inside chunks,
a recurrence across them) in float32, at the published chunk length,
which need not be the program's: the result does not depend on it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.models.dense_gqa import rmsnorm


def _dims(cfg):
    d = cfg["hidden_size"]
    di = cfg["expand"] * d
    return (d, di, cfg["state_size"], di // cfg["head_dim"], cfg["head_dim"],
            cfg["conv_kernel"], cfg["vocab_size"], cfg["num_hidden_layers"])


def init(cfg, key):
    """The configuration's initialisation from one key: projections
    N(0, 1) / sqrt(fan_in) (dt's N(0, 0.02^2)), the convolution
    N(0, 1) * 0.5 / sqrt(kernel), dt_bias = softplus^-1(dt) with log dt
    uniform in [log 1e-3, log 1e-1], A_log = log(1..heads), D and norms 1,
    embedding N(0, 0.02^2).  Keys: split(key, 8)[0] split over the layers,
    each layer's key into 8 (z, x, B, C, dt, dt_bias, conv, out);
    split(key, 8)[1] is the embedding."""
    d, di, st, nh, hp, kc, V, L = _dims(cfg)
    keys = jax.random.split(key, 8)
    conv_ch = di + 2 * st

    def normal(k, shape, scale):
        return jax.random.normal(k, shape) * scale

    def layer(k):
        ks = jax.random.split(k, 8)
        log_dt = jax.random.uniform(ks[5], (nh,), minval=math.log(1e-3),
                                    maxval=math.log(1e-1))
        return {
            "mamba": {
                "wz": normal(ks[0], (d, di), 1.0 / math.sqrt(d)),
                "wx": normal(ks[1], (d, di), 1.0 / math.sqrt(d)),
                "wB": normal(ks[2], (d, st), 1.0 / math.sqrt(d)),
                "wC": normal(ks[3], (d, st), 1.0 / math.sqrt(d)),
                "wdt": normal(ks[4], (d, nh), 0.02),
                "dt_bias": jnp.log(jnp.expm1(jnp.exp(log_dt))),
                "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                "D": jnp.ones((nh,), jnp.float32),
                "conv_w": normal(ks[6], (kc, conv_ch), 0.5 / math.sqrt(kc)),
                "conv_b": jnp.zeros((conv_ch,), jnp.float32),
                "norm_w": jnp.ones((di,), jnp.float32),
                "wo": normal(ks[7], (di, d), 1.0 / math.sqrt(di)),
            },
            "ln": jnp.ones((d,), jnp.float32),
        }

    return {
        "embed": normal(keys[1], (V, d), 0.02),
        "layers": jax.vmap(layer)(jax.random.split(keys[0], L)),
        "final_norm": jnp.ones((d,), jnp.float32),
    }


def segsum(x):
    """out[..., i, j] = x[..., j+1] + ... + x[..., i] for i >= j, else -inf."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool), 0), out, -jnp.inf)


def ssd(num, X, A, B, C, chunk: int):
    """X: (b, l, h, p) = x * dt; A: (b, l, h) = dt * A; B, C: (b, l, n).
    Returns y: (b, l, h, p)."""
    b, l, h, p = X.shape
    n = B.shape[-1]
    c = l // chunk
    X = X.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, n)
    C = C.reshape(b, c, chunk, n)
    A = jnp.moveaxis(A.reshape(b, c, chunk, h), -1, 1)            # b h c l
    A_cum = jnp.cumsum(A, axis=-1)
    # 1. within chunks
    Lmat = jnp.exp(segsum(A))                                     # b h c l s
    CB = num.einsum("bcln,bcsn->bcls", C, B)
    Y_diag = num.einsum("bhcls,bcshp->bclhp", CB[:, None] * Lmat, X)
    # 2. each chunk's own end state
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)               # b h c l
    states = num.einsum("bcln,bclhp->bchpn",
                        B, X * jnp.moveaxis(decay_states, 1, -1)[..., None])
    # 3. across chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0),
                                                            (1, 0)))))
    states = num.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    # 4. states to outputs
    Y_off = num.einsum("bcln,bchpn->bclhp", C, states) \
        * jnp.moveaxis(jnp.exp(A_cum), 1, -1)[..., None]
    return (Y_diag + Y_off).reshape(b, l, h, p)


def mixer(cfg, num, p, x):
    d, di, st, nh, hp, kc, _, _ = _dims(cfg)
    b, l, _ = x.shape
    z = num.mm(x, p["wz"])
    xbc = jnp.concatenate([num.mm(x, p["wx"]), num.mm(x, p["wB"]),
                           num.mm(x, p["wC"])], -1)
    dt = jax.nn.softplus(num.mm(x, p["wdt"]) + p["dt_bias"])      # b l h
    # depthwise causal convolution: tap kc-1 sees the current position
    pad = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + l] * p["conv_w"][j] for j in range(kc))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, B, C = jnp.split(xbc, [di, di + st], axis=-1)
    xs = xs.reshape(b, l, nh, hp)
    A = -jnp.exp(p["A_log"])
    y = ssd(num, xs * dt[..., None], dt * A, B, C,
            cfg.get("reference_chunk_size", cfg["chunk_size"]))
    y = (y + p["D"][:, None] * xs).reshape(b, l, di)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"], cfg["rms_norm_eps"])
    return num.mm(y, p["wo"])


def loss_sum(cfg, num, params, tokens, labels):
    """(sum of next-token cross-entropies over labels >= 0, their count)
    for a block of rows."""
    h = params["embed"][tokens]

    def body(h, lp):
        x = rmsnorm(h, lp["ln"], cfg["rms_norm_eps"])
        return h + mixer(cfg, num, lp["mamba"], x), None

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
    parameter that enters a matrix product (the tied head counted once),
    and for the scan in its recurrent form 3 x (5 per state element:
    decay, input outer product and add, output product) per layer, plus
    3 x 2 per convolution tap and channel.  Recomputation is not counted."""
    d, di, st, nh, hp, kc, V, L = _dims(cfg)
    matmul_params = L * (d * (2 * di + 2 * st + nh) + di * d) + V * d
    scan = L * 3 * (5 * nh * st * hp + 2 * kc * (di + 2 * st))
    return 6.0 * matmul_params + float(scan)
