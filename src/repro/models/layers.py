"""Shared transformer building blocks: norms, RoPE / M-RoPE, GQA attention
(QKV bias, sliding window, KV cache), SwiGLU / GELU MLPs.

Parameters are plain dict pytrees; initializers return (params, specs) where
specs are PartitionSpecs over the 'model' mesh axis chosen by
:func:`auto_spec` (first divisible preferred dim wins, else replicate --
handles head counts like 36 or expert counts like 40 that don't divide 16).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


# --------------------------------------------------------------------------
# sharding helper
# --------------------------------------------------------------------------

MODEL_AXIS_SIZE = 16  # production 'model' axis; smoke meshes divide it


def auto_spec(shape: Sequence[int], prefer: Sequence[int],
              axis_size: int = MODEL_AXIS_SIZE) -> P:
    """PartitionSpec putting 'model' on the first preferred dim divisible by
    the model-axis size; replicated otherwise."""
    for dim in prefer:
        if shape[dim] % axis_size == 0:
            spec = [None] * len(shape)
            spec[dim] = "model"
            return P(*spec)
    return P(*([None] * len(shape)))


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm_init(d: int) -> Tuple[Array, P]:
    return jnp.ones((d,), jnp.float32), P(None)


def rmsnorm(x: Array, w: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w).astype(dt)


# --------------------------------------------------------------------------
# RoPE and M-RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, hd); positions: (B, S) int."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions3: Array, theta: float,
                sections: Sequence[int]) -> Array:
    """Multimodal RoPE (Qwen2-VL): positions3 (3, B, S) = (t, h, w) ids;
    frequency channels are split into len(sections) groups, each rotated by
    its own position stream.  sum(sections) == hd // 2."""
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # build per-channel positions by section
    chunks = []
    start = 0
    for sec, pos in zip(sections, positions3):
        chunks.append(pos[..., None].astype(jnp.float32) * freqs[start:start + sec])
        start += sec
    angles = jnp.concatenate(chunks, axis=-1)  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def _head_spec(n_heads: int, hd: int, dim: int, policy: str,
               axis_size: int = MODEL_AXIS_SIZE) -> P:
    """Attention projection sharding policy (§Perf iterations 1/4).

    When n_heads divides the model axis, flat sharding IS head-aligned and
    everyone agrees.  When it doesn't (phi3: 40, granite: 24, minicpm: 36,
    qwen2: 14), the measured tradeoff is:

      'flat'      -- shard the flat H*hd dim anyway: sharded attention compute
                     but GSPMD repartitions heads and all-reduces S x S score
                     tensors (+wire).  Wins when the pair is memory-bound
                     (phi3 train: max-term 63.6s vs 109s replicated).
      'replicate' -- replicate the (small) attention weights: no score
                     collectives at all, but attention compute/memory runs on
                     every model shard.  Wins when the pair is collective-
                     bound (granite prefill: max-term 124s vs 199s flat).
    """
    aligned = n_heads % axis_size == 0
    if aligned or policy == "flat":
        if (n_heads * hd) % axis_size == 0:
            return P(None, "model") if dim == 1 else P("model", None)
        return P(None, None)
    return P(None, None)  # replicate


def attention_init(key, d: int, n_heads: int, n_kv: int, hd: int,
                   qkv_bias: bool, shard_policy: str = "flat"
                   ) -> Tuple[Dict[str, Array], Dict[str, P]]:
    ks = jax.random.split(key, 4)
    params = {
        "wq": _init(ks[0], (d, n_heads * hd)),
        "wk": _init(ks[1], (d, n_kv * hd)),
        "wv": _init(ks[2], (d, n_kv * hd)),
        "wo": _init(ks[3], (n_heads * hd, d), scale=1.0 / math.sqrt(n_heads * hd)),
    }
    specs = {
        "wq": _head_spec(n_heads, hd, 1, shard_policy),
        "wk": _head_spec(n_kv, hd, 1, shard_policy),
        "wv": _head_spec(n_kv, hd, 1, shard_policy),
        "wo": _head_spec(n_heads, hd, 0, shard_policy),
    }
    if qkv_bias:
        params.update({
            "bq": jnp.zeros((n_heads * hd,)),
            "bk": jnp.zeros((n_kv * hd,)),
            "bv": jnp.zeros((n_kv * hd,)),
        })

        def bias_spec(nh):
            s = _head_spec(nh, hd, 1, shard_policy)
            return P("model") if s[1] == "model" else P(None)

        specs.update({
            "bq": bias_spec(n_heads),
            "bk": bias_spec(n_kv),
            "bv": bias_spec(n_kv),
        })
    return params, specs


def _project_qkv(p, x, n_heads, n_kv, hd):
    B, S, _ = x.shape
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (q.reshape(B, S, n_heads, hd), k.reshape(B, S, n_kv, hd),
            v.reshape(B, S, n_kv, hd))


def _sdpa(q: Array, k: Array, v: Array, mask: Optional[Array],
          scale: Optional[float] = None) -> Array:
    """Grouped scaled-dot-product attention, scores times ``scale`` (None:
    over sqrt(hd)).  q: (B, Sq, H, hd); k, v: (B, Sk, K, hd); H = K * G."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k)
    scores = scores / math.sqrt(hd) if scale is None else scores * scale
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def _sdpa_chunked(q: Array, k: Array, v: Array, *, window: int = 0,
                  chunk: int = 1024, scale: Optional[float] = None) -> Array:
    """Flash-style attention: lax.scan over KV chunks with an online softmax.

    §Perf iteration 3: the direct SDPA materializes (B, K, G, S, S) f32 score
    tensors in HBM (the dominant memory term on phi3/minitron train+prefill);
    this keeps the working set at (B, K, G, S, chunk) and lets XLA fuse the
    rescale chain.  Causal-only (training/prefill path).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    nc = -(-k.shape[1] // chunk)
    Sk = nc * chunk
    kp = jnp.pad(k, ((0, 0), (0, Sk - k.shape[1]), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk - v.shape[1]), (0, 0), (0, 0)))
    qg = q.reshape(B, Sq, K, G, hd)
    qg = (qg / math.sqrt(hd) if scale is None else qg * scale).astype(q.dtype)
    kc = kp.reshape(B, nc, chunk, K, hd)
    vc = vp.reshape(B, nc, chunk, K, hd)
    qi = jnp.arange(Sq)

    def body(carry, xs):
        m, l, acc = carry           # (B,K,G,Sq), (B,K,G,Sq), (B,K,G,Sq,hd)
        kj, vj, j = xs              # (B,chunk,K,hd) x2, chunk index
        s = jnp.einsum("bqkgh,bckh->bkgqc", qg, kj).astype(jnp.float32)
        kidx = j * chunk + jnp.arange(chunk)
        valid = kidx[None, :] <= qi[:, None]
        if window:
            valid &= kidx[None, :] > qi[:, None] - window
        s = jnp.where(valid[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bkgqc,bckh->bkgqh", p.astype(q.dtype), vj).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, K, G, Sq), -1e30, jnp.float32) + qg.reshape(-1)[0].astype(jnp.float32) * 0
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32) + qg.reshape(-1)[0].astype(jnp.float32) * 0
    a0 = jnp.zeros((B, K, G, Sq, hd), jnp.float32) + qg.reshape(-1)[0].astype(jnp.float32) * 0
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out.astype(q.dtype), -2, 1).reshape(B, Sq, H, hd)


def causal_mask(Sq: int, Sk: int, window: int = 0, offset: int = 0) -> Array:
    """(1, 1, 1, Sq, Sk) boolean mask.  offset = Sk - Sq for cached decode."""
    qi = jnp.arange(Sq)[:, None] + offset
    ki = jnp.arange(Sk)[None, :]
    m = ki <= qi
    if window:
        m = m & (ki > qi - window)
    return m[None, None, None]


def attention(p, x: Array, *, n_heads: int, n_kv: int, hd: int,
              positions: Array, theta: float, window: int = 0,
              mrope_sections: Sequence[int] = (), causal: bool = True,
              kv: Optional[Tuple[Array, Array]] = None,
              impl: str = "direct", scale: Optional[float] = None) -> Array:
    """Full-sequence attention (training / prefill).

    kv: optional externally-provided (k, v) for cross-attention.
    impl: 'direct' (materialized scores) or 'chunked' (online softmax).
    scale: the scores' factor; None is 1 / sqrt(hd)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv, hd)
    if kv is not None:
        k, v = kv  # cross-attention: encoder keys/values (already projected)
    if mrope_sections:
        q = apply_mrope(q, positions, theta, mrope_sections)
        if kv is None:
            k = apply_mrope(k, positions, theta, mrope_sections)
    elif theta > 0 and kv is None:
        pos2 = positions if positions.ndim == 2 else positions[0]
        q = apply_rope(q, pos2, theta)
        k = apply_rope(k, pos2, theta)
    if impl == "chunked" and causal and kv is None:
        out = _sdpa_chunked(q, k, v, window=window,
                            chunk=min(1024, k.shape[1]), scale=scale)
    else:
        mask = causal_mask(S, k.shape[1], window) if causal else None
        out = _sdpa(q, k, v, mask, scale)
    return out.reshape(B, S, n_heads * hd) @ p["wo"].astype(x.dtype)


def attention_decode(p, x: Array, cache_k: Array, cache_v: Array, pos: Array,
                     *, n_heads: int, n_kv: int, hd: int, theta: float,
                     window: int = 0, mrope_sections: Sequence[int] = (),
                     scale: Optional[float] = None
                     ) -> Tuple[Array, Array, Array]:
    """One-token decode with a KV cache.

    x: (B, 1, d); cache_k/v: (B, C, K, hd) where C = max context (or window);
    pos: scalar int32 -- the absolute position of the new token.
    Returns (out (B,1,d'), new_cache_k, new_cache_v)."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, x, n_heads, n_kv, hd)
    posb = jnp.full((B, 1), pos, jnp.int32)
    if mrope_sections:
        pos3 = jnp.broadcast_to(pos, (3,))[:, None, None] * jnp.ones((3, B, 1), jnp.int32)
        q = apply_mrope(q, pos3, theta, mrope_sections)
        k = apply_mrope(k, pos3, theta, mrope_sections)
    elif theta > 0:
        q = apply_rope(q, posb, theta)
        k = apply_rope(k, posb, theta)
    C = cache_k.shape[1]
    slot = pos % C if window else jnp.minimum(pos, C - 1)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                           (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                           (0, slot, 0, 0))
    ki = jnp.arange(C)
    if window:
        # ring buffer: before it is warm only slots <= pos are live; after
        # wrap-around every slot holds one of the last C tokens.
        valid = (ki <= pos) | (pos >= C)
    else:
        valid = ki <= pos
    mask = valid[None, None, None, None, :]  # (1,1,1,1,C)
    out = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype), mask,
                scale)
    out = out.reshape(B, 1, n_heads * hd) @ p["wo"].astype(x.dtype)
    return out, cache_k, cache_v


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int) -> Tuple[Dict[str, Array], Dict[str, P]]:
    ks = jax.random.split(key, 3)
    params = {
        "wg": _init(ks[0], (d, ff)),
        "wu": _init(ks[1], (d, ff)),
        "wd": _init(ks[2], (ff, d), scale=1.0 / math.sqrt(ff)),
    }
    specs = {
        "wg": auto_spec((d, ff), prefer=(1,)),
        "wu": auto_spec((d, ff), prefer=(1,)),
        "wd": auto_spec((ff, d), prefer=(0,)),
    }
    return params, specs


def swiglu(p, x: Array) -> Array:
    g = jax.nn.silu(x @ p["wg"].astype(x.dtype))
    u = x @ p["wu"].astype(x.dtype)
    return (g * u) @ p["wd"].astype(x.dtype)


def gelu_mlp(p, x: Array) -> Array:
    h = jax.nn.gelu(x @ p["wg"].astype(x.dtype) + 0.0)
    return h @ p["wd"].astype(x.dtype)
