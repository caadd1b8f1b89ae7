"""Config -> Model: init / param_specs / loss / prefill / decode across the
six assigned families (dense, moe, ssm, hybrid, encdec-audio, vlm).

Conventions
-----------
* Per-layer parameters are stacked on a leading L axis and consumed with
  ``lax.scan`` (keeps HLO size O(1) in depth) with optional
  ``jax.checkpoint`` remat per block.
* A Model never touches the mesh: it only declares PartitionSpecs over the
  'model' axis; the trainer decides data/pod sharding.
* ``batch`` dicts:
    train:   {"tokens": (B,S) i32, "labels": (B,S) i32, [frontend stubs]}
    prefill: {"tokens": (B,S) i32, [frontend stubs]}
    decode:  token (B,1) i32 + a cache pytree + scalar position.
* Modality frontends (audio conv stack / vision tower) are stubs per spec:
  the batch carries precomputed frame/patch embeddings.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models.config import ModelConfig

Array = jax.Array
PyTree = Any


def _sinusoid(S: int, d: int, dtype) -> Array:
    pos = jnp.arange(S)[:, None].astype(jnp.float32)
    return _sinusoid_at(pos, d).astype(dtype)


def _sinusoid_at(pos: Array, d: int) -> Array:
    """Sinusoidal position encoding at (possibly dynamic) positions.
    pos: (..., 1) float -> (..., d)."""
    div = jnp.exp(jnp.arange(0, d, 2).astype(jnp.float32) * (-math.log(10000.0) / d))
    ang = pos * div
    pe = jnp.zeros(pos.shape[:-1] + (d,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(ang))
    pe = pe.at[..., 1::2].set(jnp.cos(ang))
    return pe


def cross_entropy(logits: Array, labels: Array) -> Tuple[Array, Array]:
    """Mean CE over positions with label >= 0.  logits (B,S,V), labels (B,S)."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), labels.clip(0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    per_tok = (lse - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(per_tok) / denom, denom


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init

    def init(self, key: Array) -> PyTree:
        params = self._build(key)[0]
        pdt = jnp.dtype(self.cfg.param_dtype)
        if pdt != jnp.float32:
            params = jax.tree.map(lambda p: p.astype(pdt), params)
        return params

    def init_abstract(self) -> PyTree:
        """ShapeDtypeStruct params (no allocation) -- for AOT lowering."""
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_specs(self) -> PyTree:
        return self._build_specs()

    # -- families ---------------------------------------------------------

    def _block_inits(self):
        """(layer_init_fn, spec template) for one decoder block of the family."""
        cfg = self.cfg
        d, ff, hd = cfg.d_model, cfg.d_ff, cfg.hd()

        if cfg.family in ("dense", "vlm"):
            def one(k):
                k1, k2 = jax.random.split(k)
                attn, attn_s = L.attention_init(k1, d, cfg.n_heads, cfg.n_kv_heads,
                                                hd, cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)
                mlp, mlp_s = L.mlp_init(k2, d, ff)
                ln1, _ = L.rmsnorm_init(d)
                ln2, _ = L.rmsnorm_init(d)
                return ({"attn": attn, "mlp": mlp, "ln1": ln1, "ln2": ln2},
                        {"attn": attn_s, "mlp": mlp_s, "ln1": P(None), "ln2": P(None)})
            return one

        if cfg.family == "moe":
            def one(k):
                k1, k2 = jax.random.split(k)
                attn, attn_s = L.attention_init(k1, d, cfg.n_heads, cfg.n_kv_heads,
                                                hd, cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)
                moe, moe_s = MOE.moe_init(k2, d, ff, cfg.n_experts,
                                          cfg.n_experts_held)
                ln1, _ = L.rmsnorm_init(d)
                ln2, _ = L.rmsnorm_init(d)
                return ({"attn": attn, "moe": moe, "ln1": ln1, "ln2": ln2},
                        {"attn": attn_s, "moe": moe_s, "ln1": P(None), "ln2": P(None)})
            return one

        if cfg.family in ("ssm", "hybrid"):
            def one(k):
                m, m_s = M2.mamba2_init(k, d, d_inner=cfg.d_inner(),
                                        d_state=cfg.ssm_state,
                                        n_heads=cfg.ssm_heads(), d_conv=cfg.ssm_conv)
                ln, _ = L.rmsnorm_init(d)
                return ({"mamba": m, "ln": ln}, {"mamba": m_s, "ln": P(None)})
            return one

        if cfg.family == "encdec":
            def one(k):
                k1, k2, k3 = jax.random.split(k, 3)
                attn, attn_s = L.attention_init(k1, d, cfg.n_heads, cfg.n_kv_heads,
                                                hd, cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)
                xattn, xattn_s = L.attention_init(k2, d, cfg.n_heads, cfg.n_kv_heads,
                                                  hd, cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)
                mlp, mlp_s = L.mlp_init(k3, d, ff)
                ln1, _ = L.rmsnorm_init(d)
                ln2, _ = L.rmsnorm_init(d)
                ln3, _ = L.rmsnorm_init(d)
                return ({"attn": attn, "xattn": xattn, "mlp": mlp,
                         "ln1": ln1, "ln2": ln2, "ln3": ln3},
                        {"attn": attn_s, "xattn": xattn_s, "mlp": mlp_s,
                         "ln1": P(None), "ln2": P(None), "ln3": P(None)})
            return one

        raise ValueError(cfg.family)

    def _build(self, key: Array) -> Tuple[PyTree, PyTree]:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        d, V = cfg.d_model, cfg.vocab
        one = self._block_inits()

        def layer_init(k):
            return one(k)[0]

        stacked = jax.vmap(layer_init)(jax.random.split(keys[0], cfg.n_layers))
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(keys[1], (V, d)) * 0.02).astype(jnp.float32),
            "layers": stacked,
            "final_norm": jnp.ones((d,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = (jax.random.normal(keys[2], (d, V))
                                 * (1.0 / math.sqrt(d))).astype(jnp.float32)

        if cfg.family == "hybrid":
            k1, k2 = jax.random.split(keys[3])
            attn, _ = L.attention_init(k1, d, cfg.n_heads, cfg.n_kv_heads,
                                       cfg.hd(), cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)
            mlp, _ = L.mlp_init(k2, d, cfg.d_ff)
            ln1, _ = L.rmsnorm_init(d)
            ln2, _ = L.rmsnorm_init(d)
            params["shared_attn"] = {"attn": attn, "mlp": mlp, "ln1": ln1, "ln2": ln2}

        if cfg.family == "encdec":
            def enc_init(k):
                k1, k2 = jax.random.split(k)
                attn, _ = L.attention_init(k1, d, cfg.n_heads, cfg.n_kv_heads,
                                           cfg.hd(), cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)
                mlp, _ = L.mlp_init(k2, d, cfg.d_ff)
                ln1, _ = L.rmsnorm_init(d)
                ln2, _ = L.rmsnorm_init(d)
                return {"attn": attn, "mlp": mlp, "ln1": ln1, "ln2": ln2}
            params["encoder"] = jax.vmap(enc_init)(
                jax.random.split(keys[4], cfg.encoder_layers))
            params["enc_norm"] = jnp.ones((d,), jnp.float32)

        return params, None

    def _build_specs(self) -> PyTree:
        cfg = self.cfg
        d, V = cfg.d_model, cfg.vocab
        one = self._block_inits()
        _, block_specs = one(jax.random.key(0))
        lift = lambda tree: jax.tree.map(lambda s: P(None, *s), tree,
                                         is_leaf=lambda s: isinstance(s, P))
        specs: Dict[str, Any] = {
            "embed": L.auto_spec((V, d), prefer=(0,)),
            "layers": lift(block_specs),
            "final_norm": P(None),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = L.auto_spec((d, V), prefer=(1,))
        if cfg.family == "hybrid":
            attn_s = L.attention_init(jax.random.key(0), d, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.hd(), cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)[1]
            mlp_s = L.mlp_init(jax.random.key(0), d, cfg.d_ff)[1]
            specs["shared_attn"] = {"attn": attn_s, "mlp": mlp_s,
                                    "ln1": P(None), "ln2": P(None)}
        if cfg.family == "encdec":
            attn_s = L.attention_init(jax.random.key(0), d, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.hd(), cfg.qkv_bias,
                                                shard_policy=cfg.attn_shard_policy)[1]
            mlp_s = L.mlp_init(jax.random.key(0), d, cfg.d_ff)[1]
            specs["encoder"] = lift({"attn": attn_s, "mlp": mlp_s,
                                     "ln1": P(None), "ln2": P(None)})
            specs["enc_norm"] = P(None)
        return specs

    # --------------------------------------------------------------- forward

    def _embed_inputs(self, params, batch) -> Tuple[Array, Array]:
        """Returns (hidden (B,S,d), positions) handling frontend stubs."""
        cfg = self.cfg
        adt = jnp.dtype(cfg.activation_dtype)
        tok_emb = params["embed"].astype(adt)

        if cfg.family == "vlm" and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(adt)  # (B, Pn, d) stub tower output
            te = tok_emb[batch["tokens"]]            # (B, St, d)
            h = jnp.concatenate([ve, te], axis=1)
            B, S, _ = h.shape
            Pn = ve.shape[1]
            # M-RoPE ids: vision patches on an (h, w) grid at t=0; text tokens
            # advance t (and h=w=t) after the vision span -- Qwen2-VL scheme.
            side = max(int(math.sqrt(Pn)), 1)
            pidx = jnp.arange(Pn)
            tpos = jnp.concatenate([jnp.zeros((Pn,), jnp.int32),
                                    jnp.arange(S - Pn, dtype=jnp.int32) + 1])
            hpos = jnp.concatenate([(pidx // side).astype(jnp.int32),
                                    jnp.arange(S - Pn, dtype=jnp.int32) + 1])
            wpos = jnp.concatenate([(pidx % side).astype(jnp.int32),
                                    jnp.arange(S - Pn, dtype=jnp.int32) + 1])
            pos3 = jnp.stack([tpos, hpos, wpos])[:, None, :].repeat(B, axis=1)
            return h, pos3

        h = self._scale_embedding(tok_emb[batch["tokens"]])
        B, S, _ = h.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(pos, (3, B, S))
        return h, pos

    def _scale_embedding(self, h: Array) -> Array:
        m = self.cfg.embedding_multiplier
        return h if m == 1.0 else h * m

    def _residual(self, h: Array, y: Array) -> Array:
        """A decoder branch's output added to the residual stream."""
        m = self.cfg.residual_multiplier
        return h + y if m == 1.0 else h + y * m

    def _logits(self, params, h: Array) -> Array:
        cfg = self.cfg
        h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = h @ head.astype(h.dtype)
        s = cfg.logits_scaling
        return logits if s == 1.0 else logits / s

    def _decoder_blocks(self, params, h: Array, positions,
                        enc_out: Optional[Array] = None
                        ) -> Tuple[Array, Array, Dict[str, Array]]:
        """Scan the stacked decoder blocks.  Returns (hidden, aux_loss,
        routing statistics: empty but for the moe family)."""
        cfg = self.cfg
        hd = cfg.hd()
        attn_kw = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
                       positions=positions, theta=cfg.rope_theta,
                       window=cfg.attn_window,
                       mrope_sections=cfg.mrope_sections,
                       impl=cfg.attn_impl,
                       scale=cfg.attention_multiplier or None)
        res = self._residual

        if cfg.family in ("dense", "vlm"):
            def block(carry, lp):
                h, aux = carry
                h = res(h, L.attention(lp["attn"],
                                       L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                       **attn_kw))
                h = res(h, L.swiglu(lp["mlp"],
                                    L.rmsnorm(h, lp["ln2"], cfg.norm_eps)))
                return (h, aux), None
        elif cfg.family == "moe":
            def block(carry, lp):
                h, aux = carry
                h = res(h, L.attention(lp["attn"],
                                       L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                       **attn_kw))
                y, st = MOE.moe_apply(lp["moe"],
                                      L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                                      n_experts=cfg.n_experts,
                                      k=cfg.experts_per_tok)
                return (res(h, y), aux), st
        elif cfg.family == "ssm":
            def block(carry, lp):
                h, aux = carry
                h = h + M2.mamba2_apply(lp["mamba"], L.rmsnorm(h, lp["ln"], cfg.norm_eps),
                                        d_inner=cfg.d_inner(), d_state=cfg.ssm_state,
                                        n_heads=cfg.ssm_heads(), chunk=cfg.ssm_chunk,
                                        norm_eps=cfg.norm_eps)
                return (h, aux), None
        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def block(carry, xs):
                lp, idx = xs
                h, aux = carry
                h = h + M2.mamba2_apply(lp["mamba"], L.rmsnorm(h, lp["ln"], cfg.norm_eps),
                                        d_inner=cfg.d_inner(), d_state=cfg.ssm_state,
                                        n_heads=cfg.ssm_heads(), chunk=cfg.ssm_chunk,
                                        norm_eps=cfg.norm_eps)

                def with_attn(h):
                    h = h + L.attention(shared["attn"],
                                        L.rmsnorm(h, shared["ln1"], cfg.norm_eps),
                                        **attn_kw)
                    return h + L.swiglu(shared["mlp"],
                                        L.rmsnorm(h, shared["ln2"], cfg.norm_eps))

                h = jax.lax.cond(idx % cfg.attn_every == cfg.attn_every - 1,
                                 with_attn, lambda h: h, h)
                return (h, aux), None
        elif cfg.family == "encdec":
            def block(carry, lp):
                h, aux = carry
                h = h + L.attention(lp["attn"], L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                    **attn_kw)
                # cross-attention: project encoder output with this layer's k/v
                xk = (enc_out @ lp["xattn"]["wk"].astype(h.dtype))
                xv = (enc_out @ lp["xattn"]["wv"].astype(h.dtype))
                B, Se, _ = enc_out.shape
                xk = xk.reshape(B, Se, cfg.n_kv_heads, hd)
                xv = xv.reshape(B, Se, cfg.n_kv_heads, hd)
                h = h + L.attention(lp["xattn"], L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                                    n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
                                    positions=positions, theta=0.0, causal=False,
                                    kv=(xk, xv))
                h = h + L.swiglu(lp["mlp"], L.rmsnorm(h, lp["ln3"], cfg.norm_eps))
                return (h, aux), None
        else:
            raise ValueError(cfg.family)

        if cfg.remat:
            block = jax.checkpoint(block, prevent_cse=False)

        # data-derived zero: keeps the aux carry's varying-manual-axes type
        # consistent under shard_map (see mamba2._ssd_chunked)
        aux0 = h.reshape(-1)[0].astype(jnp.float32) * 0.0
        if cfg.family == "hybrid":
            xs = (params["layers"], jnp.arange(cfg.n_layers))
        else:
            xs = params["layers"]
        (h, aux), per_layer = jax.lax.scan(block, (h, aux0), xs)
        if cfg.family != "moe":
            return h, aux, {}
        # the load-balancing loss averaged over the layers; the counters of
        # every layer's routing
        return h, jnp.mean(per_layer["aux"]), {
            "moe_held_rows": jnp.sum(per_layer["held_rows"]).astype(jnp.float32),
            "moe_load_max_over_mean": jnp.max(per_layer["load"])}

    def _encode(self, params, frames: Array) -> Array:
        """Whisper-style encoder over stub frame embeddings (B, F, d)."""
        cfg = self.cfg
        adt = jnp.dtype(cfg.activation_dtype)
        h = frames.astype(adt) + _sinusoid(frames.shape[1], cfg.d_model, adt)
        B, S, _ = h.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        def block(h, lp):
            h = h + L.attention(lp["attn"], L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=cfg.hd(),
                                positions=pos, theta=0.0, causal=False)
            h = h + L.swiglu(lp["mlp"], L.rmsnorm(h, lp["ln2"], cfg.norm_eps))
            return h, None

        if cfg.remat:
            block = jax.checkpoint(block, prevent_cse=False)
        h, _ = jax.lax.scan(block, h, params["encoder"])
        return L.rmsnorm(h, params["enc_norm"], cfg.norm_eps)

    def forward(self, params, batch) -> Tuple[Array, Array]:
        """Full-sequence forward -> (logits (B,S,V), aux loss)."""
        logits, aux, _ = self._forward(params, batch)
        return logits, aux

    def _forward(self, params, batch) -> Tuple[Array, Array, Dict[str, Array]]:
        cfg = self.cfg
        adt = jnp.dtype(cfg.activation_dtype)
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self._encode(params, batch["frames"])
            h = params["embed"].astype(adt)[batch["tokens"]]
            h = h + _sinusoid(h.shape[1], cfg.d_model, adt)
            B, S, _ = h.shape
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        else:
            h, pos = self._embed_inputs(params, batch)
        h, aux, stats = self._decoder_blocks(params, h, pos, enc_out)
        return self._logits(params, h), aux, stats

    def prefill(self, params, batch) -> Array:
        """Inference prefill: full-sequence forward, returns last-position
        logits (B, V)."""
        logits, _ = self.forward(params, batch)
        return logits[:, -1]

    def encode_cross_cache(self, params, frames: Array, cache: PyTree) -> PyTree:
        """encdec only: run the encoder and fill the per-layer cross-attention
        K/V of a fresh decode cache."""
        cfg = self.cfg
        assert cfg.family == "encdec"
        enc = self._encode(params, frames)
        B = frames.shape[0]
        hd = cfg.hd()

        def one(lp):
            xk = (enc @ lp["xattn"]["wk"].astype(enc.dtype)
                  ).reshape(B, -1, cfg.n_kv_heads, hd)
            xv = (enc @ lp["xattn"]["wv"].astype(enc.dtype)
                  ).reshape(B, -1, cfg.n_kv_heads, hd)
            return xk, xv

        ck, cv = jax.vmap(one)(params["layers"])
        return {**cache, "cross_k": ck.astype(cache["cross_k"].dtype),
                "cross_v": cv.astype(cache["cross_v"].dtype)}

    # ---------------------------------------------------------------- loss

    def loss(self, params, batch) -> Tuple[Array, Dict[str, Array]]:
        cfg = self.cfg
        logits, aux, stats = self._forward(params, batch)
        labels = batch["labels"]
        if cfg.family == "vlm" and "vision_embeds" in batch:
            # no loss on the vision span
            Pn = batch["vision_embeds"].shape[1]
            pad = jnp.full(labels.shape[:1] + (Pn,), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        ce, ntok = cross_entropy(logits, labels)
        total = ce + cfg.router_aux_weight * aux
        return total, {"ce": ce, "aux_loss": aux, **stats}

    # ------------------------------------------------------------- serving

    def init_cache(self, batch_size: int, max_len: int) -> PyTree:
        cfg = self.cfg
        hd = cfg.hd()
        kvd = jnp.dtype(cfg.activation_dtype)
        C = min(max_len, cfg.attn_window) if cfg.attn_window else max_len

        def attn_cache(layers: int):
            return {
                "k": jnp.zeros((layers, batch_size, C, cfg.n_kv_heads, hd), kvd),
                "v": jnp.zeros((layers, batch_size, C, cfg.n_kv_heads, hd), kvd),
            }

        if cfg.family in ("dense", "vlm", "moe"):
            return attn_cache(cfg.n_layers)
        if cfg.family == "ssm":
            mk = M2.mamba2_cache_init(batch_size, d_inner=cfg.d_inner(),
                                      d_state=cfg.ssm_state, n_heads=cfg.ssm_heads(),
                                      d_conv=cfg.ssm_conv, dtype=kvd)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), mk)
        if cfg.family == "hybrid":
            mk = M2.mamba2_cache_init(batch_size, d_inner=cfg.d_inner(),
                                      d_state=cfg.ssm_state, n_heads=cfg.ssm_heads(),
                                      d_conv=cfg.ssm_conv, dtype=kvd)
            mamba = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), mk)
            shared = attn_cache(1)
            return {"mamba": mamba, "shared": shared}
        if cfg.family == "encdec":
            return {
                "self": attn_cache(cfg.n_layers),
                "cross_k": jnp.zeros((cfg.n_layers, batch_size, cfg.encoder_frames,
                                      cfg.n_kv_heads, hd), kvd),
                "cross_v": jnp.zeros((cfg.n_layers, batch_size, cfg.encoder_frames,
                                      cfg.n_kv_heads, hd), kvd),
            }
        raise ValueError(cfg.family)

    def cache_specs(self) -> PyTree:
        """PartitionSpecs for the cache (kv-heads / channels over 'model')."""
        cfg = self.cfg
        hd = cfg.hd()
        if cfg.n_kv_heads % L.MODEL_AXIS_SIZE == 0:
            kv_spec = P(None, None, None, "model", None)   # shard kv heads
        elif hd % L.MODEL_AXIS_SIZE == 0:
            kv_spec = P(None, None, None, None, "model")   # shard head_dim
        else:
            kv_spec = P(None, None, None, None, None)
        if cfg.family in ("dense", "vlm", "moe"):
            return {"k": kv_spec, "v": kv_spec}
        if cfg.family == "ssm":
            return {"state": P(None, None, None, None, None),
                    "conv": P(None, None, None, None)}
        if cfg.family == "hybrid":
            return {"mamba": {"state": P(None, None, None, None, None),
                              "conv": P(None, None, None, None)},
                    "shared": {"k": kv_spec, "v": kv_spec}}
        if cfg.family == "encdec":
            return {"self": {"k": kv_spec, "v": kv_spec},
                    "cross_k": kv_spec, "cross_v": kv_spec}
        raise ValueError(cfg.family)

    def decode_step(self, params, cache: PyTree, token: Array, pos: Array
                    ) -> Tuple[Array, PyTree]:
        """One-token decode.  token (B,1) i32; pos scalar i32."""
        cfg = self.cfg
        adt = jnp.dtype(cfg.activation_dtype)
        hd = cfg.hd()
        h = self._scale_embedding(params["embed"].astype(adt)[token])  # (B,1,d)
        if cfg.family == "encdec":
            pe = _sinusoid_at(jnp.asarray(pos, jnp.float32)[None, None, None],
                              cfg.d_model)[0]
            h = h + pe.astype(adt)

        def attn_block(h, lp, ck, cv):
            y, ck, cv = L.attention_decode(
                lp["attn"], L.rmsnorm(h, lp["ln1"], cfg.norm_eps), ck, cv, pos,
                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
                theta=cfg.rope_theta, window=cfg.attn_window,
                mrope_sections=cfg.mrope_sections,
                scale=cfg.attention_multiplier or None)
            return self._residual(h, y), ck, cv

        if cfg.family in ("dense", "vlm", "moe"):
            def block(h, xs):
                lp, ck, cv = xs
                h, ck, cv = attn_block(h, lp, ck, cv)
                hn = L.rmsnorm(h, lp["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    y, _ = MOE.moe_apply(lp["moe"], hn, n_experts=cfg.n_experts,
                                         k=cfg.experts_per_tok)
                else:
                    y = L.swiglu(lp["mlp"], hn)
                return self._residual(h, y), (ck, cv)

            h, (ks, vs) = jax.lax.scan(
                lambda c, xs: block(c, xs), h,
                (params["layers"], cache["k"], cache["v"]))
            cache = {"k": ks, "v": vs}

        elif cfg.family == "ssm":
            def block(h, xs):
                lp, cc = xs
                y, cc = M2.mamba2_decode(lp["mamba"], L.rmsnorm(h, lp["ln"], cfg.norm_eps),
                                         cc, d_inner=cfg.d_inner(),
                                         d_state=cfg.ssm_state,
                                         n_heads=cfg.ssm_heads(), norm_eps=cfg.norm_eps)
                return h + y, cc

            h, cache = jax.lax.scan(block, h, (params["layers"], cache))

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]
            sk, sv = cache["shared"]["k"][0], cache["shared"]["v"][0]

            def block(carry, xs):
                h, sk, sv = carry
                lp, cc, idx = xs
                y, cc = M2.mamba2_decode(lp["mamba"], L.rmsnorm(h, lp["ln"], cfg.norm_eps),
                                         cc, d_inner=cfg.d_inner(),
                                         d_state=cfg.ssm_state,
                                         n_heads=cfg.ssm_heads(), norm_eps=cfg.norm_eps)
                h = h + y

                def with_attn(args):
                    h, sk, sv = args
                    y, sk, sv = L.attention_decode(
                        shared["attn"], L.rmsnorm(h, shared["ln1"], cfg.norm_eps),
                        sk, sv, pos, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                        hd=hd, theta=cfg.rope_theta, window=cfg.attn_window)
                    h = h + y
                    h = h + L.swiglu(shared["mlp"],
                                     L.rmsnorm(h, shared["ln2"], cfg.norm_eps))
                    return h, sk, sv

                h, sk, sv = jax.lax.cond(
                    idx % cfg.attn_every == cfg.attn_every - 1,
                    with_attn, lambda a: a, (h, sk, sv))
                return (h, sk, sv), cc

            (h, sk, sv), mamba_cache = jax.lax.scan(
                block, (h, sk, sv),
                (params["layers"], cache["mamba"], jnp.arange(cfg.n_layers)))
            cache = {"mamba": mamba_cache,
                     "shared": {"k": sk[None], "v": sv[None]}}

        elif cfg.family == "encdec":
            def block(h, xs):
                lp, ck, cv, xk, xv = xs
                h, ck, cv = attn_block(h, lp, ck, cv)
                y = L.attention(lp["xattn"], L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                                n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=hd,
                                positions=jnp.zeros((h.shape[0], 1), jnp.int32),
                                theta=0.0, causal=False,
                                kv=(xk.astype(h.dtype), xv.astype(h.dtype)))
                h = h + y
                h = h + L.swiglu(lp["mlp"], L.rmsnorm(h, lp["ln3"], cfg.norm_eps))
                return h, (ck, cv)

            h, (ks, vs) = jax.lax.scan(
                block, h,
                (params["layers"], cache["self"]["k"], cache["self"]["v"],
                 cache["cross_k"], cache["cross_v"]))
            cache = {"self": {"k": ks, "v": vs},
                     "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
        else:
            raise ValueError(cfg.family)

        return self._logits(params, h), cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
