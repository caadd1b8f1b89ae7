"""Exact causal self-attention through the Pallas splash kernel.

``causal_attention(q, k, v, scale)``: q (B, S, H, hd), k and v (B, S, K, hd)
with H a multiple of K (grouped-query attention), -> (B, S, H, hd), each
query attending to itself and every earlier key with an exact softmax.  On
the TPU it runs ``jax.experimental.pallas.ops.tpu.splash_attention``: the
forward kernel and its own dq and dkv backward kernels, which take the K kv
heads natively, skip the causal mask's empty blocks and keep the scores,
the softmax and the probabilities in VMEM, so no (S, S) tensor reaches HBM.
The kernels accumulate their products in f32 and keep the softmax in f32.
q is scaled by the attention's factor before the kernel (a power of two in
every configuration of this repo, so exact in bfloat16).  The batch folds
into the heads: row b's head h is kernel head b H + h, whose kv head
(b H + h) / G = b K + h / G is row b's own.  Off the TPU the same call runs
the jnp oracle, the materialised ``(B, K, G, S, S)`` scores of
``models/layers.py``.

Inside the trainers' shard_map a Pallas call may not see a GSPMD-auto axis
and must carry the operands' vma.  As ``kernels/gmm.py`` does for megablox,
the kernels run in a nested shard_map over the auto axes without the vma
check, their outputs marked varying like the operands, under a custom VJP
of this module's own whose rules are splash's forward and backward rules
(splash's own custom VJP, differentiated there, would hand back cotangents
that lack the operands' vma).  Where ``model`` is one of the auto axes each
model shard takes its own heads; ``supported`` refuses a ``model`` axis
that does not divide both H and K.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

Array = jax.Array

#: the mesh axis whose shards take their own heads
MODEL = "model"
#: the kernel's sequence tile: S must be a multiple of it
LANES = 128
#: the largest query and key block, tried first.  On a v5e at 1 x 4,096
#: tokens, 24 / 8 heads of 64, blocks of 1,024 make the backward kernels
#: faster than 512 and the step 1.2% faster; 2,048 does not fit VMEM
BLOCK = 1024
#: the shortest sequence the dispatch takes the kernel at.  On a v5e a
#: training step at 4 x 512 tokens (14 / 2 heads of 64) ran 2.1% slower
#: through the kernel than through materialised scores, and one at
#: 1 x 4,096 (24 / 8 heads of 64) 42% faster
MIN_SEQ = 2048

_splash = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.splash_attention.splash_attention_kernel")
_masks = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.splash_attention.splash_attention_mask")
# splash's own custom VJP; its ``fwd`` and ``bwd`` rules run the kernels
_rules = _splash._splash_attention_custom

#: ``impl`` values: the kernel compiled, the kernel in interpret mode, the
#: jnp oracle; ``None`` picks the kernel on the TPU (interpret mode under
#: ``repro.analysis.sanitize``) and the oracle elsewhere
IMPLS = ("kernel", "interpret", "oracle")


def block(seq: int) -> int:
    """The query and key block at sequence ``seq``: the largest of
    ``BLOCK``, ``BLOCK / 2``, ... ``LANES`` that divides it."""
    b = BLOCK
    while seq % b:
        b //= 2
    return b


def _model_shards() -> int:
    """Size of a GSPMD-auto ``model`` axis of the ambient mesh, else 1."""
    mesh = compat.abstract_mesh()
    if MODEL not in compat.auto_axes_of(mesh):
        return 1
    return mesh.shape[MODEL]


def supported(seq: int, n_heads: int, n_kv: int) -> bool:
    """Whether the kernel can take self-attention of this shape here: S a
    multiple of 128 and a ``model`` axis, if any, dividing both head
    counts."""
    m = _model_shards()
    return seq % LANES == 0 and n_heads % m == 0 and n_kv % m == 0


def default_impl() -> str:
    from repro.analysis import sanitize

    # sanitize mode runs the kernel in interpret mode on the TPU too
    return ("oracle" if jax.default_backend() != "tpu" else
            "interpret" if sanitize.active() else "kernel")


def takes(seq: int, n_heads: int, n_kv: int) -> bool:
    """Whether causal self-attention of this shape runs the kernel here: on
    the TPU, from ``MIN_SEQ`` tokens on, where ``supported``."""
    return (default_impl() != "oracle" and seq >= MIN_SEQ
            and supported(seq, n_heads, n_kv))


def _kernel(heads: int, seq: int, interpret: bool):
    """The splash kernel of ``heads`` causal heads at sequence ``seq``.
    Built at each trace, since it holds its mask's block tables as arrays
    of the trace; splash works the tables out once per shape."""
    blk = block(seq)
    sizes = _splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    mask = _masks.MultiHeadMask([_masks.CausalMask((seq, seq))] * heads)
    return _splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                   q_seq_shards=1, interpret=interpret)


def _fold(x):
    """(B, N, S, hd) -> (B N, S, hd): the batch folded into the heads."""
    return x.reshape(-1, *x.shape[2:])


def _forward(q, k, v, *, interpret):
    """(B, H, S, hd) q and (B, K, S, hd) k, v -> the (B, H, S, hd) output
    and the (B, H, S) f32 log-sum-exp of each query's scores."""
    B, H, S, _ = q.shape
    kern = _kernel(B * H, S, interpret)
    out, res = _rules.fwd(kern.fwd_mask_info, None, None, _fold(q),
                          _fold(k), _fold(v), None, None, **kern.kwargs)
    return out.reshape(q.shape), res[6].reshape(B, H, S)


def _backward(q, k, v, out, lse, do, *, interpret):
    """dq, dk, dv of the forward's (q, k, v) under the output's cotangent."""
    B, H, S, _ = q.shape
    kern = _kernel(B * H, S, interpret)
    kw = kern.kwargs
    res = (_fold(q), _fold(k), _fold(v), None, None, _fold(out),
           lse.reshape(B * H, S), kern.dq_mask_info, kern.dkv_mask_info)
    grads = _rules.bwd(
        kw["save_residuals"], kw["mask_value"], kw["is_mqa"],
        kw["block_sizes"], kw["residual_checkpoint_name"],
        kw["mask_function"], kw["attn_logits_soft_cap"], kw["interpret"],
        res, _fold(do))
    dq, dk, dv = grads[3:6]
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _sharded(fn, *operands):
    """``fn`` on (B, N, ...) operands, each model shard on its own heads,
    in a shard_map over the ambient mesh's auto axes; its results marked
    varying like the operands."""
    vma = compat.vma_of(*operands)
    auto = compat.auto_axes_of(compat.abstract_mesh())
    if auto:
        spec = P(None, MODEL) if _model_shards() > 1 else P()
        fn = jax.shard_map(fn, in_specs=(spec,) * len(operands),
                           out_specs=spec, axis_names=set(auto),
                           check_vma=False)
    return jax.tree.map(lambda x: compat.varying(x, vma), fn(*operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attend(interpret, q, k, v):
    return _attend_fwd(interpret, q, k, v)[0]


def _attend_fwd(interpret, q, k, v):
    out, lse = _sharded(functools.partial(_forward, interpret=interpret),
                        q, k, v)
    return out, (q, k, v, out, lse)


def _attend_bwd(interpret, res, do):
    return _sharded(functools.partial(_backward, interpret=interpret),
                    *res, do.astype(res[0].dtype))


_attend.defvjp(_attend_fwd, _attend_bwd)


def causal_attention(q: Array, k: Array, v: Array,
                     scale: float | None = None,
                     impl: str | None = None) -> Array:
    """Exact causal softmax attention, scores times ``scale`` (None: over
    sqrt(hd)); q (B, S, H, hd), k and v (B, S, K, hd) -> (B, S, H, hd)."""
    impl = default_impl() if impl is None else impl
    if impl not in IMPLS:
        raise ValueError(f"attention impl {impl!r}; known: {IMPLS}")
    if impl == "oracle":
        from repro.models.layers import _sdpa, causal_mask

        S = q.shape[1]
        return _sdpa(q, k, v, causal_mask(S, S), scale)
    B, S, H, hd = q.shape
    if not supported(S, H, k.shape[2]):
        raise ValueError(f"the splash kernel cannot take S={S}, H={H}, "
                         f"K={k.shape[2]} under this mesh")
    factor = 1.0 / math.sqrt(hd) if scale is None else scale
    heads = lambda x: jnp.swapaxes(x, 1, 2)
    operands = (heads(q * jnp.asarray(factor, q.dtype)), heads(k), heads(v))
    vma = compat.vma_of(*operands)
    operands = [compat.varying(x, vma) for x in operands]
    return heads(_attend(impl == "interpret", *operands))
