"""jit'd public wrappers around the Pallas kernels.

Handle arbitrary input shapes (flatten + pad to (nb, block) slabs), pick
interpret mode automatically off-TPU, and expose the same signatures as the
jnp oracles in ref.py (tests assert allclose between the two).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.kernels import block_topk as K
from repro.kernels import pack as KP

Array = jax.Array


def _interpret_default() -> bool:
    # sanitize mode (repro.analysis.sanitize) forces interpret even on TPU:
    # interpret mode raises on out-of-bounds ref indexing where the hardware
    # silently clamps
    from repro.analysis import sanitize

    return sanitize.active() or jax.default_backend() != "tpu"


def _unpartitioned(kernel, *operands):
    """Call a Pallas kernel where its Mosaic lowering accepts it.  GSPMD
    cannot partition a Mosaic kernel, so under a mesh with auto axes (the
    trainers' 'model' axis, inside their shard_map over the worker axes) the
    call runs in a shard_map over those axes, its operands replicated."""
    auto = compat.auto_axes_of(compat.abstract_mesh())
    if not auto:
        return kernel(*operands)
    return jax.shard_map(kernel, in_specs=P(), out_specs=P(),
                         axis_names=set(auto))(*operands)


def _to_slabs(x: Array, block: int, tile: int = K.TILE_NB
              ) -> Tuple[Array, int, Tuple[int, ...]]:
    xf = x.reshape(-1)
    d = xf.shape[0]
    nb = -(-d // block)
    nb_pad = -(-nb // tile) * tile
    xp = jnp.pad(xf, (0, nb_pad * block - d)).reshape(nb_pad, block)
    return xp, d, x.shape


@functools.partial(jax.jit, static_argnames=("block", "kb", "interpret"))
def block_topk(x: Array, block: int = 1024, kb: int = 64,
               interpret: bool | None = None) -> Array:
    """Dense block-top-k compression of an arbitrary-shape tensor."""
    interpret = _interpret_default() if interpret is None else interpret
    xp, d, shape = _to_slabs(x, block)
    out = K.block_topk_pallas(xp, kb, interpret=interpret)
    return out.reshape(-1)[:d].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block", "kb", "lam", "interpret"))
def efbv_update(g: Array, h: Array, lam: float, block: int = 1024, kb: int = 64,
                interpret: bool | None = None) -> Tuple[Array, Array]:
    """Fused worker update: d = C(g - h); h' = h + lam d.  Returns (d, h')."""
    interpret = _interpret_default() if interpret is None else interpret
    gp, d_len, shape = _to_slabs(g, block)
    hp, _, _ = _to_slabs(h.astype(g.dtype), block)
    d_out, h_out = K.efbv_update_pallas(gp, hp, lam, kb, interpret=interpret)
    unpad = lambda a: a.reshape(-1)[:d_len].reshape(shape)
    return unpad(d_out), unpad(h_out).astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("block", "kb", "lam", "interpret",
                                             "stream"))
def efbv_pack_update(g: Array, h: Array, lam: float, block: int = 1024,
                     kb: int = 64, interpret: bool | None = None,
                     stream: bool = False
                     ) -> Tuple[Tuple[Array, Array], Array]:
    """Fused compress-and-pack worker update (kernels/pack.py): one HBM pass
    computing d = block_topk(g - h), h' = h + lam d, and the wire payload.

    Returns ((values, indices), h') with values/indices of shape (nb, kb),
    nb = ceil(g.size / block) -- the same payload layout as
    ``BlockTopK.encode``.  The kernel's grid may end in a ragged step, so
    only the streaming variant pads rows (to STREAM_TILE_NB, sliced off).
    ``stream=True`` selects the async-copy kernel variant (the payload slab
    DMAs toward HBM while the h update computes); bit-identical payloads.
    """
    interpret = _interpret_default() if interpret is None else interpret
    tile = KP.STREAM_TILE_NB if stream else 1
    gp, d_len, shape = _to_slabs(g, block, tile)
    # h keeps its own dtype: the kernel subtracts in f32, so pre-rounding h
    # to g.dtype would break bit-identity with the jnp oracle on mixed dtypes
    hp, _, _ = _to_slabs(h, block, tile)
    vals, idx, h_out = _unpartitioned(
        functools.partial(KP.pack_update_pallas, lam=lam, kb=kb,
                          interpret=interpret, stream=stream), gp, hp)
    nb = -(-d_len // block)
    h_new = h_out.reshape(-1)[:d_len].reshape(shape)
    return (vals[:nb], idx[:nb]), h_new


# jitted where the kernel is called, inside _unpartitioned's shard_map: XLA
# names a custom call after its innermost call, so the kernel's instruction
# is %block_unpack_sum.N on every mesh, the name a trace finds it by
_block_unpack_sum = jax.jit(KP.block_unpack_sum,
                            static_argnames=("block", "interpret"))


def block_decode_sum(vals: Array, idx: Array, block: int,
                     interpret: bool | None = None) -> Array:
    """Sort-free block-top-k decode-sum (kernels/pack.py): the payload
    (values f32, block-local indices int32), (nb, kb) or worker-stacked
    (n, nb, kb), -> the dense (nb, block) f32 sum, each position's terms
    added in ascending worker, then slot, order.  The kernel reads the
    payload transposed, one block per lane."""
    interpret = _interpret_default() if interpret is None else interpret
    if vals.ndim == 2:
        vals, idx = vals[None], idx[None]
    n, nb, kb = vals.shape
    lanes = lambda a: jnp.transpose(a, (0, 2, 1)).reshape(n * kb, nb)
    return _unpartitioned(
        functools.partial(_block_unpack_sum, block=block,
                          interpret=interpret), lanes(vals), lanes(idx))


# default flat-vector slab width for the codec kernels below (rand-k / QSGD
# have no block structure of their own; 1024 lanes = 8 full vregs)
_CODEC_COLS = 1024


@functools.partial(jax.jit, static_argnames=("lam", "scale", "interpret"))
def randk_update(g: Array, h: Array, idx: Array, lam: float, scale: float,
                 interpret: bool | None = None) -> Array:
    """Fused rand-k worker update (kernels/pack.py): h' = h + lam * d with
    d = randk(g - h) rebuilt in VMEM from the SMEM index list -- the dense d
    never reaches HBM.  ``idx``: (k,) int32 flat positions into g; returns
    h' shaped/dtyped like h."""
    interpret = _interpret_default() if interpret is None else interpret
    gp, d_len, _ = _to_slabs(g, _CODEC_COLS)
    hp, _, h_shape = _to_slabs(h, _CODEC_COLS)
    h_out = _unpartitioned(
        functools.partial(KP.randk_update_pallas, scale=scale, lam=lam,
                          interpret=interpret), gp, hp, idx)
    return h_out.reshape(-1)[:d_len].reshape(h_shape)


@functools.partial(jax.jit, static_argnames=("lam", "s", "interpret"))
def qsgd_pack_update(g: Array, h: Array, u: Array, norm: Array, lam: float,
                     s: int, interpret: bool | None = None
                     ) -> Tuple[Array, Array]:
    """Fused QSGD quantize-and-pack (kernels/pack.py): returns the flat
    (g.size,) signed level stream (int8 for s <= 127, int16 above) and
    h' = h + lam * dequant(levels).  ``u``: the (g.size,) uniform draws of
    the jnp oracle; ``norm``: scalar ||g - h||_2."""
    interpret = _interpret_default() if interpret is None else interpret
    gp, d_len, _ = _to_slabs(g, _CODEC_COLS, tile=KP.QS_TILE_NB)
    hp, _, h_shape = _to_slabs(h, _CODEC_COLS, tile=KP.QS_TILE_NB)
    up_, _, _ = _to_slabs(u, _CODEC_COLS, tile=KP.QS_TILE_NB)
    lvl, h_out = _unpartitioned(
        functools.partial(KP.qsgd_pack_update_pallas, s=s, lam=lam,
                          interpret=interpret),
        gp, hp, up_, jnp.reshape(norm, (1, 1)).astype(jnp.float32))
    levels = lvl.reshape(-1)[:d_len]
    return levels, h_out.reshape(-1)[:d_len].reshape(h_shape)
