"""Pallas TPU kernel: block-local top-k gradient compression.

TPU adaptation of the paper's top-k (DESIGN §3.4): an exact global top-k
needs a sort across HBM, which maps terribly onto the TPU vector unit.
Instead each VMEM-resident block keeps its own kb largest-magnitude entries
via *iterative max extraction*: kb data-parallel passes over the (8,128)
vregs -- no sort, no gather, exact first-index tie-breaking, and the working
set never leaves VMEM.

Grid: one step per tile of TILE_NB blocks; BlockSpec tiles are
(TILE_NB, BLOCK) slabs in VMEM (BLOCK a multiple of 128 lanes, TILE_NB a
multiple of 8 sublanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

TILE_NB = 8  # blocks (rows) per grid step


def _select_mask(xa, kb: int):
    """(rows, block) magnitudes -> 0/1 keep-mask, kb per row, exact."""
    block = xa.shape[1]
    # f32 column indices (exact for block < 2**24) from an int32 iota:
    # Mosaic's tpu.iota is integer-only, and cumsum has no Mosaic lowering
    cols = jax.lax.broadcasted_iota(jnp.int32, xa.shape, 1).astype(jnp.float32)

    def body(_, selected):
        score = jnp.where(selected > 0, -jnp.inf, xa)
        m = jnp.max(score, axis=1, keepdims=True)
        # (isfinite has no Pallas TPU lowering; != -inf is the same guard)
        is_m = (score == m) & (m != -jnp.inf)
        # first-index tie-break via min-reduction (cumsum doesn't lower)
        cmin = jnp.min(jnp.where(is_m, cols, float(block)), axis=1,
                       keepdims=True)
        first = is_m & (cols == cmin)
        return selected + first.astype(xa.dtype)

    return jax.lax.fori_loop(0, kb, body, jnp.zeros_like(xa))


def _block_topk_kernel(x_ref, o_ref, *, kb: int):
    x = x_ref[...]
    mask = _select_mask(jnp.abs(x).astype(jnp.float32), kb)
    o_ref[...] = x * mask.astype(x.dtype)


def block_topk_pallas(x2d: Array, kb: int, *, interpret: bool = False) -> Array:
    """x2d: (nb, block) -- nb % TILE_NB == 0, block % 128 == 0."""
    nb, block = x2d.shape
    assert nb % TILE_NB == 0 and block % 128 == 0, (nb, block)
    grid = (nb // TILE_NB,)
    return pl.pallas_call(
        functools.partial(_block_topk_kernel, kb=kb),
        grid=grid,
        in_specs=[pl.BlockSpec((TILE_NB, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_NB, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), x2d.dtype),
        interpret=interpret,
    )(x2d)


def _efbv_update_kernel(g_ref, h_ref, d_ref, h_out_ref, *, kb: int, lam: float):
    """Fused: d = block_topk(g - h); h_new = h + lam * d.  One HBM pass over
    (g, h) instead of three (delta materialize, compress, h update).  lam is
    a compile-time constant (it comes from the paper's closed-form lam*)."""
    g = g_ref[...]
    h = h_ref[...]
    # subtract in f32: bit-identical between interpret mode (which emulates
    # bf16 arithmetic in f32) and real TPU lowering
    delta = g.astype(jnp.float32) - h.astype(jnp.float32)
    mask = _select_mask(jnp.abs(delta), kb)
    d = (delta * mask).astype(g.dtype)
    d_ref[...] = d
    h_out_ref[...] = (h.astype(jnp.float32) + lam * d.astype(jnp.float32)
                      ).astype(h.dtype)


def efbv_update_pallas(g2d: Array, h2d: Array, lam: float, kb: int, *,
                       interpret: bool = False):
    nb, block = g2d.shape
    assert nb % TILE_NB == 0 and block % 128 == 0, (nb, block)
    grid = (nb // TILE_NB,)
    spec = pl.BlockSpec((TILE_NB, block), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_efbv_update_kernel, kb=kb, lam=float(lam)),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((nb, block), g2d.dtype),
                   jax.ShapeDtypeStruct((nb, block), h2d.dtype)),
        interpret=interpret,
    )(g2d, h2d)
