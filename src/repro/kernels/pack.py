"""Pallas TPU kernels: fused compress-AND-pack for the wire codecs.

The unfused hot path of any compressor costs three HBM passes and
materializes a dense tensor the theory says should never exist on the wire:

    d      = C(g - h)                 # dense (nb, block) write
    h     <- h + lam * d              # dense read + write
    payload = pack(d)                 # dense read, payload write

Three codecs get a fused kernel here, each with the same property -- the
dense compressed d lives only in VMEM, never in HBM:

  * block-top-k (`_pack_update_kernel`): one pass over (g, h) emitting the
    (values, block-local indices) payload and h_out.
  * rand-k (`_randk_update_kernel`): the k kept positions are
    data-INdependent, so they are drawn outside and prefetched to SMEM; the
    kernel does the dense-free h <- h + lam * d pass in one sweep, and the
    payload values are an O(k) gather outside.
  * QSGD (`_qsgd_pack_kernel`): after a scalar norm reduction, one pass over
    (g, h, uniforms) emits the int8/int16 quantized level stream and h_out
    -- the dequantized d is built in VMEM for the h update and discarded.

The decode side has one kernel: the block-top-k decode-sum
(`_block_unpack_sum_kernel`) rebuilds each dense block from its own
(value, index) pairs by compares against a position iota -- no sort, no
scatter.

All kernels reproduce the jnp oracles' f32 arithmetic op-for-op, which is
what makes the payloads bit-identical across oracle / interpret / compiled
backends -- the differential harness in tests/harness.py pins this.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_topk import TILE_NB

Array = jax.Array

QS_TILE_NB = 32  # rows per grid step for int8 outputs (min int8 tile: 32x128)
# rows per grid step of the streaming pack kernel: its payload is DMA'd
# transposed, (kb, rows), and a DMA's minor dimension must fill 128 lanes
STREAM_TILE_NB = 128
# candidate rows per grid step of the non-streaming pack kernel, widest
# first (``pack_tile``): multiples of 128, so the transposed tile and the
# (kb, rows) payload block are lane-dense
PACK_TILES = (1024, 512, 256, 128)
# VMEM bytes per element of a tile row: g, h and h_out double-buffered
# (f32) and about six (block, rows) f32 intermediates of the selection
_PACK_ROW_BYTES = 3 * 2 * 4 + 6 * 4
# VMEM bytes per kept entry of a tile row: the f32 values and int32 index
# payload blocks double-buffered, and the kb (1, rows) rounds of each held
# on 8 sublanes until they are concatenated.  Against Mosaic's own scoped
# allocation for v5e (35-38 B per element, about 40 per kept entry, at
# blocks 256-4096 and kb 16-256) the estimate is an upper bound
_PACK_KB_BYTES = 2 * 2 * 4 + 2 * 8 * 4
# v5e's default scoped VMEM limit: the widest tile whose working set fits
# it is taken, and the narrowest tile asks for more where it needs more
SCOPED_VMEM = 16 * 2 ** 20
# the most of v5e's 128 MiB of VMEM a pack kernel asks for: a block so wide
# that even the narrowest tile needs more has no kernel
PACK_VMEM_MAX = 96 * 2 ** 20


def _out(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel output varying over the mesh axes its operands vary over:
    inside the trainers' shard_map every output must carry its vma."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _select_block_topk(delta_t, kb: int):
    """The shared selection core of both pack kernels, on a transposed tile:
    each block runs down axis 0 of the (block, rows) ``delta_t``, one block
    per lane.  Returns the lane-dense payloads vals f32 (kb, rows) and cols
    f32 (kb, rows), and selected bool (block, rows).

    A round's reduction over a block is an elementwise max (or min) across
    the tile's vregs and one 8-sublane reduce, and the rows are independent
    lanes: a wide tile gives each round many vregs of independent work.
    One body keeps the streaming and non-streaming kernels bit-identical by
    construction."""
    mag = jnp.abs(delta_t)
    block = mag.shape[0]
    # column indices compared in f32 (exact for block < 2**24), the same
    # compares the jnp oracle's tie-breaking is pinned against.  Mosaic's
    # tpu.iota is integer-only, so the iota is built as int32 and converted;
    # cumsum has no Mosaic lowering, hence the min-reduction tie-break below
    cols = jax.lax.broadcasted_iota(jnp.int32, mag.shape, 0).astype(
        jnp.float32)

    # python-unrolled over the (static, small) kb: payload rows are
    # assembled with one concatenate -- loop-carried dynamic_update_slice has
    # no Mosaic lowering, and the unroll keeps everything elementwise+reduce.
    # score carries the selection: a kept entry's score is -inf, which no
    # magnitude is, so the selected mask is (score == -inf) at the end
    score = mag
    v_rows, c_rows = [], []
    for _ in range(kb):
        m = jnp.max(score, axis=0, keepdims=True)
        # exact first-index tie-breaking == jax.lax.top_k's stable order:
        # the smallest column index among the maxima.  m == -inf is the
        # all-selected block (kb == block), spelled as a compare because
        # isfinite has no Pallas TPU lowering; cmin = block keeps nothing
        cmin = jnp.min(jnp.where(score == m, cols, float(block)), axis=0,
                       keepdims=True)
        cmin = jnp.where(m != -jnp.inf, cmin, float(block))
        first = cols == cmin
        # exactly one column is first where cmin < block, none elsewhere.
        # The value is taken by a max over -inf fill, which returns it bit
        # for bit (a sum's +0.0 start would turn a kept -0.0 into +0.0)
        kept = cmin < block
        v = jnp.max(jnp.where(first, delta_t, -jnp.inf), axis=0,
                    keepdims=True)
        v_rows.append(jnp.where(kept, v, 0.0))
        c_rows.append(jnp.where(kept, cmin, 0.0))
        score = jnp.where(first, -jnp.inf, score)
    return (jnp.concatenate(v_rows, axis=0),
            jnp.concatenate(c_rows, axis=0), score == -jnp.inf)


def _pack_update_kernel(g_ref, h_ref, vals_ref, idx_ref, h_out_ref, *,
                        kb: int, lam: float):
    """One (rows, block) slab of g and h: selection on the transposed tile,
    the payload written lane-dense as a (kb, rows) block, h_out in the
    slab's own layout."""
    g = g_ref[...]
    h = h_ref[...]
    # subtract in f32: bit-identical between interpret mode and TPU lowering
    delta_t = (g.astype(jnp.float32) - h.astype(jnp.float32)).T
    vals, cols, selected = _select_block_topk(delta_t, kb)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = cols.astype(jnp.int32)
    d = jnp.where(selected, delta_t, 0.0).T
    h_out_ref[...] = (h.astype(jnp.float32) + lam * d).astype(h_out_ref.dtype)


def _pack_update_stream_kernel(g_ref, h_ref, vals_ref, idx_ref, h_out_ref,
                               v_scr, i_scr, sems, *, kb: int, lam: float):
    """Async-copy variant: the payload slab is computed into VMEM scratch and
    DMA'd toward its HBM output (vals_ref/idx_ref live in pl.ANY) while
    the h update still computes -- the wire bytes of this grid step stream
    out under the remaining compute instead of waiting for the step's
    epilogue.  Mosaic refuses a DMA whose minor dimension is narrower than
    the 128-lane tiling, which the lane-dense (kb, rows) payload meets.
    Same body as the non-streaming kernel, so the same bits."""
    t = pl.program_id(0)
    g = g_ref[...]
    h = h_ref[...]
    delta_t = (g.astype(jnp.float32) - h.astype(jnp.float32)).T
    vals, cols, selected = _select_block_topk(delta_t, kb)
    v_scr[...] = vals.astype(v_scr.dtype)
    i_scr[...] = cols.astype(jnp.int32)
    rows = v_scr.shape[1]
    v_dma = pltpu.make_async_copy(
        v_scr, vals_ref.at[:, pl.ds(t * rows, rows)], sems.at[0])
    i_dma = pltpu.make_async_copy(
        i_scr, idx_ref.at[:, pl.ds(t * rows, rows)], sems.at[1])
    v_dma.start()
    i_dma.start()
    d = jnp.where(selected, delta_t, 0.0).T
    h_out_ref[...] = (h.astype(jnp.float32) + lam * d).astype(h_out_ref.dtype)
    # the wait doubles as the write-after-read guard: the next grid step may
    # not overwrite the scratch slabs until this step's copies have landed
    v_dma.wait()
    i_dma.wait()


def pack_vmem_bytes(tile: int, block: int, kb: int) -> int:
    """Upper estimate of the VMEM one grid step of either pack kernel holds,
    for ``tile`` rows of ``block`` entries keeping ``kb`` of each."""
    return tile * (block * _PACK_ROW_BYTES + kb * _PACK_KB_BYTES)


def pack_vmem_gap(block: int, kb: int) -> Optional[str]:
    """Why the pack kernels cannot take ``block``/``kb`` within
    PACK_VMEM_MAX, or None.  The narrowest legal tile is 128 rows (a leaf of
    fewer blocks takes them all, which needs less)."""
    need = pack_vmem_bytes(STREAM_TILE_NB, block, kb)
    if need > PACK_VMEM_MAX:
        return (f"needs {need / 2 ** 20:.0f} MiB of VMEM for block {block}, "
                f"kb {kb} at {STREAM_TILE_NB} rows, over its "
                f"{PACK_VMEM_MAX // 2 ** 20} MiB")
    return None


def pack_tile(nb: int, block: int, kb: int) -> int:
    """Blocks (rows) per grid step of the non-streaming pack kernel: the
    whole leaf where it has at most 128 blocks (a block equal to the full
    array dimension), else the widest of ``PACK_TILES`` that the leaf fills
    and whose working set fits ``SCOPED_VMEM``, else 128 rows, the
    narrowest the lane-dense payload allows.  ``pack_update_pallas`` raises
    the scoped VMEM limit to the tile's working set where that passes the
    default.  The last grid step may be ragged: rows are independent lanes
    of the transposed tile, so out-of-bounds rows touch no kept entry, and
    their outputs are dropped."""
    return widest_tile(nb, lambda t: pack_vmem_bytes(t, block, kb))


def widest_tile(nb: int, vmem_bytes) -> int:
    """``nb`` where it is at most 128 (a block equal to the full array
    dimension), else the widest of ``PACK_TILES`` that ``nb`` fills and
    whose ``vmem_bytes(tile)`` fits ``SCOPED_VMEM``, else the narrowest."""
    if nb <= 128:
        return nb
    for tile in PACK_TILES:
        if tile <= nb and vmem_bytes(tile) <= SCOPED_VMEM:
            return tile
    return PACK_TILES[-1]


def pack_update_pallas(g2d: Array, h2d: Array, lam: float, kb: int, *,
                       interpret: bool = False, stream: bool = False):
    """g2d/h2d: (nb, block) with block % 128 == 0 (and nb % STREAM_TILE_NB
    == 0 with ``stream=True``); the tile comes from ``pack_tile``.

    Returns (values (nb, kb), indices (nb, kb) int32, h_new (nb, block)).
    The kernels emit the payload lane-dense as (kb, nb), transposed here.
    ``stream=True`` takes the async-copy kernel (payload DMA overlaps the h
    update); results are bit-identical to the non-streaming kernel.
    Raises ValueError where ``pack_vmem_gap`` names a gap.
    """
    nb, block = g2d.shape
    assert block % 128 == 0, (nb, block)
    assert 0 < kb <= block, (kb, block)
    gap = pack_vmem_gap(block, kb)
    if gap is not None:
        raise ValueError(gap)
    if stream:
        tile = STREAM_TILE_NB
        assert nb % tile == 0, (nb, tile)
        kernel = _pack_update_stream_kernel
        payload = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((kb, tile), g2d.dtype),
                   pltpu.VMEM((kb, tile), jnp.int32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        tile = pack_tile(nb, block, kb)
        kernel = _pack_update_kernel
        payload = pl.BlockSpec((kb, tile), lambda i: (0, i))
        scratch = []
    slab = pl.BlockSpec((tile, block), lambda i: (i, 0))
    vmem_limit = max(SCOPED_VMEM, pack_vmem_bytes(tile, block, kb))
    vals_t, idx_t, h_new = pl.pallas_call(
        functools.partial(kernel, kb=kb, lam=float(lam)),
        grid=(pl.cdiv(nb, tile),),
        in_specs=[slab, slab],
        out_specs=(payload, payload, slab),
        out_shape=(_out((kb, nb), g2d.dtype, g2d, h2d),
                   _out((kb, nb), jnp.int32, g2d, h2d),
                   _out((nb, block), h2d.dtype, g2d, h2d)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(g2d, h2d)
    return vals_t.T, idx_t.T, h_new


# ---------------------------------------------------------------------------
# rand-k: dense-free h update with SMEM-prefetched indices
# ---------------------------------------------------------------------------

def _randk_update_kernel(idx_ref, g_ref, h_ref, h_out_ref, *, k: int,
                         scale: float, lam: float):
    """h_out = h + lam * ((g - h) masked to the k SMEM indices) * scale.

    idx_ref holds the k selected flat positions (into the padded row-major
    (nr, cols) grid) in SMEM; membership of this tile is rebuilt as an
    equality test against the tile-linear f32 iota (exact for size < 2**24,
    and out-of-tile positions can never collide with an in-tile linear
    index).  The dense rand-k output d exists only in VMEM.
    """
    t = pl.program_id(0)
    g = g_ref[...]
    h = h_ref[...]
    delta = g.astype(jnp.float32) - h.astype(jnp.float32)
    rows, cols = delta.shape
    # int32 iotas (Mosaic's tpu.iota is integer-only), compared in f32
    lin = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * cols
           + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
           ).astype(jnp.float32)
    base = t * (rows * cols)

    def body(j, mask):
        local = (idx_ref[j] - base).astype(jnp.float32)
        return jnp.maximum(mask, (lin == local).astype(jnp.float32))

    mask = jax.lax.fori_loop(0, k, body, jnp.zeros((rows, cols), jnp.float32))
    # rounding chain must match the oracle's h + lam * decode(payload):
    # delta * scale rounds first (those ARE the wire values), then lam * d.
    # The select between the two multiplies stops XLA from reassociating the
    # constant pair into one (lam * scale) product the eager oracle never
    # forms -- adjacent constant muls DO get merged on the CPU backend.
    vals_dense = delta * scale
    d = jnp.where(mask > 0, vals_dense, 0.0)
    h_out_ref[...] = (h.astype(jnp.float32) + lam * d).astype(h_out_ref.dtype)


def randk_update_pallas(g2d: Array, h2d: Array, idx: Array, scale: float,
                        lam: float, *, interpret: bool = False) -> Array:
    """g2d/h2d: (nr, cols) with nr % TILE_NB == 0, cols % 128 == 0; idx: (k,)
    int32 flat positions.  Returns h_new (nr, cols) in h2d's dtype."""
    nr, cols = g2d.shape
    assert nr % TILE_NB == 0 and cols % 128 == 0, (nr, cols)
    # f32 position compare is exact up to 2**24 inclusive (max linear index
    # is nr*cols - 1); <= admits every unpadded size < 2**24 after padding
    assert nr * cols <= 2 ** 24, (nr, cols)
    (k,) = idx.shape
    grid = (nr // TILE_NB,)
    slab = pl.BlockSpec((TILE_NB, cols), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_randk_update_kernel, k=k, scale=float(scale),
                          lam=float(lam)),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), slab, slab],
        out_specs=slab,
        out_shape=_out((nr, cols), h2d.dtype, idx, g2d, h2d),
        interpret=interpret,
    )(idx, g2d, h2d)


# ---------------------------------------------------------------------------
# QSGD: fused quantize-and-pack (int8/int16 level stream + h update)
# ---------------------------------------------------------------------------

def _qsgd_pack_kernel(norm_ref, g_ref, h_ref, u_ref, lvl_ref, h_out_ref, *,
                      s: int, lam: float):
    """One pass over (g, h, u): emits the signed level stream and
    h_out = h + lam * dequant(levels); the dense dequantized d stays in
    VMEM.  Op order matches QSGD.__call__ / QsgdQuant exactly."""
    g = g_ref[...]
    h = h_ref[...]
    u = u_ref[...]
    delta = g.astype(jnp.float32) - h.astype(jnp.float32)
    norm = norm_ref[0, 0]
    safe = jnp.where(norm > 0, norm, 1.0)
    a = jnp.abs(delta)
    level = a / safe * s
    low = jnp.floor(level)
    up = (u < (level - low)).astype(jnp.float32)
    # sign spelled as compares: jnp.sign lowers poorly on some Mosaic
    # vintages, and the two differ only at +-0 where every product below is
    # a zero of some sign anyway
    sgn = jnp.where(delta > 0, 1.0, jnp.where(delta < 0, -1.0, 0.0))
    lvq = low + up
    lvl_ref[...] = (sgn * lvq).astype(lvl_ref.dtype)
    # rounding chain matches the oracle decode exactly: reciprocal multiply
    # (jit rewrites /s inexactly) and a VECTOR-predicate select feeding the
    # tail -- scalar-predicate selects get simplified away, leaving a
    # mul+add pair that LLVM contracts into an FMA the eager oracle never
    # performs (see the rand-k kernel for the same constraint)
    dq = jnp.where(lvq > 0, (norm * sgn) * (lvq * (1.0 / s)), 0.0)
    h_out_ref[...] = (h.astype(jnp.float32) + lam * dq).astype(h_out_ref.dtype)


def qsgd_pack_update_pallas(g2d: Array, h2d: Array, u2d: Array, norm: Array,
                            s: int, lam: float, *, interpret: bool = False):
    """g2d/h2d/u2d: (nr, cols) with nr % QS_TILE_NB == 0, cols % 128 == 0;
    norm: (1, 1) f32.  Returns (levels (nr, cols) int8/int16, h_new)."""
    nr, cols = g2d.shape
    assert nr % QS_TILE_NB == 0 and cols % 128 == 0, (nr, cols)
    lvl_dtype = jnp.int8 if s <= 127 else jnp.int16
    grid = (nr // QS_TILE_NB,)
    slab = pl.BlockSpec((QS_TILE_NB, cols), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_qsgd_pack_kernel, s=int(s), lam=float(lam)),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), slab, slab, slab],
        out_specs=(slab, slab),
        out_shape=(_out((nr, cols), lvl_dtype, norm, g2d, h2d, u2d),
                   _out((nr, cols), h2d.dtype, norm, g2d, h2d, u2d)),
        interpret=interpret,
    )(norm, g2d, h2d, u2d)


# ---------------------------------------------------------------------------
# block-top-k decode-sum: the dense sum of (worker-stacked) payloads
# ---------------------------------------------------------------------------
#
# The block-top-k payload's indices are local to their block, so every dense
# block is rebuilt from its own ``n * kb`` (value, index) pairs alone: no
# global sort of the pairs and no serialized scatter, which is what XLA's TPU
# lowering of ``zeros.at[rows, idx].add(vals)`` costs.
#
# The kernel reads the payload transposed, ``(n * kb, nb)`` with one block
# per lane, and keeps a ``(block, rows)`` accumulator with one position per
# sublane: each term is a compare of its index row against an int32 iota of
# the positions, a select of its value row and an add, so a pair broadcasts
# along sublanes and never crosses lanes.  The tile is transposed in VMEM to
# the lane-dense ``(rows, block)`` slab it writes.  On a TPU v5e these
# whole-tile terms ran 1.2-3x faster than an explicit (128, 128) chunking
# that holds the accumulator in vregs.
#
# Terms go in ascending worker order, then ascending slot order: the order
# the CPU scatter adds them in, so the sums are bitwise equal to the jnp
# oracle's (``wire.scatter_add``).  Terms that miss a position add ``+0.0``,
# which leaves every sum but ``-0.0`` unchanged, and the accumulator starts
# at ``+0.0`` as the scatter's zeros do, so it never holds ``-0.0``.

# VMEM bytes per element of a tile: the (rows, block) output
# double-buffered, and about five (block, rows) 4-byte intermediates (the
# accumulator, the position iota, a term's mask and select, the transpose)
_UNPACK_ROW_BYTES = 2 * 4 + 5 * 4
# VMEM bytes per term of a tile row: the f32 value and int32 index payload
# blocks, double-buffered
_UNPACK_TERM_BYTES = 2 * 2 * 4


def unpack_vmem_bytes(tile: int, block: int, terms: int) -> int:
    """Upper estimate of the VMEM one grid step holds, for ``tile`` blocks
    of ``block`` positions rebuilt from ``terms`` pairs each."""
    return tile * (block * _UNPACK_ROW_BYTES + terms * _UNPACK_TERM_BYTES)


def unpack_gap(block: int, terms: int) -> Optional[str]:
    """Why the kernel cannot take ``block``/``terms``, or None: the block
    must fill 128 lanes, and the narrowest legal tile (128 blocks) must fit
    ``PACK_VMEM_MAX``."""
    if block % 128:
        return f"block {block} is not a multiple of 128"
    need = unpack_vmem_bytes(STREAM_TILE_NB, block, terms)
    if need > PACK_VMEM_MAX:
        return (f"needs {need / 2 ** 20:.0f} MiB of VMEM for block {block}, "
                f"{terms} terms at {STREAM_TILE_NB} blocks, over its "
                f"{PACK_VMEM_MAX // 2 ** 20} MiB")
    return None


def _block_unpack_sum_kernel(vals_ref, idx_ref, out_ref):
    """One tile: vals/idx (terms, rows) -> out (rows, block), summed.  The
    terms are python-unrolled: on a TPU v5e a loop over the workers ran
    a four-worker sum 2.6-3x slower, for a third of the compile time."""
    terms, rows = vals_ref.shape
    block = out_ref.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (block, rows), 0)
    acc = jnp.zeros((block, rows), jnp.float32)
    for j in range(terms):  # worker-major, then slot
        hit = pos == idx_ref[pl.ds(j, 1), :]
        acc = acc + jnp.where(hit, vals_ref[pl.ds(j, 1), :], 0.0)
    out_ref[...] = acc.T


def block_unpack_sum(vals_t: Array, idx_t: Array, block: int, *,
                     interpret: bool = False) -> Array:
    """vals_t f32 / idx_t int32: the transposed payload ``(n * kb, nb)``,
    term ``w * kb + s`` being worker w's slot s.  Returns the dense
    ``(nb, block)`` f32 sum.  Raises ValueError where ``unpack_gap`` names
    a gap.  ``ops.block_decode_sum`` jits it under this name, which XLA
    gives the kernel's instruction (``%block_unpack_sum.N``)."""
    terms, nb = vals_t.shape
    assert idx_t.shape == (terms, nb), (idx_t.shape, vals_t.shape)
    gap = unpack_gap(block, terms)
    if gap is not None:
        raise ValueError(gap)
    # pack_tile's rule under this kernel's VMEM estimate; a ragged last step
    # is safe, blocks being independent lanes and the rows past nb dropped
    tile = widest_tile(nb, lambda t: unpack_vmem_bytes(t, block, terms))
    payload = pl.BlockSpec((terms, tile), lambda i: (0, i))
    vmem_limit = max(SCOPED_VMEM, unpack_vmem_bytes(tile, block, terms))
    return pl.pallas_call(
        _block_unpack_sum_kernel,
        grid=(pl.cdiv(nb, tile),),
        in_specs=[payload, payload],
        out_specs=pl.BlockSpec((tile, block), lambda i: (i, 0)),
        out_shape=_out((nb, block), jnp.float32, vals_t, idx_t),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(vals_t.astype(jnp.float32), idx_t.astype(jnp.int32))
