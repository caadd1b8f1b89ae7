"""Grouped matrix products for the MoE layer's held experts.

``gmm(lhs, rhs, group_sizes)``: the rows of ``lhs`` (M, K) are sorted by
group; group g owns ``group_sizes[g]`` consecutive rows and multiplies them
by ``rhs[g]`` (K, N).  ``group_sizes`` has one entry more than ``rhs`` has
groups: the last counts the rows that belong to no held group (assignments
to experts that live on other chips), which come out as zeros, as do rows
past the total.  Forward and backward (the row gradient by a transposed
``gmm``, the weight gradient by ``tgmm``) run the Pallas kernels of
``jax.experimental.pallas.ops.tpu.megablox`` on the TPU.  Their grids visit
only the tiles that hold rows, so the work follows the routing, and a group
with no rows gets an exactly-zero weight gradient.  Off the TPU the same
custom VJP runs a jnp oracle, one masked product per group.

Inside the trainers' shard_map a Pallas call must carry the operands' vma
and may not see a GSPMD-auto axis; the kernels therefore run in a nested
shard_map over the auto axes without the vma check, and their outputs are
marked as varying like the operands (all operands are first brought to one
vma, so the custom VJP's cotangents match its primals).  Where ``model`` is
one of those auto axes and its size divides the groups, the groups are
expert-parallel over it: each model shard takes its slice of ``rhs`` and
runs only its own groups (megablox's ``group_offset``), the rows of the
others come out as zeros, and a ``psum`` over ``model`` puts the row
results together; the weight gradient stays sharded like ``rhs``.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

Array = jax.Array

#: the mesh axis the held experts are expert-parallel over
MODEL = "model"

_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

#: ``impl`` values: the kernel compiled, the kernel in interpret mode, the
#: jnp oracle; ``None`` picks the kernel on the TPU (interpret mode under
#: ``repro.analysis.sanitize``) and the oracle elsewhere
IMPLS = ("kernel", "interpret", "oracle")


def tiling(m: int, k: int, n: int):
    """(tm, tk, tn) of one call: 256 rows a tile (or the largest power of two
    from 128 down that divides m), the whole of K and N up to 2,048."""
    tm = next(t for t in (256, 128, 64, 32, 16, 8) if m % t == 0)
    return tm, (k if k <= 2048 else 512), (n if n <= 2048 else 512)


def _vma(*xs):
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _varying(x, vma):
    missing = tuple(sorted(vma - jax.typeof(x).vma))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _expert_shards(groups: int) -> int:
    """Model shards the ``groups`` split over: the size of a GSPMD-auto
    ``model`` axis that divides them, else 1."""
    mesh = compat.abstract_mesh()
    if MODEL not in compat.auto_axes_of(mesh):
        return 1
    n = mesh.shape[MODEL]
    return n if groups % n == 0 else 1


def _group_offset(local_groups: int, shards: int):
    """The first group of this model shard (None: all groups are here)."""
    if shards == 1:
        return None
    return jax.lax.axis_index(MODEL).astype(jnp.int32) * local_groups


def _mosaic(fn, vma, operands, in_specs=P(), out_specs=P()):
    auto = compat.auto_axes_of(compat.abstract_mesh())
    if auto:
        fn = jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                           axis_names=set(auto), check_vma=False)
    return _varying(fn(*operands), vma)


def _oracle(lhs, rhs, group_sizes, *, transpose_rhs=False):
    ends = jnp.cumsum(group_sizes)[:rhs.shape[0]]
    starts = ends - group_sizes[:rhs.shape[0]]
    row = jnp.arange(lhs.shape[0])[:, None]
    w = rhs.swapaxes(1, 2) if transpose_rhs else rhs
    out = jnp.zeros((lhs.shape[0], w.shape[2]), jnp.float32)
    for g in range(w.shape[0]):
        part = jnp.dot(lhs, w[g], preferred_element_type=jnp.float32)
        out = out + jnp.where((row >= starts[g]) & (row < ends[g]), part, 0.0)
    return out.astype(lhs.dtype)


def _oracle_t(lhs_t, rhs, group_sizes, groups):
    """Per-group lhs_t[:, rows of g] @ rhs[rows of g] -> (groups, K, N)."""
    ends = jnp.cumsum(group_sizes)[:groups]
    starts = ends - group_sizes[:groups]
    row = jnp.arange(rhs.shape[0])[:, None]
    return jnp.stack([
        jnp.dot(lhs_t, jnp.where((row >= starts[g]) & (row < ends[g]), rhs, 0),
                preferred_element_type=jnp.float32)
        for g in range(groups)]).astype(lhs_t.dtype)


def _forward(impl, lhs, rhs, group_sizes, transpose_rhs=False):
    if impl == "oracle":
        return _oracle(lhs, rhs, group_sizes, transpose_rhs=transpose_rhs)
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    shards = _expert_shards(rhs.shape[0])

    def call(lhs, rhs, group_sizes):
        out = _megablox.gmm(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=tiling(m, k, n), transpose_rhs=transpose_rhs,
            group_offset=_group_offset(rhs.shape[0], shards),
            interpret=impl == "interpret")
        return jax.lax.psum(out, MODEL) if shards > 1 else out

    experts = P(MODEL) if shards > 1 else P()
    return _mosaic(call, _vma(lhs, rhs, group_sizes), (lhs, rhs, group_sizes),
                   in_specs=(P(), experts, P()))


def _weight_grad(impl, lhs, grad, group_sizes, groups, dtype):
    if impl == "oracle":
        return _oracle_t(lhs.swapaxes(0, 1), grad, group_sizes, groups)
    m, k = lhs.shape
    shards = _expert_shards(groups)
    local = groups // shards

    def call(lhs_t, grad, group_sizes):
        return _megablox.tgmm(
            lhs_t, grad, group_sizes, preferred_element_type=dtype,
            tiling=tiling(m, k, grad.shape[1]), num_actual_groups=local,
            group_offset=_group_offset(local, shards),
            interpret=impl == "interpret")

    return _mosaic(call, _vma(lhs, grad, group_sizes),
                   (lhs.swapaxes(0, 1), grad, group_sizes),
                   out_specs=P(MODEL) if shards > 1 else P())


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gmm(impl, lhs, rhs, group_sizes):
    return _forward(impl, lhs, rhs, group_sizes)


def _gmm_fwd(impl, lhs, rhs, group_sizes):
    return _forward(impl, lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(impl, res, grad):
    lhs, rhs, group_sizes = res
    grad = grad.astype(lhs.dtype)
    d_lhs = _forward(impl, grad, rhs, group_sizes, transpose_rhs=True)
    d_rhs = _weight_grad(impl, lhs, grad, group_sizes, rhs.shape[0], rhs.dtype)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs: Array, rhs: Array, group_sizes: Array,
        impl: str | None = None) -> Array:
    """Grouped product, (M, K) x (G, K, N) -> (M, N) in ``lhs.dtype``;
    ``group_sizes``: (G + 1,) int32, the last entry the rows of no group."""
    if impl is None:
        from repro.analysis import sanitize

        # sanitize mode runs the kernel in interpret mode on the TPU too
        impl = ("oracle" if jax.default_backend() != "tpu" else
                "interpret" if sanitize.active() else "kernel")
    if impl not in IMPLS:
        raise ValueError(f"gmm impl {impl!r}; known: {IMPLS}")
    if group_sizes.shape != (rhs.shape[0] + 1,):
        raise ValueError(f"group_sizes {group_sizes.shape} must count "
                         f"{rhs.shape[0]} groups and the rows of none")
    vma = _vma(lhs, rhs, group_sizes)
    lhs, rhs, group_sizes = (_varying(x, vma) for x in (lhs, rhs, group_sizes))
    return _gmm(impl, lhs, rhs.astype(lhs.dtype), group_sizes)
