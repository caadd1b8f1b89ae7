"""Compressed cross-worker aggregation: the master step of Algorithm 1 as
TPU collectives (DESIGN §3.2).

Two-phase structure (sound under shard_map's static replication checker):

  phase 1 -- *inside* shard_map (manual over the worker axes, GSPMD-auto over
  'model'): each worker compresses its gradient innovation and updates its
  control variate.  Everything returned is worker-varying (stacked on a
  leading axis sharded over (pod, data)).

  phase 2 -- *outside* shard_map, plain GSPMD: the master average d_bar is a
  reduction over the worker-sharded leading axis; XLA lowers it to the actual
  wire collective, which is what the roofline reads:

    dense_psum       -> all-reduce of the dense delta (d words / worker);
                        paper-faithful semantics, no byte savings.
    sparse_allgather -> all-gather of the compressor's wire-codec payload
                        (block/flat (values, indices), bit-packed signs,
                        quantized streams -- see repro.distributed.wire) +
                        local decode-sum: the TPU-native realization of the
                        paper's "bits per node proportional to t*k"
                        accounting, for EVERY compressor in the zoo.

Both modes are bit-identical given the same compressor draws (tests assert
this): the wire format changes, Algorithm 1 does not.

Federated rounds (per-round client sampling) thread a per-worker scalar
``mask`` through :func:`compress_local`: an absent worker's message is gated
to decode-zero and its control variate stays stale, so :func:`combine_global`
needs no variant -- the 1/n mean over pre-masked messages IS the paper's
aggregation restricted to the sampled subset, preserving the running-average
invariant h_avg = (1/n) sum_i h_i.  See
docs/algorithms.md#partial-participation--stochastic-gradients for the mask
semantics and docs/wire_format.md for the payload layouts and bit accounting.
"""

from __future__ import annotations

import contextlib
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.efbv import EFBV, Downlink
from repro.distributed import wire

PyTree = Any
AGG_MODES = ("dense_psum", "sparse_allgather")


# --------------------------------------------------------------------------
# phase 1: worker-local (runs inside shard_map)
# --------------------------------------------------------------------------

def compress_local(
    algo: EFBV,
    key: Optional[jax.Array],
    grads: PyTree,
    h_local: PyTree,
    *,
    mode: str = "dense_psum",
    wire_dtype: str = "float32",
    mask: Optional[jax.Array] = None,
    worker: Optional[jax.Array] = None,
    stream: bool = False,
) -> Tuple[PyTree, PyTree]:
    """d_i = C_i(grad_i - h_i); h_i <- h_i + lam d_i.

    Returns (message, h_local_new) where message is either the dense d_i
    (mode=dense_psum) or the per-leaf wire-codec payload
    (mode=sparse_allgather; every compressor declares one -- see
    repro.distributed.wire).

    ``mask`` is this worker's scalar participation indicator for the round
    (federated mode, docs/algorithms.md): at mask = 0 the message is gated
    to decode-zero (wire.LeafCodec.mask_message / a zeroed dense d_i) and
    h_i stays STALE; at mask = 1 both gates are bitwise identities, and
    ``mask=None`` (full participation) skips them entirely.

    ``worker`` is this worker's (traced) linear index, required when
    ``algo.fleet`` is set: a heterogeneous fleet selects worker i's own
    compressor with lax.switch.  Mixed fleets need a uniform message shape,
    so they run under dense_psum only; the homogeneous fast paths are
    untouched (EFBV.make collapses a uniform fleet to fleet=None).

    ``stream=True`` (the pipelined trainer) asks codecs with an async-copy
    fused kernel to start DMAing the payload toward HBM while the control
    variate update still computes; payload bits are identical either way.
    """
    if mode not in AGG_MODES:
        raise ValueError(f"mode {mode!r} not in {AGG_MODES}")
    if algo.fleet is not None:
        if mode != "dense_psum":
            raise ValueError(
                "mixed fleets need a uniform per-worker message shape; "
                "mode='sparse_allgather' cannot stack heterogeneous "
                "payloads -- use mode='dense_psum'")
        if worker is None:
            raise ValueError("mixed-fleet compress_local needs the worker "
                             "index (worker=)")

    leaves, treedef = jax.tree.flatten(grads)
    h_leaves = treedef.flatten_up_to(h_local)
    fmt = wire.tree_format_for(algo.compressor, grads, wire_dtype=wire_dtype,
                               rules=algo.leaf_rules) \
        if mode == "sparse_allgather" else None
    if algo.leaf_rules and algo.fleet is None:
        # dense path under per-leaf rules: each leaf runs its own resolved
        # (clamped) compressor -- the dense twin of the TreeWire codecs
        dense_comps = [wire.clamp_for_leaf(
            wire.resolve_leaf(algo.leaf_rules, p, algo.compressor),
            int(g.size)) for p, g in zip(wire.leaf_paths(grads), leaves)]
    else:
        dense_comps = [algo.compressor] * len(leaves)
    msgs, h_new_leaves = [], []
    for j, (g_leaf, h_leaf) in enumerate(zip(leaves, h_leaves)):
        kj = None if key is None else jax.random.fold_in(key, j)
        if fmt is not None:
            # fused compress-and-pack through the leaf's codec: emits the
            # payload AND EFBV.worker_update (h <- h + lam d) in one pass;
            # codecs with a Pallas kernel (block-top-k, rand-k, QSGD) never
            # materialize the dense d_i in HBM.
            payload, h_leaf_new = wire.encode_update(
                fmt.leaves[j], kj, g_leaf, h_leaf, algo.lam, stream=stream)
            if mask is not None:
                payload = fmt.leaves[j].mask_message(payload, mask)
            msgs.append(payload)
        else:
            delta = g_leaf - h_leaf
            if algo.fleet is not None:
                # worker-indexed dispatch: every member's program is traced,
                # the switch picks this worker's at run time (dense outputs
                # share one shape, so the branches unify)
                if kj is None:
                    branches = tuple((lambda dl, c=c: c(None, dl))
                                     for c in algo.fleet)
                    d_leaf = jax.lax.switch(worker, branches, delta)
                else:
                    branches = tuple((lambda k_, dl, c=c: c(k_, dl))
                                     for c in algo.fleet)
                    d_leaf = jax.lax.switch(worker, branches, kj, delta)
            else:
                d_leaf = dense_comps[j](kj, delta)
            if mask is not None:
                d_leaf_wire = d_leaf * jnp.asarray(mask, d_leaf.dtype)
            else:
                d_leaf_wire = d_leaf
            msgs.append(d_leaf_wire)
            h_leaf_new = algo.worker_update(h_leaf, d_leaf)
        if mask is not None:
            h_leaf_new = jnp.where(mask > 0, h_leaf_new, h_leaf)
        h_new_leaves.append(h_leaf_new)
    h_local_new = jax.tree.unflatten(treedef, h_new_leaves)
    message = jax.tree.unflatten(treedef, msgs) if mode == "dense_psum" else msgs
    return message, h_local_new


# --------------------------------------------------------------------------
# phase 2: master aggregation (runs under GSPMD, outside shard_map)
# --------------------------------------------------------------------------

def combine_global(
    algo: EFBV,
    message_stacked,
    h_avg: PyTree,
    *,
    n_workers: int,
    mode: str = "dense_psum",
    wire_dtype: str = "float32",
    chunks: int = 1,
    mesh=None,
) -> Tuple[PyTree, PyTree]:
    """d_bar = (1/n) sum_i d_i; g = h_avg + nu d_bar; h_avg <- h_avg + lam d_bar.

    ``message_stacked`` carries a leading worker axis of size n sharded over
    (pod, data); the reduction over it IS the wire collective.  With a
    ``mesh``, the sparse_allgather payloads are gathered onto every device
    before the local decode: left to itself the partitioner scatters each
    worker's payload where it lies and all-reduces the DENSE result, so the
    compressed exchange would travel uncompressed.

    ``chunks`` > 1 (the pipelined exchange) splits the worker axis of each
    sparse payload into that many equal slices and decode-sums them in fixed
    ascending order, so XLA can overlap the decode of early chunks with the
    transfer of late ones.  ``chunks=1`` is byte-identical to the historical
    single decode-sum; the dense path ignores chunking (one psum is one
    transfer).

    Named scopes (see train/trainer.py): the gather, or the dense mean, is
    ``efbv.exchange``; the decode-sum and the master update are
    ``efbv.decode``.
    """
    ref_leaves, treedef = jax.tree.flatten(h_avg)
    if mode == "dense_psum":
        with jax.named_scope("efbv.exchange"):
            d_bar = jax.tree.map(lambda d: jnp.mean(d, axis=0),
                                 message_stacked)
    else:
        fmt = wire.tree_format_for(algo.compressor, h_avg,
                                   wire_dtype=wire_dtype,
                                   rules=algo.leaf_rules)
        if mesh is not None:
            with jax.named_scope("efbv.exchange"):
                message_stacked = jax.lax.with_sharding_constraint(
                    message_stacked, NamedSharding(mesh, P()))
        d_bar_leaves = []
        with jax.named_scope("efbv.decode"), _on_mesh(mesh):
            for payload, codec, ref in zip(message_stacked, fmt.leaves,
                                           ref_leaves):
                # payload components carry a leading worker axis; the gather
                # of the payload is the wire, the decode-sum is local (one
                # codec, one layout, one combine for every compressor).
                dense = wire.chunked_decode_sum(codec, payload, chunks)
                d_bar_leaves.append((dense / n_workers).reshape(ref.shape))
        d_bar = jax.tree.unflatten(treedef, d_bar_leaves)
    with jax.named_scope("efbv.decode"):
        g, h_avg_new = algo.master_update(h_avg, d_bar)
    return g, h_avg_new


def _on_mesh(mesh):
    """``mesh`` as the ambient mesh of a decode outside shard_map: the
    decode's Pallas kernel then runs in a shard_map over the mesh's auto
    axes (kernels/ops.py::_unpartitioned), where GSPMD alone could not
    partition it.  No mesh, or one already ambient, changes nothing."""
    if mesh is None or not compat.abstract_mesh().empty:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def ring_allgather(message: PyTree, axis_name, n: int) -> PyTree:
    """All-gather every worker's ``message`` over ``axis_name`` as an n-hop
    ppermute ring, reconstructing the CANONICAL source order.

    Equivalent to ``jax.lax.all_gather(message, axis_name)`` bit-for-bit, but
    exposed as n-1 point-to-point hops so the pipelined trainer's chunked
    decode (:func:`combine_global` with ``chunks`` > 1) can start consuming
    early arrivals while late hops are still in flight.  Each hop h delivers
    the message of worker (i - h) mod n to worker i; writing it at index
    (i - h) mod n restores src order, so every replica sees the SAME stacked
    array and the fixed-order chunked sum stays replica-identical.
    """
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def gather_leaf(leaf):
        bufs = jnp.zeros((n,) + leaf.shape, leaf.dtype)
        cur = leaf
        bufs = bufs.at[idx].set(cur)
        for hop in range(1, n):
            cur = jax.lax.ppermute(cur, axis_name, perm)
            src = (idx - hop) % n
            bufs = bufs.at[src].set(cur)
        return bufs

    return jax.tree.map(gather_leaf, message)


# --------------------------------------------------------------------------
# phase 3: master -> worker broadcast (the downlink channel)
# --------------------------------------------------------------------------

def broadcast_global(
    downlink: Downlink,
    key: Optional[jax.Array],
    params: PyTree,
    w: PyTree,
    *,
    wire_dtype: str = "float32",
    mesh=None,
) -> Tuple[PyTree, list]:
    """One downlink round: the master encodes C_s(x^{t+1} - w^t) through its
    codec and every worker applies the decoded innovation to the shared
    reconstruction w.  Returns (w_new, payloads); the payloads are what
    crosses the wire (``downlink.format_for(params).downlink_bits_per_round()``
    bits, exactly).  The trainer and the reference driver call
    :meth:`repro.core.efbv.Downlink.broadcast` through here, so the downlink
    math lives in one place.  ``key`` must be the round's
    ``downlink_key(step_key)`` so all paths draw the same broadcast.
    A trainer's ``mesh`` is made ambient around it, as around
    :func:`combine_global`'s decode.
    """
    with jax.named_scope("efbv.downlink"), _on_mesh(mesh):
        return downlink.broadcast(key, params, w, wire_dtype=wire_dtype)


# --------------------------------------------------------------------------
# single-call reference (used by equivalence tests, runs un-sharded)
# --------------------------------------------------------------------------

def efbv_aggregate_reference(
    algo: EFBV,
    keys: jax.Array,  # (n,) worker keys
    grads_stacked: PyTree,  # leading worker axis n
    h_stacked: PyTree,
    h_avg: PyTree,
    *,
    mode: str = "dense_psum",
    wire_dtype: str = "float32",
    masks: Optional[jax.Array] = None,  # (n,) participation mask
) -> Tuple[PyTree, PyTree, PyTree]:
    n = jax.tree.leaves(grads_stacked)[0].shape[0]
    widx = jnp.arange(n)  # threaded for the mixed-fleet lax.switch dispatch
    if masks is None:
        msg, h_new = jax.vmap(
            lambda k, g, h, i: compress_local(algo, k, g, h, mode=mode,
                                              wire_dtype=wire_dtype, worker=i)
        )(keys, grads_stacked, h_stacked, widx)
    else:
        msg, h_new = jax.vmap(
            lambda k, g, h, m, i: compress_local(algo, k, g, h, mode=mode,
                                                 wire_dtype=wire_dtype,
                                                 mask=m, worker=i)
        )(keys, grads_stacked, h_stacked, masks, widx)
    g, h_avg_new = combine_global(algo, msg, h_avg, n_workers=n, mode=mode,
                                  wire_dtype=wire_dtype)
    return g, h_new, h_avg_new
