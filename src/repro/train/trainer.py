"""Distributed training step: forward/backward under GSPMD (model axis) +
EF-BV compressed gradient aggregation over the worker axes (pod, data).

This is the integration point of the paper into the framework, in two phases
(see distributed/aggregate.py for why):

    phase 1 -- shard_map( manual = worker axes, auto = 'model' ):
        grads_i  = grad( mean loss over the *local* data shard )   # nabla f_i
        message_i, h_i = compress_local(...)                       # Algorithm 1, worker side
    phase 2 -- plain GSPMD:
        g, h_avg = combine_global(stacked messages, ...)           # the wire collective
        params  <- optimizer(params, g)                            # replicated over workers

Per-worker control variates h_i live in the TrainState with a leading worker
axis sharded over (pod, data); inside phase 1 each worker sees its own h_i.

The federated execution mode (``participation=``) samples a per-round worker
mask before phase 1 and threads it through the shard_map as a worker-sharded
(n,) array: sampled workers run Algorithm 1 unchanged, absent workers' wire
messages are gated to decode-zero and their h_i stay stale -- see
docs/algorithms.md#partial-participation--stochastic-gradients.

Bidirectional compression (``downlink=``) adds a phase 3: workers evaluate
gradients at the master's downlink control variate w (their shared model
reconstruction) and the round ends with ONE compressed broadcast through
the downlink codec (aggregate.broadcast_global) -- identical for present
and absent workers, so w stays replicated.  Heterogeneous fleets
(``algo.fleet``) dispatch each worker's own compressor inside phase 1 via
lax.switch on the worker index (dense_psum mode; mixed payload shapes
cannot stack).

The declarative way to obtain a train step is
``repro.core.build(spec).train_step(loss_fn, opt, mesh)``: the
:class:`repro.core.ExperimentSpec` threads agg/wire_dtype/downlink/
participation from its fields into :func:`make_train_step` (docs/api.md).

The step names its layers with ``jax.named_scope``, so that a
profile of the compiled step splits its device time by layer (each
instruction's ``op_name`` carries the innermost scope): ``efbv.fwd_bwd``
(value_and_grad, the f32 cast, ``grad_transform``), ``efbv.compress``
(compress_local), ``efbv.optimizer`` (update + apply_updates) and
``efbv.step_metrics`` (the per-step norms); aggregate.py adds
``efbv.exchange``, ``efbv.decode`` and ``efbv.downlink``.  The scopes are
metadata only: the compiled program is the same without them.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.efbv import (EFBV, PIPELINE_FOLD, Downlink, Participation,
                             Pipeline, downlink_key, participation_key)
from repro.distributed import wire
from repro.distributed.aggregate import (broadcast_global, combine_global,
                                         compress_local)
from repro.distributed.spec import (
    batch_spec, linear_worker_index, stack_worker_spec, to_named_sharding,
)
from repro.launch.mesh import num_workers, worker_axes
from repro.optim.optimizers import Optimizer, apply_updates, global_norm

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt_state: PyTree
    h: PyTree        # per-worker control variates, leading axis n
    h_avg: PyTree    # master's uplink control variate
    step: jax.Array
    # the master's DOWNLINK control variate w: the workers' shared
    # reconstruction of the model under bidirectional compression (one
    # replicated copy -- every worker decodes the same broadcast).  None
    # when the broadcast is uncompressed.
    w: PyTree = None
    # the IN-FLIGHT wire payload of the pipelined schedule (pipeline=depth:1,
    # docs/algorithms.md#pipelined-rounds): the message compressed at round
    # t-1, applied by the master at round t while round t's own payload is
    # still on the wire.  Stacked on a leading worker axis like the phase-1
    # message it double-buffers; None when the schedule is sequential.
    inflight: PyTree = None


def init_inflight(algo: EFBV, params: PyTree, n: int, *,
                  agg_mode: str = "dense_psum",
                  wire_dtype: str = "float32") -> PyTree:
    """The round-0 priming payload of the pipelined schedule: every worker's
    slot holds a REAL wire message that decodes to exactly zero, so the first
    step's master update is g = h_avg0 + nu * 0 (Algorithm 1's x-update is a
    no-op while the h recursion already advances).  Drawn from
    fold_in(key(0), PIPELINE_FOLD) -- the one convention the trainers, the
    reference driver and the differential harness all share."""
    base = jax.random.fold_in(jax.random.key(0), PIPELINE_FOLD)
    if agg_mode != "sparse_allgather":
        return jax.tree.map(
            lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params)
    fmt = wire.tree_format_for(algo.compressor, params, wire_dtype=wire_dtype,
                               rules=algo.leaf_rules)
    tile = lambda a: jnp.tile(a[None], (n,) + (1,) * a.ndim)
    return [jax.tree.map(tile, wire.zero_message(
                codec, jax.random.fold_in(base, j)))
            for j, codec in enumerate(fmt.leaves)]


def init_train_state(params: PyTree, optimizer: Optimizer, mesh, *,
                     bidirectional: bool = False,
                     algo: Optional[EFBV] = None,
                     agg_mode: str = "dense_psum",
                     wire_dtype: str = "float32",
                     pipeline: Optional[Pipeline] = None) -> TrainState:
    n = num_workers(mesh)
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    h = jax.tree.map(lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params)
    pipelined = pipeline is not None and pipeline.depth > 0
    if pipelined and algo is None:
        raise ValueError("a pipelined TrainState buffers a wire payload; "
                         "init_train_state needs algo= to build it")
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        h=h,
        h_avg=zeros,
        step=jnp.zeros((), jnp.int32),
        w=jax.tree.map(jnp.array, params) if bidirectional else None,
        inflight=init_inflight(algo, params, n, agg_mode=agg_mode,
                               wire_dtype=wire_dtype) if pipelined else None,
    )


def train_state_shardings(mesh, param_specs: PyTree, state: TrainState) -> TrainState:
    """NamedShardings for every TrainState leaf (params/opt sharded over
    'model', h additionally over the worker axes, scalars replicated)."""
    p_shard = to_named_sharding(mesh, param_specs)

    # momenta share param shapes; match by shape against the param specs
    params_flat = jax.tree.leaves(state.params)
    specs_flat = jax.tree.leaves(param_specs, is_leaf=lambda s: isinstance(s, P))
    shape_to_spec = {}
    for leaf, spec in zip(params_flat, specs_flat):
        shape_to_spec.setdefault(leaf.shape, spec)

    def spec_for(leaf):
        return shape_to_spec.get(leaf.shape, P())

    opt_sh = jax.tree.map(lambda l: NamedSharding(mesh, spec_for(l)), state.opt_state)
    h_sh = to_named_sharding(mesh, stack_worker_spec(mesh, param_specs))
    havg_sh = jax.tree.map(lambda l: NamedSharding(mesh, spec_for(l)), state.h_avg)
    rep = NamedSharding(mesh, P())
    w_sh = None if state.w is None \
        else jax.tree.map(lambda _, s: s, state.w, p_shard)
    fl_sh = _inflight_shardings(mesh, state.inflight)
    return TrainState(params=p_shard, opt_state=opt_sh, h=h_sh, h_avg=havg_sh,
                      step=rep, w=w_sh, inflight=fl_sh)


def _inflight_shardings(mesh, inflight: PyTree):
    """Every in-flight payload leaf carries a leading worker axis of size n:
    shard it over the worker axes like the live phase-1 message it mirrors."""
    if inflight is None:
        return None
    waxes = worker_axes(mesh)
    return jax.tree.map(
        lambda _: NamedSharding(mesh, P(tuple(waxes))), inflight)


def _optimizer_step(optimizer: Optimizer, g: PyTree, state: TrainState):
    """(params, updates, opt_state) after one optimizer step on g."""
    with jax.named_scope("efbv.optimizer"):
        updates, opt_state = optimizer.update(g, state.opt_state, state.params)
        return apply_updates(state.params, updates), updates, opt_state


def make_train_step(
    loss_fn: Callable[[PyTree, Any], Tuple[jax.Array, dict]],
    optimizer: Optimizer,
    algo: EFBV,
    mesh,
    *,
    agg_mode: str = "dense_psum",
    wire_dtype: str = "float32",
    remat: bool = False,
    downlink: Optional[Downlink] = None,
    participation: Optional[Participation] = None,
    pipeline: Optional[Pipeline] = None,
    grad_transform: Optional[Callable[[PyTree], PyTree]] = None,
) -> Callable[[TrainState, Any, jax.Array], Tuple[TrainState, dict]]:
    """Build the jitted multi-pod train step.

    loss_fn(params, batch) -> (scalar loss, metrics dict); it sees the LOCAL
    batch shard (the worker's f_i) and may use GSPMD-auto 'model' collectives.

    ``wire_dtype`` selects the value precision of sparse/dense payloads under
    ``agg_mode='sparse_allgather'`` (float32 / bfloat16 / float16; quantized
    and bit-packed codecs ignore it).

    With ``downlink`` the step runs *bidirectional* compression
    (core/efbv.py::Downlink / run_reference, same math here): workers
    evaluate gradients at the master's downlink control variate w -- their
    shared reconstruction of the model -- and the round ends with ONE
    compressed broadcast C_s(x^{t+1} - w^t) through the downlink codec,
    which every worker (present or absent under partial participation)
    decodes identically.  Requires a TrainState built with
    ``init_train_state(..., bidirectional=True)``.  An Identity downlink
    is lossless and keeps the run bit-identical to ``downlink=None``.

    ``participation`` switches on the federated execution mode
    (docs/algorithms.md#partial-participation--stochastic-gradients): each
    round samples a worker mask from fold_in(step_key, PARTICIPATION_FOLD)
    OUTSIDE phase 1 (so the reference and sharded paths draw the same
    subset) and threads it through the shard_map as a worker-sharded (n,)
    array; absent workers' messages are gated to decode-zero and their h_i
    stay stale.  None / 'full' keeps the original unmasked code path.

    ``grad_transform`` (optional) rewrites each worker's fp32 gradient tree
    BEFORE Algorithm 1's compress step -- the worker-side hook of the MoE
    expert-sparsity contract (``repro.models.moe.zero_inactive_expert_grads``
    composes the routed-expert mask with the wire codec so the payload only
    carries routed experts; docs/finetuning.md#expert-sparsity).  It must be
    a per-worker pure function of one gradient pytree; None is the exact
    historical step.

    ``pipeline`` (depth 1) switches on the one-round-stale two-phase
    schedule (docs/algorithms.md#pipelined-rounds): the master applies the
    in-flight payload of round t-1 from ``state.inflight`` while round t's
    freshly compressed message replaces it -- the wire exchange of round t
    overlaps the backward pass of round t+1.  Workers' h_i advance on their
    OWN round-t messages, the master's (h_avg, x) recursion lags one round;
    depth 0 / None is the exact sequential step, bit for bit.  Requires a
    TrainState built with ``init_train_state(..., pipeline=...)``.
    """
    waxes = worker_axes(mesh)
    n = num_workers(mesh)
    federated = participation is not None and not participation.is_full
    pipelined = pipeline is not None and pipeline.depth > 0
    # chunked decode (fixed ascending order, see wire.chunked_decode_sum)
    # lets the decode of early chunks overlap the transfer of late ones
    chunks = wire.pipeline_chunks(n) \
        if (pipelined and agg_mode == "sparse_allgather") else 1

    if remat:
        loss_fn = jax.checkpoint(loss_fn)

    # ---- phase 1: worker-local grad + compress (manual over worker axes) ----
    def worker_body(params_for_grad, h_i, batch_i, kw, m=None, widx=None,
                    stream=False):
        with jax.named_scope("efbv.fwd_bwd"):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params_for_grad, batch_i)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            if grad_transform is not None:
                grads = grad_transform(grads)
        with jax.named_scope("efbv.compress"):
            message, h_i_new = compress_local(
                algo, kw, grads, h_i, mode=agg_mode, wire_dtype=wire_dtype,
                mask=m, worker=widx, stream=stream)
        with jax.named_scope("efbv.step_metrics"):
            local_metrics = {
                "loss": loss,
                "grad_norm": global_norm(grads),
                "h_residual": global_norm(
                    jax.tree.map(lambda a, b: a - b, grads, h_i_new)),
                **aux,
            }
        return message, h_i_new, local_metrics

    def local_phase(params, h, batch, key, mask=None):
        widx = linear_worker_index(mesh)
        kw = jax.random.fold_in(key, widx)

        # Differentiate w.r.t. a *worker-varying* view of the params: without
        # the pcast, jax's VMA machinery would treat the cotangent of the
        # worker-invariant params as invariant and psum it over the worker
        # axes -- giving sum_i grad f_i instead of this worker's grad f_i.
        params_v = compat.pcast_varying(params, tuple(waxes))
        h_loc = jax.tree.map(lambda a: a[0], h)
        m = None if mask is None else mask[0]
        # streaming (payload DMA under the h update) only here, un-vmapped:
        # pallas_call batching would re-purpose the grid dim the streaming
        # kernel slices its HBM outputs by
        message, h_loc_new, local_metrics = worker_body(
            params_v, h_loc, batch, kw, m, widx, stream=pipelined)
        # stack everything on the worker axis
        stack = lambda t: jax.tree.map(lambda a: a[None], t)
        return stack(message), stack(h_loc_new), stack(local_metrics)

    base_in_specs = (P(), P(waxes), batch_spec(mesh), P())
    local_sharded = compat.shard_map(
        local_phase,
        mesh=mesh,
        # the (n,) participation mask rides in worker-sharded: inside the
        # manual region each worker sees its own scalar mask bit
        in_specs=base_in_specs + ((P(waxes),) if federated else ()),
        out_specs=(P(waxes), P(waxes), P(waxes)),
        manual_axes=waxes,
    )

    # ---- full step: phase 1 + phase 2 under one jit ---------------------------
    def train_step(state: TrainState, batch, key):
        # under bidirectional compression workers only ever see w, the
        # master's downlink control variate (their model reconstruction)
        eval_params = state.w if downlink is not None else state.params
        if federated:
            # sampled OUTSIDE phase 1 so reference and sharded paths draw the
            # identical subset S_t from the identical key
            mask = participation.sample_mask(participation_key(key), n)
            message, h_new, local_metrics = local_sharded(
                eval_params, state.h, batch, key, mask)
        else:
            mask = None
            message, h_new, local_metrics = local_sharded(
                eval_params, state.h, batch, key)

        # pipelined: the master consumes the IN-FLIGHT payload (round t-1)
        # while `message` (round t) takes its slot in the double buffer --
        # the data dependence between this round's wire exchange and the
        # optimizer breaks, so XLA overlaps it with the next backward pass
        apply_msg = state.inflight if pipelined else message
        g, h_avg_new = combine_global(
            algo, apply_msg, state.h_avg, n_workers=n, mode=agg_mode,
            wire_dtype=wire_dtype, chunks=chunks, mesh=mesh)

        params, updates, opt_state = _optimizer_step(optimizer, g, state)

        metrics = {k: jnp.mean(v, axis=0) for k, v in local_metrics.items()}
        with jax.named_scope("efbv.step_metrics"):
            metrics["g_norm"] = global_norm(g)
            metrics["update_norm"] = global_norm(updates)
        if federated:
            metrics["participants"] = jnp.sum(mask)

        w = state.w
        if downlink is not None:
            # phase 3: one compressed broadcast through the downlink codec;
            # every worker applies the same decoded innovation, so one
            # replicated copy of w suffices (and absent workers under
            # partial participation decode the identical payload).
            w, _ = broadcast_global(downlink, downlink_key(key), params, w,
                                    wire_dtype=wire_dtype, mesh=mesh)
            with jax.named_scope("efbv.step_metrics"):
                metrics["w_err"] = global_norm(
                    jax.tree.map(lambda a, b: a - b, params, w))

        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            h=h_new,
            h_avg=h_avg_new,
            step=state.step + 1,
            w=w,
            inflight=message if pipelined else state.inflight,
        )
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=(0,))
