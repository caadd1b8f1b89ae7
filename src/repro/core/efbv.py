"""EF-BV (Algorithm 1) over pytrees, with EF21 / DIANA as parametrizations.

Two execution styles share this module:

* the *reference* style used by the convex benchmarks and tests: all n
  workers' control variates are materialized with a leading worker axis and
  the per-worker compressors run under ``vmap`` -- bit-exact semantics of
  Algorithm 1 incl. the master-side bookkeeping;

* the *distributed* style (repro/distributed/aggregate.py) runs the same
  per-worker math inside ``shard_map`` where the leading worker axis is the
  mesh's (pod, data) axes and the master aggregation is a collective.

Both call into :func:`worker_update` / :func:`master_update` below so the
algorithm lives in exactly one place.

Beyond the exact-gradient, full-participation regime of the paper's
experiments, the module also implements the *federated* execution mode
(docs/algorithms.md#partial-participation--stochastic-gradients): per-round
client sampling via :class:`Participation` masks -- only the sampled subset
S_t compresses and communicates, absent workers keep their control variates
h_i stale -- through the masked variants :meth:`EFBV.worker_update_masked` /
:meth:`EFBV.step_federated` and the :func:`run_reference` driver, which also
takes stochastic (minibatch-resampled) local gradients.  With an all-ones
mask every masked op reduces bitwise to its unmasked twin, so full
participation reproduces the original trajectories bit-for-bit (pinned by
tests/test_federated.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.contract import Compressor
from repro.core import theory

Array = jax.Array
PyTree = Any

#: fold_in tag for the per-round participation-mask key.  All execution paths
#: (the reference driver, the shard_map trainer, the differential
#: harness) derive the mask from fold_in(round_key, PARTICIPATION_FOLD) so the
#: sampled subset S_t is identical everywhere; worker compressor keys are
#: untouched, which is what keeps p = 1 bit-identical to full participation.
PARTICIPATION_FOLD = 0xFEDE4A7E
#: fold_in tag for the per-round minibatch-resampling key (stochastic local
#: gradients) -- decorrelated from both the mask and the compressor draws.
RESAMPLE_FOLD = 0x5A3D0B17
#: fold_in tag for the per-round downlink (master -> worker broadcast) key.
#: One key per round, shared by every worker: the broadcast is a single
#: message, so present and absent workers decode the SAME payload.
DOWNLINK_FOLD = 0xD0401B17
#: fold_in tag for the pipelined schedule's PRIMING payload key: the round-0
#: in-flight buffer is a real wire message that decodes to zero (encode of a
#: zero vector, participation-masked to zero), drawn once from
#: fold_in(key(0), PIPELINE_FOLD) so every execution path primes identically.
PIPELINE_FOLD = 0xF1FE11E
#: fold_in tag for the reference driver's run key: Run.reference() derives
#: its trajectory key from fold_in(key(seed), REFERENCE_FOLD) so it is
#: decorrelated from the problem-data key (jax.random.key(seed) raw).  The
#: value predates this name; changing it would shift every recorded
#: reference trajectory.
REFERENCE_FOLD = 0x5EED


@dataclasses.dataclass(frozen=True)
class Participation:
    """Per-round client-sampling scheme (the federated execution mode).

    kind:
      * ``full``          -- every worker participates (the paper's setting);
      * ``bernoulli``     -- worker i participates independently w.p. ``p``;
      * ``fixed``         -- a uniformly random subset of exactly ``s`` workers.

    Masks are {0., 1.}-valued float32 so that gating is pure arithmetic:
    ``m * d`` zeroes an absent worker's message and ``where(m > 0, h', h)``
    keeps its control variate stale -- both bitwise identities at m = 1.
    """

    kind: str = "full"
    p: float = 1.0   # bernoulli inclusion probability
    s: int = 0       # fixed-size participant count

    def __post_init__(self):
        if self.kind not in ("full", "bernoulli", "fixed"):
            raise ValueError(f"participation kind {self.kind!r}")
        if self.kind == "bernoulli" and not 0.0 < self.p <= 1.0:
            raise ValueError(f"bernoulli participation needs 0 < p <= 1, got {self.p}")
        if self.kind == "fixed" and self.s < 1:
            raise ValueError(f"fixed participation needs s >= 1, got {self.s}")

    @staticmethod
    def parse(spec: str) -> "Participation":
        """Parse the CLI syntax: 'full' | 'bernoulli:p' | 'fixed:s'."""
        name, _, arg = spec.partition(":")
        if name == "full":
            return Participation()
        if name == "bernoulli":
            return Participation(kind="bernoulli", p=float(arg))
        if name == "fixed":
            return Participation(kind="fixed", s=int(arg))
        raise ValueError(f"participation spec {spec!r} (want full | "
                         f"bernoulli:p | fixed:s)")

    @property
    def is_full(self) -> bool:
        return self.kind == "full" or (self.kind == "bernoulli" and self.p >= 1.0)

    def fraction(self, n: int) -> float:
        """Expected fraction of participating workers, E|S_t| / n."""
        if self.kind == "bernoulli":
            return self.p
        if self.kind == "fixed":
            return min(self.s, n) / n
        return 1.0

    def sample_mask(self, key: Array, n: int) -> Array:
        """(n,) float32 participation mask for one round."""
        if self.kind == "bernoulli":
            return jax.random.bernoulli(key, self.p, (n,)).astype(jnp.float32)
        if self.kind == "fixed":
            if self.s > n:
                raise ValueError(f"fixed:{self.s} participation with only {n} workers")
            return (jax.random.permutation(key, n) < self.s).astype(jnp.float32)
        return jnp.ones((n,), jnp.float32)


def participation_key(round_key: Array) -> Array:
    """The shared derivation of the mask key from a round key."""
    return jax.random.fold_in(round_key, PARTICIPATION_FOLD)


def downlink_key(round_key: Array) -> Array:
    """The shared derivation of the broadcast key from a round key.  All
    execution paths (the reference driver, the trainer, the differential
    harness) use this, so the master's compressor draw -- and therefore the
    broadcast every worker decodes -- is identical everywhere."""
    return jax.random.fold_in(round_key, DOWNLINK_FOLD)


@dataclasses.dataclass(frozen=True)
class Pipeline:
    """The pipelined (one-round-stale) execution schedule.

    ``depth = 0`` is the sequential schedule of the paper: round t's
    aggregate is computed from round t's messages.  ``depth = 1``
    double-buffers the compressed payload: round t *applies* the messages
    compressed at round t-1 while round t's own messages are still on the
    wire -- the allgather/broadcast overlaps the next backward pass.
    Workers advance their control variates h_i on time; only the master's
    (g, h_avg) update lags one round, which the auto-tuning absorbs via
    :func:`repro.core.theory.pipeline_eta` / ``pipeline_omega``.  Depths
    beyond 1 would need a ring of in-flight buffers and are rejected.
    """

    depth: int = 0

    def __post_init__(self):
        if not isinstance(self.depth, int) or self.depth < 0:
            raise ValueError(
                f"pipeline depth must be an int >= 0, got {self.depth!r}")
        if self.depth > 1:
            raise ValueError(
                f"pipeline depth {self.depth} not implemented: the trainers "
                "double-buffer exactly ONE in-flight payload; use 'off' or "
                "'depth:1'")

    @staticmethod
    def parse(spec: str) -> "Pipeline":
        """Parse the CLI syntax: '' | 'off' | 'depth:k' (k in {0, 1}).

        Thin delegate into the unified spec grammar
        (:mod:`repro.core.specgrammar`), which also provides the lossless
        ``format_pipeline`` inverse; depth validation stays in
        :meth:`__post_init__`."""
        from repro.core import specgrammar
        return Pipeline(depth=specgrammar.parse_pipeline(spec))

    @property
    def is_off(self) -> bool:
        return self.depth == 0


# ------------------------------------------------------------------------------
# the downlink channel: master -> worker compressed model broadcast
# ------------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Downlink:
    """Master-side EF-BV state for the server -> worker model broadcast
    (EF21-BC generalized to any zoo compressor/codec; Fatkhullin et al. 2021,
    referenced by the paper as an extension).

    The master keeps its own control variate ``w`` -- the workers' shared
    reconstruction of the model -- and each round broadcasts the compressed
    model innovation through the compressor's wire codec:

        q^t   = C_s(x^{t+1} - w^t)          (one message, every worker)
        w^t+1 = w^t + lam_s * q^t

    Workers evaluate their gradients at ``w``, so the uplink direction is
    Algorithm 1 unchanged (with h_i tracking grad f_i(w)).  Because the
    broadcast is ONE payload drawn from the shared :func:`downlink_key`,
    federated rounds need no special casing: an absent worker decodes the
    exact same broadcast it would have received while present, so every
    worker's ``w`` stays replicated -- one copy suffices.

    ``lam_s`` is the downlink scaling (Prop. 1 applies to C_s too); the
    EF21-BC choice is 1.  With ``C_s = Identity`` (and a lossless f32 wire)
    the update telescopes to ``w = x`` and the implementation assigns ``x``
    verbatim, which is what keeps identity-downlink runs *bit-identical* to
    the uncompressed-broadcast trajectories (pinned by the harness).
    """

    compressor: Compressor
    lam: float = 1.0

    @staticmethod
    def parse(spec: str) -> Optional["Downlink"]:
        """CLI syntax: '' | 'none' -> None (uncompressed dense broadcast);
        otherwise any zoo compressor spec, e.g. 'qsgd:16', 'block_topk:256,16',
        optionally '@lam' for the downlink scaling ('topk:64@0.9').

        Thin delegate into the unified spec grammar
        (:mod:`repro.core.specgrammar`), which also provides the lossless
        ``format_downlink`` inverse."""
        from repro.core import specgrammar
        parsed = specgrammar.parse_downlink(spec)
        if parsed is None:
            return None
        compressor, lam = parsed
        return Downlink(compressor=compressor, lam=lam)

    def _is_lossless(self, wire_dtype: str) -> bool:
        from repro.core.compressors import Identity
        return (isinstance(self.compressor, Identity) and self.lam == 1.0
                and wire_dtype == "float32")

    def init(self, params: PyTree) -> PyTree:
        """w^0 = x^0 (workers start from the broadcast initial model)."""
        return jax.tree.map(jnp.asarray, params)

    def format_for(self, tree: PyTree, *, wire_dtype: str = "float32"):
        """The downlink WireFormat (one broadcast message per round)."""
        from repro.distributed import wire
        return wire.format_for(self.compressor, tree, wire_dtype=wire_dtype)

    def broadcast(self, key: Optional[Array], x: PyTree, w: PyTree, *,
                  wire_dtype: str = "float32"
                  ) -> Tuple[PyTree, list]:
        """One downlink round: returns ``(w_new, payloads)``.

        ``payloads`` is the per-leaf wire payload of the single broadcast
        message (what actually crosses the master -> worker wire;
        ``wire.payload_bytes`` of it equals ``downlink_bits_per_round / 8``
        exactly).  ``w_new = w + lam_s * decode(payload)`` -- computed from
        the DECODED payload, so master and workers agree bit-for-bit on the
        reconstruction.  The Identity/f32 wire is lossless and assigns
        ``w_new = x`` verbatim (bitwise; see the class docstring).
        """
        from repro.distributed import wire
        leaves, treedef = jax.tree.flatten(x)
        w_leaves = treedef.flatten_up_to(w)
        payloads, new_leaves = [], []
        for j, (xj, wj) in enumerate(zip(leaves, w_leaves)):
            codec = wire.codec_of(self.compressor, tuple(xj.shape),
                                  int(xj.size), wire_dtype)
            kj = None if key is None else jax.random.fold_in(key, j)
            delta = (xj.astype(jnp.float32)
                     - wj.astype(jnp.float32)).reshape(-1)
            payload = codec.encode(kj, delta)
            payloads.append(payload)
            if self._is_lossless(wire_dtype):
                new_leaves.append(xj)
            else:
                q = codec.decode(payload).reshape(xj.shape)
                new_leaves.append((wj.astype(jnp.float32)
                                   + self.lam * q).astype(wj.dtype))
        return jax.tree.unflatten(treedef, new_leaves), payloads

    # ---- the serving push protocol (compressed-delta model distribution) ----

    def serve_format(self, tree: PyTree, *, wire_dtype: str = "float32",
                     rules=None):
        """The downlink wire format of one serving push for ``tree``:
        the flat per-leaf format of :attr:`compressor`, or -- with per-leaf
        codec ``rules`` (wire.parse_leaf_rules) -- the pytree-native
        :class:`repro.distributed.wire.TreeWire`.  ``push_bits(fmt)`` is
        the exact envelope size of one delta push."""
        from repro.distributed import wire
        return wire.tree_format_for(self.compressor, tree,
                                    wire_dtype=wire_dtype,
                                    rules=tuple(rules) if rules else None)

    def push_kind(self, wire_dtype: str = "float32", rules=None) -> str:
        """'snapshot' for a lossless wire (the payload decodes to the model
        itself and the replica ASSIGNS it -- an identity-downlink push is a
        full checkpoint, bit-for-bit), 'delta' otherwise (the payload
        decodes to the innovation and the replica accumulates it).
        Per-leaf ``rules`` can re-map any leaf to a lossy codec, so a ruled
        push is always a delta."""
        if rules:
            return "delta"
        return "snapshot" if self._is_lossless(wire_dtype) else "delta"

    def encode_push(self, key: Optional[Array], x: PyTree, w: PyTree, *,
                    wire_dtype: str = "float32", rules=None
                    ) -> Tuple[PyTree, list]:
        """Trainer-side half of one serving push: returns ``(w_new,
        payloads)`` -- the replicas' next shared reconstruction and the one
        broadcast message that produces it.

        The payloads are the SAME bits the in-training broadcast puts on
        the wire (same codecs, same ``fold_in(key, j)`` leaf keys as
        :meth:`broadcast`), and ``w_new`` is computed by APPLYING them
        through :meth:`apply_push` -- the replica-side arithmetic -- so the
        pusher's w and every replica's w agree bit-for-bit by construction.
        A lossless wire ships a 'snapshot' (the model encoded absolutely,
        decode-assigns to exactly ``x``) instead of a delta: same exact bit
        count, and it preserves the :meth:`broadcast` invariant that a
        lossless downlink pins ``w = x`` verbatim, which ``w + (x - w)``
        float arithmetic would not."""
        from repro.distributed import wire
        fmt = self.serve_format(x, wire_dtype=wire_dtype, rules=rules)
        leaves, treedef = jax.tree.flatten(x)
        w_leaves = treedef.flatten_up_to(w)
        snapshot = self.push_kind(wire_dtype, rules) == "snapshot"
        payloads = []
        for j, (codec, xj, wj) in enumerate(zip(fmt.leaves, leaves,
                                                w_leaves)):
            kj = None if key is None else jax.random.fold_in(key, j)
            if snapshot:
                flat = xj.astype(jnp.float32).reshape(-1)
            else:
                flat = (xj.astype(jnp.float32)
                        - wj.astype(jnp.float32)).reshape(-1)
            payloads.append(codec.encode(kj, flat))
        w_new = self.apply_push(payloads, w, wire_dtype=wire_dtype,
                                rules=rules)
        return w_new, payloads

    def apply_push(self, payloads, w: PyTree, *,
                   wire_dtype: str = "float32", rules=None) -> PyTree:
        """Replica-side half of one serving push: decode the broadcast
        payloads and advance the local reconstruction, ``w_new = w + lam *
        decode(payload)`` per leaf ('delta' pushes) or ``w_new =
        decode(payload)`` verbatim ('snapshot' pushes from a lossless
        wire).  Same arithmetic, same op order as the trainer side
        (:meth:`broadcast` / :meth:`encode_push`), so a replica that
        applies every push in version order reconstructs the trainer's w
        bit-for-bit -- the property tests/test_serve_delta.py pins for
        every zoo codec."""
        fmt = self.serve_format(w, wire_dtype=wire_dtype, rules=rules)
        w_leaves, treedef = jax.tree.flatten(w)
        snapshot = self.push_kind(wire_dtype, rules) == "snapshot"
        new_leaves = []
        for codec, wj, p in zip(fmt.leaves, w_leaves, payloads):
            q = codec.decode(p).reshape(wj.shape)
            if snapshot:
                new_leaves.append(q.astype(wj.dtype))
            else:
                new_leaves.append((wj.astype(jnp.float32)
                                   + self.lam * q).astype(wj.dtype))
        return jax.tree.unflatten(treedef, new_leaves)


class EFBVState(NamedTuple):
    """State of Algorithm 1.

    h:      per-worker control variates h_i -- leading axis n in the reference
            impl; local (no leading axis) inside shard_map.
    h_avg:  the master's running average h^t = (1/n) sum_i h_i^t.
    step:   iteration counter t.
    """

    h: PyTree
    h_avg: PyTree
    step: Array


@dataclasses.dataclass(frozen=True)
class EFBV:
    """The algorithm, frozen so it can be a static jit argument.

    lam/nu are the two scaling parameters (Sect. 3): lam controls the control-
    variate update (variance reduction), nu the gradient-estimate update
    (error feedback).  nu = lam -> EF21; nu = 1 -> DIANA.

    ``fleet`` switches on the *heterogeneous* setting (Beznosikov et al.
    2020): worker i runs its OWN compressor ``fleet[i]`` (length exactly n;
    round-robin expansion happens at parse time, see
    ``compressors.make_fleet``).  ``compressor`` then holds ``fleet[0]`` as
    the representative; (lam, nu) are tuned for the aggregated mixed-fleet
    constants (theory.tune_fleet).  A homogeneous fleet collapses to
    ``fleet=None`` so the single-compressor fast paths stay untouched.

    ``leaf_rules`` switches on the *pytree-native* wire (wire.TreeWire):
    (fnmatch-pattern, Compressor) pairs resolved against each leaf's
    '/'-joined path, first match wins, unmatched leaves keep ``compressor``.
    Every consumer (compress_delta, the aggregation paths, init_inflight)
    resolves leaves through the same wire.tree_format_for chokepoint, and
    (lam, nu) are tuned for the worst-case leaf composition
    (theory.tune_tree).  ``leaf_rules=None`` is the flat wire, bitwise.
    """

    compressor: Compressor
    lam: float
    nu: float
    fleet: Optional[Tuple[Compressor, ...]] = None
    leaf_rules: Optional[Tuple[Tuple[str, Compressor], ...]] = None

    # ---- constructors -------------------------------------------------------

    @staticmethod
    def make(compressor, d: int, n: int, mode: theory.Mode = "efbv",
             independent: bool = True,
             participation: Optional[float] = None,
             pipeline: Optional[int] = None,
             leaf_rules: Optional[Tuple[Tuple[str, Compressor], ...]] = None
             ) -> "EFBV":
        """Auto-tuned instance (Remark 1).  ``participation`` is the expected
        per-round participation fraction p; when given, (lam, nu) are tuned
        for the effective compressor b*C, b ~ Bernoulli(p) (theory.tune_partial
        -- see docs/theory.md).  ``pipeline`` is the staleness depth of the
        pipelined schedule; when given, the one-round delay is folded into
        the certified constants (theory.pipeline_eta / pipeline_omega) --
        None / 0 is an exact no-op.

        ``compressor`` may be a sequence of compressors -- a heterogeneous
        fleet, round-robin expanded to n members -- tuned via
        theory.tune_fleet (worst-case aggregation; see docs/theory.md).

        ``leaf_rules`` (per-leaf codec rules, wire.parse_leaf_rules) tunes
        (lam, nu) for the worst-case composition over the base compressor
        and every rule member at dimension d (theory.tune_tree; leaf sizes
        are tree-dependent, and the worst-case aggregate is size-free).
        An empty/None rule set is an exact no-op."""
        if isinstance(compressor, (list, tuple)):
            if leaf_rules:
                raise ValueError("per-leaf codec rules cannot be combined "
                                 "with a heterogeneous worker fleet")
            from repro.core.compressors import expand_fleet
            members = expand_fleet(tuple(compressor), n)
            t = theory.tune_for(members, d, n, independent=independent,
                                mode=mode, participation=participation,
                                pipeline=pipeline)
            fleet = None if len(set(members)) == 1 else members
            return EFBV(members[0], lam=t.lam, nu=t.nu, fleet=fleet)
        if leaf_rules:
            if not independent:
                raise ValueError("per-leaf codec tuning assumes independent "
                                 "per-worker compressors")
            comps = [compressor] + [c for _, c in leaf_rules]
            for c in comps:
                if getattr(c, "joint", False):
                    # same rejection as wire.parse_leaf_rules: the string
                    # grammar cannot name a joint compressor, this guards
                    # the programmatic path
                    raise ValueError(
                        "jointly-defined compressors (m-nice) cannot be "
                        "leaf-codec rules: their draws couple all workers")
            t = theory.tune_tree([c.eta(d) for c in comps],
                                 [c.omega(d) for c in comps],
                                 n=n, aggregate="worst", mode=mode,
                                 participation=participation,
                                 pipeline=pipeline)
            return EFBV(compressor, lam=t.lam, nu=t.nu,
                        leaf_rules=tuple(leaf_rules))
        t = theory.tune_for(compressor, d, n, independent=independent, mode=mode,
                            participation=participation, pipeline=pipeline)
        return EFBV(compressor, lam=t.lam, nu=t.nu)

    @staticmethod
    def ef21(compressor: Compressor, d: int, n: int) -> "EFBV":
        return EFBV.make(compressor, d, n, mode="ef21")

    @staticmethod
    def diana(compressor: Compressor, d: int, n: int) -> "EFBV":
        return EFBV.make(compressor, d, n, mode="diana")

    # ---- state --------------------------------------------------------------

    def init(self, params: PyTree, n: int, stacked: bool = True) -> EFBVState:
        """h_i^0 = 0 (any init works; 0 matches the paper's experiments)."""
        zeros = jax.tree.map(jnp.zeros_like, params)
        if stacked:
            h = jax.tree.map(lambda z: jnp.zeros((n,) + z.shape, z.dtype), params)
        else:
            h = zeros
        return EFBVState(h=h, h_avg=zeros, step=jnp.zeros((), jnp.int32))

    # ---- algorithm core (shared by reference and distributed paths) ----------

    def compress_delta(self, key: Optional[Array], grad: PyTree, h: PyTree,
                       compressor: Optional[Compressor] = None) -> PyTree:
        """d_i = C_i(grad_i - h_i), leaf-wise with decorrelated keys.

        ``compressor`` overrides ``self.compressor`` (the heterogeneous-fleet
        path passes worker i's own member).  With ``leaf_rules`` set (and no
        override) each leaf runs the compressor its path resolves to, clamped
        to the leaf's size -- the dense twin of the TreeWire codec path, so
        reference and wire trajectories stay bit-identical leaf-wise."""
        comp = self.compressor if compressor is None else compressor
        leaves, treedef = jax.tree.flatten(grad)
        h_leaves = treedef.flatten_up_to(h)
        if compressor is None and self.leaf_rules:
            from repro.distributed import wire
            comps = [wire.clamp_for_leaf(
                wire.resolve_leaf(self.leaf_rules, p, comp), int(g.size))
                for p, g in zip(wire.leaf_paths(grad), leaves)]
        else:
            comps = [comp] * len(leaves)
        outs = []
        for j, (cj, g, hj) in enumerate(zip(comps, leaves, h_leaves)):
            kj = None if key is None else jax.random.fold_in(key, j)
            outs.append(cj(kj, g - hj))
        return jax.tree.unflatten(treedef, outs)

    def _compress_fleet(self, keys: Array, grads: PyTree, h: PyTree,
                        n: int) -> PyTree:
        """Per-worker d_i = C_i(grad_i - h_i) for a heterogeneous fleet:
        a static Python loop over workers (each member is a different
        program), stacked back on the worker axis.  Key derivation matches
        the vmap path (keys[i] for worker i) so a homogeneous fleet draws
        identically to :meth:`step`'s vmap."""
        if len(self.fleet) != n:
            raise ValueError(f"fleet of {len(self.fleet)} members for {n} "
                             "workers (expand_fleet sizes it to n)")
        d_workers = []
        for i in range(n):
            g_i = jax.tree.map(lambda a: a[i], grads)
            h_i = jax.tree.map(lambda a: a[i], h)
            d_workers.append(
                self.compress_delta(keys[i], g_i, h_i, self.fleet[i]))
        return jax.tree.map(lambda *ds: jnp.stack(ds), *d_workers)

    def worker_update(self, h: PyTree, d: PyTree) -> PyTree:
        """h_i <- h_i + lam d_i."""
        return jax.tree.map(lambda hj, dj: hj + self.lam * dj, h, d)

    def worker_update_masked(self, h: PyTree, d: PyTree, m: Array) -> PyTree:
        """Participation-gated worker update: h_i <- h_i + lam d_i when worker
        i is sampled (m = 1), STALE h_i otherwise (m = 0).

        ``where`` (not ``h + m*lam*d``) so an absent worker's h_i is the old
        array verbatim; at m = 1 the taken branch is exactly
        :meth:`worker_update`'s arithmetic, hence bit-identical.
        """
        return jax.tree.map(
            lambda hj, dj: jnp.where(m > 0, hj + self.lam * dj, hj), h, d)

    def master_update(self, h_avg: PyTree, d_bar: PyTree) -> Tuple[PyTree, PyTree]:
        """g <- h + nu d_bar ; h <- h + lam d_bar.  Returns (g, new h_avg).

        The federated mode needs NO master variant: absent workers' messages
        are zeroed worker-side and d_bar stays normalized by n (not |S_t|),
        which is exactly what preserves the running-average invariant
        h_avg = (1/n) sum_i h_i when only the sampled h_i moved.
        """
        g = jax.tree.map(lambda hj, dj: hj + self.nu * dj, h_avg, d_bar)
        h_new = jax.tree.map(lambda hj, dj: hj + self.lam * dj, h_avg, d_bar)
        return g, h_new

    # ---- reference (vmap-over-workers) step ----------------------------------

    def compress_round(self, key: Array, grads: PyTree, state: EFBVState,
                       mask: Optional[Array] = None
                       ) -> Tuple[PyTree, PyTree]:
        """The worker half of one round: returns ``(d_bar, h_new)`` --
        the normalized aggregate d_bar = (1/n) sum_i [m_i] C_i(grad_i - h_i)
        and the advanced per-worker control variates -- WITHOUT the master
        update.  Factored out of :meth:`step` / :meth:`step_federated`
        (which compose it with :meth:`master_update`, bit-identical to
        their historical bodies) so the pipelined schedule can apply a
        one-round-stale d_bar while h_i advances on time."""
        n = jax.tree.leaves(grads)[0].shape[0]

        if getattr(self.compressor, "joint", False):
            if mask is not None:
                raise ValueError(
                    "jointly-defined compressors (m-nice) model participation "
                    "themselves; combine them with Participation masks is "
                    "ambiguous")

            # jointly-defined compressors (m-nice partial participation,
            # Sect. 2.4): every worker samples from the SAME round key
            def one_worker(i, g_i, h_i):
                return jax.tree.map(
                    lambda g, h: self.compressor.joint_call(key, i, g - h),
                    g_i, h_i)

            d = jax.vmap(one_worker)(jnp.arange(n), grads, state.h)
            h_new = jax.vmap(self.worker_update)(state.h, d)
            d_bar = jax.tree.map(lambda dj: jnp.mean(dj, axis=0), d)
            return d_bar, h_new

        keys = jax.random.split(key, n)
        if self.fleet is not None:
            d = self._compress_fleet(keys, grads, state.h, n)
        else:
            d = jax.vmap(lambda k, g_i, h_i: self.compress_delta(k, g_i, h_i)
                         )(keys, grads, state.h)
        if mask is None:
            h_new = jax.vmap(self.worker_update)(state.h, d)
            d_bar = jax.tree.map(lambda dj: jnp.mean(dj, axis=0), d)
        else:
            h_new = jax.vmap(self.worker_update_masked)(state.h, d, mask)
            d_bar = jax.tree.map(
                lambda dj: jnp.mean(
                    mask.reshape((n,) + (1,) * (dj.ndim - 1)) * dj, axis=0), d)
        return d_bar, h_new

    def step(self, key: Array, grads: PyTree, state: EFBVState
             ) -> Tuple[PyTree, EFBVState]:
        """One round of Algorithm 1.

        grads: per-worker gradients with leading axis n on every leaf
               (grads_i = nabla f_i(x^t)).
        Returns (g^{t+1}, new state); the caller applies
        x^{t+1} = prox_{gamma R}(x^t - gamma g^{t+1}).
        """
        d_bar, h_new = self.compress_round(key, grads, state)
        g, h_avg_new = self.master_update(state.h_avg, d_bar)
        return g, EFBVState(h=h_new, h_avg=h_avg_new, step=state.step + 1)

    # ---- federated (partial-participation) reference step ---------------------

    def step_federated(self, key: Array, grads: PyTree, state: EFBVState,
                       mask: Array) -> Tuple[PyTree, EFBVState]:
        """One round of Algorithm 1 under per-round client sampling.

        ``mask`` is the (n,) {0., 1.} participation mask of this round
        (Participation.sample_mask).  Only sampled workers contribute their
        compressed innovation d_i and advance h_i; absent workers' h_i stay
        stale and their (zero) message still counts in the 1/n normalization,
        preserving h_avg = (1/n) sum_i h_i.  With an all-ones mask this is
        bit-identical to :meth:`step`.
        """
        if getattr(self.compressor, "joint", False):
            raise ValueError(
                "jointly-defined compressors (m-nice) model participation "
                "themselves; combine them with Participation masks is ambiguous")
        d_bar, h_new = self.compress_round(key, grads, state, mask)
        g, h_avg_new = self.master_update(state.h_avg, d_bar)
        return g, EFBVState(h=h_new, h_avg=h_avg_new, step=state.step + 1)


# ------------------------------------------------------------------------------
# proximal operators for the composite term R (problem (1))
# ------------------------------------------------------------------------------

def prox_zero(gamma: float, x: PyTree) -> PyTree:
    return x


def prox_l2(mu_reg: float) -> Callable[[float, PyTree], PyTree]:
    """R = (mu_reg/2)||x||^2  ->  prox = x / (1 + gamma mu_reg)."""

    def prox(gamma, x):
        return jax.tree.map(lambda v: v / (1.0 + gamma * mu_reg), x)

    return prox


def prox_l1(lam_reg: float) -> Callable[[float, PyTree], PyTree]:
    """R = lam_reg ||x||_1  ->  soft threshold."""

    def prox(gamma, x):
        t = gamma * lam_reg
        return jax.tree.map(lambda v: jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0), x)

    return prox


def proximal_step(x: PyTree, g: PyTree, gamma: float,
                  prox: Callable[[float, PyTree], PyTree] = prox_zero) -> PyTree:
    """x^{t+1} = prox_{gamma R}(x^t - gamma g^{t+1})."""
    y = jax.tree.map(lambda xv, gv: xv - gamma * gv, x, g)
    return prox(gamma, y)


# ------------------------------------------------------------------------------
# THE reference driver: one lax.scan subsuming every execution mode
# ------------------------------------------------------------------------------

class ReferenceRun(NamedTuple):
    """Result of :func:`run_reference`.

    x:       final iterate.
    state:   final :class:`EFBVState` (per-worker + master control variates).
    w:       final downlink control variate (workers' shared model
             reconstruction) under bidirectional compression; None otherwise.
    metrics: per-round scalars from ``record``; None when not recording.
    pending: the in-flight aggregate d_bar of the LAST round under the
             pipelined schedule (compressed but not yet applied by the
             master); None for the sequential schedule.
    """

    x: PyTree
    state: EFBVState
    w: Optional[PyTree]
    metrics: Optional[Array]
    pending: Optional[PyTree] = None


def run_reference(
    *,
    algo: EFBV,
    grad_fn: Callable[[Array, PyTree], PyTree],  # (key, x|w) -> n-leading grads
    x0: PyTree,
    gamma: float,
    steps: int,
    key: Array,
    n: int,
    participation: Optional[Participation] = None,
    downlink: Optional[Downlink] = None,
    prox: Callable[[float, PyTree], PyTree] = prox_zero,
    record: Optional[Callable[[PyTree], Array]] = None,
    wire_dtype: str = "float32",
    pipeline: Optional[Pipeline] = None,
) -> ReferenceRun:
    """jit-compiled lax.scan over Algorithm 1 -- the ONE reference driver.

    The execution mode is selected by what is (not) supplied, exactly the
    cross-product :class:`repro.core.spec.ExperimentSpec` declares:

    * ``participation`` None / full -- the paper's full-participation regime
      (:meth:`EFBV.step`); otherwise per-round client sampling with the
      shared :func:`participation_key` mask derivation and
      :meth:`EFBV.step_federated` (absent workers keep h_i stale).
    * ``downlink`` None -- uncompressed model broadcast (workers read x);
      otherwise the bidirectional wire: workers evaluate gradients at the
      shared reconstruction ``w`` and each round ends with ONE compressed
      broadcast drawn from :func:`downlink_key`.
    * ``grad_fn(key, x)`` may consume the per-round resampling key
      (fold_in(round_key, RESAMPLE_FOLD)) for stochastic local gradients;
      exact-gradient callers simply ignore it.
    * ``pipeline`` None / depth 0 -- the sequential schedule; depth 1 is
      the exact dense oracle of the trainers' pipelined schedule: the
      master applies the aggregate compressed one round earlier (round 0
      applies a zero buffer, so x is unchanged while h_i advances), and
      the last round's aggregate is returned as ``.pending``.

    Each simpler mode reduces *bitwise* to the corresponding specialization:
    the masked ops are arithmetic identities at m = 1 and the Identity/f32
    downlink assigns w = x verbatim, so the spec-driven path
    (``repro.core.build(spec).reference()``) stays bit-identical to a direct
    call supplying only the relevant arguments (pinned by tests/test_spec.py).
    """
    part = participation if participation is not None else Participation()
    depth = 0 if pipeline is None else pipeline.depth
    state0 = algo.init(x0, n)
    w0 = downlink.init(x0) if downlink is not None else None

    if depth:
        # pipelined schedule: the master applies the aggregate compressed
        # `depth` (= 1) rounds ago; the in-flight buffer rides in the carry
        # and starts at the zero aggregate (round 0 leaves x unchanged).
        pending0 = jax.tree.map(jnp.zeros_like, x0)

        def body(carry, k):
            x, w, st, pending = carry
            eval_at = w if downlink is not None else x
            grads = grad_fn(jax.random.fold_in(k, RESAMPLE_FOLD), eval_at)
            if part.is_full:
                d_new, h_new = algo.compress_round(k, grads, st)
            else:
                mask = part.sample_mask(participation_key(k), n)
                d_new, h_new = algo.compress_round(k, grads, st, mask)
            g, h_avg_new = algo.master_update(st.h_avg, pending)
            st = EFBVState(h=h_new, h_avg=h_avg_new, step=st.step + 1)
            x = proximal_step(x, g, gamma, prox)
            if downlink is not None:
                w, _ = downlink.broadcast(downlink_key(k), x, w,
                                          wire_dtype=wire_dtype)
            m = record(x) if record is not None else jnp.zeros(())
            return (x, w, st, d_new), m

        keys = jax.random.split(key, steps)
        (x, w, state, pending), metrics = jax.lax.scan(
            body, (x0, w0, state0, pending0), keys)
        return ReferenceRun(x=x, state=state, w=w,
                            metrics=metrics if record is not None else None,
                            pending=pending)

    def body(carry, k):
        x, w, st = carry
        # under bidirectional compression workers only ever see w
        eval_at = w if downlink is not None else x
        grads = grad_fn(jax.random.fold_in(k, RESAMPLE_FOLD), eval_at)
        if part.is_full:
            g, st = algo.step(k, grads, st)
        else:
            mask = part.sample_mask(participation_key(k), n)
            g, st = algo.step_federated(k, grads, st, mask)
        x = proximal_step(x, g, gamma, prox)
        if downlink is not None:
            w, _ = downlink.broadcast(downlink_key(k), x, w,
                                      wire_dtype=wire_dtype)
        m = record(x) if record is not None else jnp.zeros(())
        return (x, w, st), m

    keys = jax.random.split(key, steps)
    (x, w, state), metrics = jax.lax.scan(body, (x0, w0, state0), keys)
    return ReferenceRun(x=x, state=state, w=w,
                        metrics=metrics if record is not None else None)
