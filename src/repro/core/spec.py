"""One declarative, serializable experiment surface for the whole repo.

EF-BV's point is that ONE parameterized object C(eta, omega) unifies what
used to be separate algorithm families (DIANA, EF21).  This module does the
same for the *system*: a frozen :class:`ExperimentSpec` captures the full
execution cross-product --

    uplink compressor (or ';'-separated heterogeneous fleet),
    aggregation wire + value dtype,
    downlink broadcast channel,
    per-round client sampling,
    algorithm parametrization (efbv / ef21 / diana / none),
    problem (built-in convex problems or a model arch),
    backend (reference / shard_map),
    steps / seed / stepsize

-- with lossless JSON round-trips, CLI-style parsing, and a stable
:meth:`ExperimentSpec.fingerprint` hash (used by the checkpoint layer to
refuse mismatched resumes and by the CI bench to key its trajectory rows).

:func:`build` turns a spec into a :class:`Run`: the single entry point
whose ``.reference()`` drives :func:`repro.core.efbv.run_reference` (the
one lax.scan driver; the historical run / run_federated / run_bidirectional
entry points are gone), whose ``.train_step()`` builds
the shard_map trainer's step, whose ``.round_bits()`` delegates to the
exact wire accounting, and whose ``.tuned`` delegates to the paper's
auto-tuning (:func:`repro.core.theory.tune_for`).  Every future scenario is
a new spec field, not a fourth driver; the migration table from the old
surface lives in docs/algorithms.md#migrating-to-experimentspec and the
doctested API reference in docs/api.md.
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
import json
import math
from typing import Any, Callable, Optional, Sequence, Tuple, Union

SPEC_VERSION = 1

MODES = ("efbv", "ef21", "diana", "none")
AGG_MODES = ("dense_psum", "sparse_allgather")
BACKENDS = ("reference", "shard_map")
WIRE_DTYPES = ("float32", "bfloat16", "float16")  # == wire.VAL_DTYPES
#: problems the reference backend can build itself (anything else is a model
#: arch id from repro.configs.ARCHS, trainer backends only)
REFERENCE_PROBLEMS = ("quadratic", "logreg")

PyTree = Any


class SpecError(ValueError):
    """An ExperimentSpec that does not describe a runnable experiment."""


def _choice(field: str, value: str, allowed: Sequence[str]) -> None:
    if value not in allowed:
        raise SpecError(f"spec.{field} = {value!r} not in {tuple(allowed)}")


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Parsed form of ``ExperimentSpec.serve``: the replica-fleet serving
    leg.  The trainer's compressed downlink doubles as a model-delta
    streaming protocol (launch/serve.py); this object sizes the simulated
    fleet and its decode workload.

    Fields (','-separated 'key:value' entries in the spec string; any
    subset, missing keys keep the defaults below):

    replicas:  serving replicas reconstructing w from delta pushes.
    slots:     continuous-batching slots per replica (concurrent requests).
    prompt:    prompt length per request (0 = BOS-only generation).
    gen:       tokens generated per request.
    max_len:   decode-cache capacity; prompt + gen must fit.
    pushes:    delta pushes the fleet driver replays per run.
    """

    replicas: int = 2
    slots: int = 2
    prompt: int = 4
    gen: int = 8
    max_len: int = 32
    pushes: int = 3

    def __post_init__(self):
        for f in ("replicas", "slots", "gen", "max_len", "pushes"):
            v = getattr(self, f)
            if not isinstance(v, int) or v <= 0:
                raise SpecError(f"serve.{f} must be a positive int, got "
                                f"{v!r}")
        if not isinstance(self.prompt, int) or self.prompt < 0:
            raise SpecError(f"serve.prompt must be an int >= 0, got "
                            f"{self.prompt!r}")
        if self.prompt + self.gen > self.max_len:
            raise SpecError(
                f"serve.prompt + serve.gen = {self.prompt + self.gen} "
                f"overruns the decode cache (serve.max_len = {self.max_len});"
                " shorten the request or raise max_len")

    @classmethod
    def parse(cls, s: str) -> Optional["ServeSpec"]:
        """'' -> None; 'replicas:4,gen:16' -> ServeSpec(replicas=4, gen=16).
        Unknown keys raise with the known field list."""
        if not s:
            return None
        known = {f.name: f.default for f in dataclasses.fields(cls)}
        kw: dict = {}
        for entry in s.split(","):
            entry = entry.strip()
            if not entry:
                continue
            if ":" not in entry:
                raise SpecError(f"serve entry {entry!r} is not 'key:value'")
            key, val = entry.split(":", 1)
            key = key.strip().replace("-", "_")
            if key not in known:
                raise SpecError(f"unknown serve field {key!r}; known: "
                                f"{sorted(known)}")
            if key in kw:
                raise SpecError(f"serve field {key!r} given twice")
            try:
                kw[key] = int(val)
            except ValueError:
                raise SpecError(f"serve.{key} wants an int, got "
                                f"{val!r}") from None
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """The full experiment, as data.  Frozen + hashable (jit-static safe);
    every field is a JSON scalar so ``to_json`` / ``from_json`` round-trip
    losslessly and :meth:`fingerprint` is stable across field ordering.

    Fields (all optional -- the defaults are the PR-1 smoke setup):

    compressor:    uplink compressor spec ('qsgd:16', 'block_topk:256,16',
                   ...); ';'-separated specs declare a heterogeneous fleet
                   assigned round-robin to the n workers (needs
                   agg='dense_psum').
    mode:          'efbv' | 'ef21' | 'diana' (the (lam, nu) parametrization,
                   auto-tuned per Remark 1) | 'none' (no compression layer).
    agg:           'dense_psum' | 'sparse_allgather' (the wire the trainers
                   aggregate over; the reference backend always runs the
                   exact dense recursion).
    wire_dtype:    value precision of sparse/dense payloads ('float32' |
                   'bfloat16' | 'float16'; quantized codecs ignore it).
    downlink:      master -> worker broadcast compressor spec (optionally
                   '@lam'); '' = uncompressed dense broadcast.
    participation: 'full' | 'bernoulli:p' | 'fixed:s' per-round client
                   sampling.
    resample:      stochastic local gradients (per-round minibatch
                   resampling) instead of exact/streamed gradients.
    pipeline:      'off' | 'depth:1' -- double-buffer the compressed
                   payload so round t applies the message compressed at
                   round t-1 (the exchange overlaps the next backward
                   pass).  'depth:0' parses and means 'off'.  Trainer
                   backends only; the auto-tuning folds the staleness in
                   via theory.pipeline_eta/omega.
    leaf_codecs:   per-leaf codec rules for the pytree-native wire:
                   ';'-separated 'pattern=compressor_spec' entries matched
                   (fnmatch, first wins) against each leaf's '/'-joined
                   path; a bare compressor spec is the default rule '*',
                   and unmatched leaves keep ``compressor``.  '' = the flat
                   single-codec wire.  (lam, nu) are tuned for the
                   worst-case leaf composition (theory.tune_tree).
    backend:       'reference' (vmap-over-workers exact semantics) |
                   'shard_map' (the distributed trainer).
    problem:       'quadratic' | 'logreg' (built-in convex problems, the
                   reference backend) or a model arch id (trainers).
    smoke:         trainer arch problems only: run the reduced (CPU-sized)
                   config of the arch.  Part of the identity -- smoke and
                   full runs of the same arch are DIFFERENT experiments
                   (different model size), so their fingerprints differ
                   and the checkpoint gate keeps them apart.
    mesh:          trainer device mesh, e.g. '2x2' (trailing axes of
                   ('pod', 'data', 'model')); '' for the reference backend.
    n:             number of workers (must equal the mesh's worker-axis
                   product for trainer backends).
    d:             problem dimension; also the dimension the compression
                   constants (eta, omega) are certified at for auto-tuning.
    serve:         replica-fleet serving leg: ','-separated 'key:value'
                   sizing of the simulated fleet fed by the compressed
                   downlink (see :class:`ServeSpec`), e.g.
                   'replicas:4,slots:2,prompt:4,gen:8'.  '' = no serving
                   leg (and the field serializes only when set, so every
                   pre-existing fingerprint is unchanged).  Model-arch
                   problems only -- the built-in convex problems have no
                   decode loop.
    steps:         rounds to run.
    gamma:         stepsize; 0.0 = auto-tune from the theory (Remark 1,
                   built-in problems only).
    seed:          base PRNG seed (problem data, round keys, masks).
    """

    compressor: str = "block_topk:256,16"
    mode: str = "efbv"
    agg: str = "dense_psum"
    wire_dtype: str = "float32"
    downlink: str = ""
    participation: str = "full"
    resample: bool = False
    backend: str = "reference"
    problem: str = "quadratic"
    smoke: bool = False
    mesh: str = ""
    n: int = 8
    d: int = 64
    steps: int = 100
    gamma: float = 0.0
    seed: int = 0
    pipeline: str = "off"
    leaf_codecs: str = ""
    serve: str = ""

    # ---- validation --------------------------------------------------------

    def __post_init__(self):
        from repro.core.compressors import make_compressor
        from repro.core.efbv import Downlink, Participation, Pipeline

        _choice("mode", self.mode, MODES)
        _choice("agg", self.agg, AGG_MODES)
        _choice("backend", self.backend, BACKENDS)
        _choice("wire_dtype", self.wire_dtype, WIRE_DTYPES)
        for f in ("n", "d", "steps"):
            if not isinstance(getattr(self, f), int) or getattr(self, f) <= 0:
                raise SpecError(f"spec.{f} must be a positive int, got "
                                f"{getattr(self, f)!r}")
        if self.gamma < 0:
            raise SpecError(f"spec.gamma must be >= 0 (0 = auto-tune), got "
                            f"{self.gamma}")

        members = self.fleet_specs()
        if not members:
            raise SpecError("spec.compressor is empty")
        for m in members:  # raises ValueError with the registry's message
            make_compressor(m)
        if len(members) > self.n:
            raise SpecError(f"fleet of {len(members)} members for only "
                            f"{self.n} workers")
        if len(set(members)) > 1 and self.agg == "sparse_allgather":
            raise SpecError(
                "heterogeneous fleet + sparse wire: mixed payload shapes "
                "cannot stack over the all-gather; set agg='dense_psum' "
                f"or use a uniform compressor (got {self.compressor!r})")

        if self.smoke and self.problem in REFERENCE_PROBLEMS:
            raise SpecError("spec.smoke selects a model arch's reduced "
                            "config; the built-in problems "
                            f"{REFERENCE_PROBLEMS} are sized by spec.d/n")

        if self.leaf_codecs:
            if len(set(members)) > 1:
                raise SpecError(
                    "spec.leaf_codecs assigns compressors per LEAF of one "
                    "uplink compressor; a heterogeneous fleet assigns them "
                    "per WORKER -- use one or the other (got compressor="
                    f"{self.compressor!r})")
            if self.mode == "none":
                raise SpecError("spec.leaf_codecs configures the compression "
                                "layer's wire; mode='none' has no "
                                "compression layer")
            from repro.distributed import wire
            wire.parse_leaf_rules(self.leaf_codecs)  # raises on a bad rule

        if self.serve:
            ServeSpec.parse(self.serve)  # raises on a bad serve string
            if self.problem in REFERENCE_PROBLEMS:
                raise SpecError(
                    "spec.serve sizes the model-serving fleet; the built-in "
                    f"problems {REFERENCE_PROBLEMS} have no decode loop -- "
                    "set problem to a model arch")

        part = Participation.parse(self.participation)
        if part.kind == "fixed" and part.s > self.n:
            raise SpecError(f"participation 'fixed:{part.s}' needs at least "
                            f"that many workers, spec.n = {self.n}")
        Downlink.parse(self.downlink)  # raises on a bad compressor spec
        pipe = Pipeline.parse(self.pipeline)  # raises on a bad depth spec

        if self.backend == "reference":
            if pipe.depth:
                raise SpecError(
                    "the pipelined schedule double-buffers the trainer's "
                    "wire payload; the reference backend runs the exact "
                    "sequential recursion (set pipeline='off', or "
                    "backend='shard_map')")
            if self.problem not in REFERENCE_PROBLEMS:
                raise SpecError(
                    f"the reference backend runs the built-in problems "
                    f"{REFERENCE_PROBLEMS}, got {self.problem!r}; model "
                    "archs need backend='shard_map'")
            if self.mesh:
                raise SpecError("spec.mesh is a trainer-backend field; the "
                                "reference backend takes n directly (set "
                                "mesh='')")
            if self.resample and self.problem == "quadratic":
                raise SpecError("the quadratic problem has exact gradients "
                                "only; resample=True needs problem='logreg' "
                                "or a trainer backend")
        else:
            if not self.mesh:
                raise SpecError(f"backend {self.backend!r} needs a device "
                                "mesh, e.g. mesh='2x2'")
            workers = self.mesh_workers()
            if workers != self.n:
                raise SpecError(
                    f"spec.n = {self.n} but mesh {self.mesh!r} has {workers} "
                    "workers (product of the non-'model' axes)")
            if self.problem not in REFERENCE_PROBLEMS:
                from repro.configs import ARCHS
                if self.problem not in ARCHS:
                    raise SpecError(
                        f"unknown problem {self.problem!r}: want one of "
                        f"{REFERENCE_PROBLEMS} or a model arch in "
                        f"{sorted(ARCHS)}")

    # ---- derived views -----------------------------------------------------

    def fleet_specs(self) -> Tuple[str, ...]:
        """The ';'-separated compressor members (length 1 = homogeneous)."""
        return tuple(s.strip() for s in self.compressor.split(";")
                     if s.strip())

    def serve_spec(self) -> Optional["ServeSpec"]:
        """The parsed serving leg (None when ``serve`` is unset)."""
        return ServeSpec.parse(self.serve)

    def mesh_dims(self) -> Tuple[int, ...]:
        try:
            return tuple(int(x) for x in self.mesh.split("x"))
        except ValueError:
            raise SpecError(f"spec.mesh {self.mesh!r} is not an 'AxBxC' "
                            "integer shape") from None

    def mesh_workers(self) -> int:
        """Worker count of the mesh: product of the non-'model' axes
        (axes are the trailing names of ('pod', 'data', 'model'), matching
        repro.launch.mesh.make_mesh)."""
        return mesh_worker_count(self.mesh_dims())

    # ---- serialization -----------------------------------------------------

    def to_dict(self) -> dict:
        d = {"spec_version": SPEC_VERSION, **dataclasses.asdict(self)}
        # Fields added after spec_version 1 shipped serialize only when
        # non-default: 'off' IS the default, so dropping it keeps every
        # pre-existing spec file and fingerprint byte-stable, and the
        # "equal specs <-> equal fingerprints" property still holds.
        if self.pipeline == "off":
            del d["pipeline"]
        if self.leaf_codecs == "":
            del d["leaf_codecs"]
        if self.serve == "":
            del d["serve"]
        return d

    def to_json(self, indent: Optional[int] = 1) -> str:
        """Lossless JSON form (``from_json(to_json(s)) == s``)."""
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True) + "\n"

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentSpec":
        d = dict(d)
        version = d.pop("spec_version", SPEC_VERSION)
        if version != SPEC_VERSION:
            raise SpecError(f"spec_version {version!r} != {SPEC_VERSION} "
                            "(this build cannot read that spec)")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise SpecError(f"unknown spec fields {unknown}; known: "
                            f"{sorted(known)}")
        return cls(**d)

    @classmethod
    def from_json(cls, text: str) -> "ExperimentSpec":
        return cls.from_dict(json.loads(text))

    def fingerprint(self) -> str:
        """Stable 16-hex-digit hash of the spec: independent of field
        ordering and formatting (canonical sorted-key JSON underneath), and
        includes the defaults, so two specs are equal iff their fingerprints
        are.  Checkpoints embed it to refuse mismatched resumes; the CI
        bench keys its rows by it."""
        canon = json.dumps(self.to_dict(), sort_keys=True,
                           separators=(",", ":"))
        return hashlib.sha256(canon.encode()).hexdigest()[:16]

    # ---- CLI-style parsing -------------------------------------------------

    @classmethod
    def parse(cls, argv: Union[str, Sequence[str]]) -> "ExperimentSpec":
        """Build a spec from CLI-style strings.

        Accepts one string or a token list, in '--key value', '--key=value'
        or bare 'key=value' form ('-' and '_' interchangeable in keys);
        boolean fields also take the bare '--resample' flag form.  Unknown
        keys raise with the list of known fields.

            ExperimentSpec.parse("--compressor qsgd:16 --downlink qsgd:16")
            ExperimentSpec.parse(["participation=bernoulli:0.5", "--n", "8"])
        """
        toks = argv.split() if isinstance(argv, str) else list(argv)
        defaults = {f.name: f.default for f in dataclasses.fields(cls)}
        kw: dict = {}
        i = 0
        while i < len(toks):
            tok = toks[i]
            key = tok[2:] if tok.startswith("--") else tok
            if "=" in key:
                key, val = key.split("=", 1)
                i += 1
            else:
                if not tok.startswith("--"):
                    raise SpecError(f"cannot parse token {tok!r}: want "
                                    "'--key value' or 'key=value'")
                nxt = toks[i + 1] if i + 1 < len(toks) else None
                if isinstance(defaults.get(key.replace("-", "_")), bool) and (
                        nxt is None or nxt.startswith("--") or "=" in nxt):
                    val = "true"
                    i += 1
                else:
                    if nxt is None:
                        raise SpecError(f"flag {tok!r} is missing a value")
                    val = nxt
                    i += 2
            key = key.replace("-", "_")
            if key not in defaults:
                raise SpecError(f"unknown spec field {key!r}; known: "
                                f"{sorted(defaults)}")
            kw[key] = _coerce(key, val, defaults[key])
        return cls(**kw)


def mesh_worker_count(dims: Sequence[int]) -> int:
    """The EF-BV worker count of a mesh shape: product of the non-'model'
    axes, where axes are the trailing names of ('pod', 'data', 'model') --
    THE formula (shared with launch.mesh.num_workers semantics), so spec
    validation, the train driver and the CI bench can never drift."""
    dims = tuple(dims)
    axes = ("pod", "data", "model")[-len(dims):]
    return int(math.prod(s for s, a in zip(dims, axes) if a != "model"))


def _coerce(key: str, val: str, default: Any) -> Any:
    if isinstance(default, bool):
        low = str(val).lower()
        if low in ("1", "true", "yes", "on"):
            return True
        if low in ("0", "false", "no", "off"):
            return False
        raise SpecError(f"spec.{key} wants a boolean, got {val!r}")
    try:
        if isinstance(default, int):
            return int(val)
        if isinstance(default, float):
            return float(val)
    except ValueError:
        raise SpecError(f"spec.{key} wants {type(default).__name__}, got "
                        f"{val!r}") from None
    return val


# -----------------------------------------------------------------------------
# built-in reference problems
# -----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Quadratic:
    """Strongly convex quadratic finite sum f_i(x) = 0.5 x'Q_i x - b_i'x
    (the differential harness's trajectory problem, exported so spec-driven
    reference runs and tests/harness.py draw the SAME gradients)."""

    Q: Any  # (n, d, d)
    b: Any  # (n, d)

    @staticmethod
    def make(n: int, d: int, seed: int = 0) -> "Quadratic":
        import jax
        import jax.numpy as jnp
        import numpy as np

        key = jax.random.key(seed)
        A = jax.random.normal(key, (n, d, d)) / np.sqrt(d)
        Q = jnp.einsum("nij,nkj->nik", A, A) + 0.5 * jnp.eye(d)
        b = jax.random.normal(jax.random.key(seed + 1), (n, d))
        return Quadratic(Q=Q, b=b)

    @property
    def n(self) -> int:
        return self.Q.shape[0]

    @property
    def d(self) -> int:
        return self.Q.shape[2]

    def grads(self, x):
        """Per-worker gradients Q_i x - b_i, shape (n, d)."""
        import jax.numpy as jnp

        return jnp.einsum("nij,j->ni", self.Q, x) - self.b

    def f(self, x):
        import jax.numpy as jnp

        quad = 0.5 * jnp.einsum("j,nij,i->n", x, self.Q, x)
        return jnp.mean(quad - self.b @ x)

    def L_i(self):
        import jax.numpy as jnp

        return jnp.linalg.eigvalsh(self.Q)[:, -1]

    def L(self) -> float:
        import jax.numpy as jnp

        return float(jnp.max(self.L_i()))

    def L_tilde(self) -> float:
        import jax.numpy as jnp

        return float(jnp.sqrt(jnp.mean(self.L_i() ** 2)))

    def solve(self):
        """Exact minimizer of the average: mean(Q) x* = mean(b)."""
        import jax.numpy as jnp

        x_star = jnp.linalg.solve(jnp.mean(self.Q, 0), jnp.mean(self.b, 0))
        return x_star, float(self.f(x_star))


# -----------------------------------------------------------------------------
# the Run object: one entry point over every backend
# -----------------------------------------------------------------------------

class Run:
    """A built (executable) experiment.  Construct via :func:`build`.

    Exposes the spec's derived objects (``algo``, ``participation``,
    ``downlink``), the reference driver (:meth:`reference`), the
    distributed trainer (:meth:`train_step`), the exact wire accounting
    (:meth:`round_bits`) and the paper's auto-tuning (:attr:`tuned`)."""

    def __init__(self, spec: ExperimentSpec):
        from repro.core.compressors import Identity, make_compressor
        from repro.core.efbv import EFBV, Downlink, Participation, Pipeline

        self.spec = spec
        self.participation: Participation = Participation.parse(
            spec.participation)
        self.downlink: Optional[Downlink] = Downlink.parse(spec.downlink)
        self.pipeline: Pipeline = Pipeline.parse(spec.pipeline)
        members = tuple(make_compressor(s) for s in spec.fleet_specs())
        self.leaf_rules = None
        if spec.leaf_codecs:
            from repro.distributed import wire
            self.leaf_rules = wire.parse_leaf_rules(spec.leaf_codecs)
        if spec.mode == "none":
            self.algo = EFBV(Identity(), lam=1.0, nu=1.0)
        else:
            comp = members if len(members) > 1 else members[0]
            self.algo = EFBV.make(
                comp, d=spec.d, n=spec.n, mode=spec.mode,
                participation=(self.participation.fraction(spec.n)
                               if self.federated else None),
                pipeline=self.pipeline.depth or None,
                leaf_rules=self.leaf_rules)
        self.compressor = self.algo.compressor

    def __repr__(self):
        return (f"Run(fingerprint={self.spec.fingerprint()}, "
                f"backend={self.spec.backend!r}, "
                f"compressor={self.spec.compressor!r})")

    # ---- derived properties ------------------------------------------------

    @property
    def federated(self) -> bool:
        return not self.participation.is_full

    @property
    def n(self) -> int:
        return self.spec.n

    def _tune(self, **kw):
        """The spec's auto-tuning call, shared by :attr:`tuned` and the
        auto-stepsize path: fleet / per-leaf / participation / pipeline
        composition on the SAME compressor objects ``algo`` was tuned
        with."""
        from repro.core import theory

        spec = self.spec
        part = (self.participation.fraction(spec.n) if self.federated
                else None)
        if self.algo.leaf_rules:
            comps = [self.compressor] + [c for _, c in self.algo.leaf_rules]
            return theory.tune_tree(
                [c.eta(spec.d) for c in comps],
                [c.omega(spec.d) for c in comps],
                n=spec.n, aggregate="worst", mode=spec.mode,
                participation=part, pipeline=self.pipeline.depth or None,
                **kw)
        return theory.tune_for(
            self.algo.fleet if self.algo.fleet is not None
            else self.compressor,
            spec.d, spec.n, mode=spec.mode, participation=part,
            pipeline=self.pipeline.depth or None, **kw)

    @property
    def tuned(self):
        """The paper's auto-tuning for this spec (delegates to
        :func:`repro.core.theory.tune_for` -- or ``tune_tree`` under
        per-leaf codec rules: fleet / participation composition included,
        on the SAME compressor objects ``algo`` was tuned with).  None for
        mode='none'."""
        if self.spec.mode == "none":
            return None
        return self._tune()

    # ---- built-in problems -------------------------------------------------

    def problem_instance(self):
        """The built-in reference problem (:class:`Quadratic` or
        :class:`repro.problems.LogReg`), seeded from the spec."""
        spec = self.spec
        if spec.problem == "quadratic":
            return Quadratic.make(spec.n, spec.d, spec.seed)
        if spec.problem == "logreg":
            import jax

            from repro.problems import LogReg, make_synthetic

            A, b = make_synthetic(jax.random.key(spec.seed), N=16 * spec.d,
                                  d=spec.d)
            return LogReg.split(A, b, n=spec.n, mu_reg=0.1)
        raise SpecError(f"problem {spec.problem!r} is a model arch: build "
                        "its loss via repro.models and use .train_step()")

    # ---- the reference driver ----------------------------------------------

    def reference(self, grad_fn: Optional[Callable] = None,
                  x0: Optional[PyTree] = None, *,
                  gamma: Optional[float] = None,
                  prox: Optional[Callable] = None,
                  record: Optional[Callable] = None,
                  key=None):
        """Run the exact reference recursion of this spec: ONE driver for
        plain / federated / bidirectional execution
        (:func:`repro.core.efbv.run_reference`).

        With no arguments the spec is self-contained: the built-in problem
        supplies ``grad_fn``/``x0`` (stochastic minibatch gradients when
        ``spec.resample``) and, when ``spec.gamma == 0``, the auto-tuned
        stepsize of Remark 1.  Custom problems pass ``grad_fn`` (signature
        ``x -> grads`` or ``(key, x) -> grads``), ``x0`` and ``gamma``.
        Returns a :class:`repro.core.efbv.ReferenceRun`.
        """
        import jax
        import jax.numpy as jnp

        from repro.core import efbv

        spec = self.spec
        if grad_fn is not None and gamma is None and spec.gamma == 0.0:
            # auto-tuned stepsizes need the PROBLEM's smoothness constants;
            # silently using the built-in problem's would misstep a custom
            # objective
            raise SpecError("a custom grad_fn needs a stepsize: pass "
                            "gamma= (or set spec.gamma > 0)")
        # (x0 defaults to zeros without touching the problem, so a custom
        # grad_fn never pays for building the built-in problem)
        prob = self.problem_instance() if grad_fn is None else None

        if grad_fn is None:
            if spec.resample:
                batch = max(1, prob.A.shape[1] // 8)
                gf = lambda k, x: prob.minibatch_grads(k, x, batch)  # noqa: E731
            else:
                gf = lambda _k, x: prob.grads(x)  # noqa: E731
        else:
            try:
                takes_key = len(inspect.signature(grad_fn).parameters) >= 2
            except (TypeError, ValueError):
                takes_key = False
            gf = grad_fn if takes_key else (lambda _k, x: grad_fn(x))

        if x0 is None:
            x0 = jnp.zeros((spec.d,), jnp.float32)
        if gamma is None:
            gamma = spec.gamma if spec.gamma > 0.0 else None
        if gamma is None:
            if spec.mode == "none":
                gamma = 1.0 / prob.L()
            else:
                gamma = self._tune(L=prob.L(), Ltilde=prob.L_tilde()).gamma
        if key is None:
            # decorrelated from the problem-data key (jax.random.key(seed))
            key = jax.random.fold_in(jax.random.key(spec.seed),
                                     efbv.REFERENCE_FOLD)

        return efbv.run_reference(
            algo=self.algo, grad_fn=gf, x0=x0, gamma=gamma, steps=spec.steps,
            key=key, n=spec.n, participation=self.participation,
            downlink=self.downlink, prox=prox or efbv.prox_zero,
            record=record, wire_dtype=spec.wire_dtype)

    # ---- the distributed trainer -------------------------------------------

    def make_mesh(self):
        """The spec's device mesh (trainer backends).  The process must
        already expose enough XLA devices -- launch/train.py forces the
        host-device count from the spec before jax initializes."""
        from repro.launch.mesh import make_mesh

        if self.spec.backend == "reference":
            raise SpecError("the reference backend has no device mesh; use "
                            ".reference()")
        return make_mesh(self.spec.mesh_dims())

    def train_step(self, loss_fn: Callable, optimizer, mesh=None,
                   **kw) -> Callable:
        """The jitted distributed train step of this spec, threading
        agg/wire_dtype/downlink/participation from the spec."""
        from repro.train import make_train_step

        if self.spec.backend == "reference":
            raise SpecError("backend='reference' has no distributed trainer:"
                            " use .reference(), or set backend='shard_map'")
        mesh = self.make_mesh() if mesh is None else mesh
        return make_train_step(loss_fn, optimizer, self.algo, mesh,
                               agg_mode=self.spec.agg,
                               wire_dtype=self.spec.wire_dtype,
                               downlink=self.downlink,
                               participation=self.participation,
                               pipeline=self.pipeline, **kw)

    def init_state(self, params: PyTree, optimizer, mesh):
        """TrainState for this spec (bidirectional iff a downlink is set;
        a zero-decoding in-flight payload buffer iff pipelined)."""
        from repro.train import init_train_state

        return init_train_state(params, optimizer, mesh,
                                bidirectional=self.downlink is not None,
                                algo=self.algo, agg_mode=self.spec.agg,
                                wire_dtype=self.spec.wire_dtype,
                                pipeline=self.pipeline)

    def state_shardings(self, mesh, param_specs: PyTree, state):
        """NamedShardings for the TrainState."""
        from repro.train import train_state_shardings

        return train_state_shardings(mesh, param_specs, state)

    # ---- exact wire accounting ---------------------------------------------

    def round_bits(self, tree: Optional[PyTree] = None, *,
                   participants: Optional[float] = None) -> dict:
        """Exact bits one round puts on the wire, both directions, for a
        gradient pytree shaped like ``tree`` (default: the spec's flat
        (d,) problem vector).

        Delegates to :func:`repro.distributed.wire.total_round_bits`
        (uplink x n + ONE broadcast, federated accounting composed into the
        uplink term) and, for heterogeneous fleets, to
        :func:`repro.distributed.wire.fleet_bits_per_round`.  Returns
        ``{'up', 'down', 'total', 'dense_both_ways'}``.
        """
        import jax
        import jax.numpy as jnp

        from repro.distributed import wire

        spec = self.spec
        if tree is None:
            tree = jnp.zeros((spec.d,), jnp.float32)
        n = spec.n
        if participants is None and self.federated:
            participants = self.participation.fraction(n) * n
        down_fmt = (None if self.downlink is None else
                    self.downlink.format_for(tree,
                                             wire_dtype=spec.wire_dtype))
        if self.algo.fleet is not None:
            fmts = wire.fleet_formats(self.algo.fleet, tree,
                                      wire_dtype=spec.wire_dtype)
            up = wire.fleet_bits_per_round(fmts)
            if participants is not None:
                # expected federated fleet round: participation bitmap +
                # each worker's own payload weighted by its inclusion
                # probability E|S_t|/n (uniform across workers for both
                # bernoulli and fixed-size sampling)
                bitmap = 32 * wire.bitmap_words(n)
                per_fleet = sum(f.bits_per_round() for f in fmts)
                if float(participants).is_integer():
                    # exact participant count: stay in int arithmetic (a
                    # float product silently rounds above 2**53)
                    num = int(participants) * per_fleet
                    up = (bitmap + num // n if num % n == 0
                          else bitmap + num / n)
                else:
                    up = bitmap + participants / n * per_fleet
            dense = fmts[0].dense_bits()
            down = (dense if down_fmt is None
                    else down_fmt.downlink_bits_per_round())
            total = up + down
        else:
            up_fmt = wire.tree_format_for(self.compressor, tree,
                                          wire_dtype=spec.wire_dtype,
                                          rules=self.algo.leaf_rules)
            up = up_fmt.bits_per_round(n_workers=n, participants=participants)
            total = wire.total_round_bits(up_fmt, down_fmt, n_workers=n,
                                          participants=participants)
            down = total - up
            dense = up_fmt.dense_bits()
        return {"up": up, "down": down, "total": total,
                "dense_both_ways": n * dense + dense}


def build(spec: ExperimentSpec) -> Run:
    """THE entry point: spec -> executable :class:`Run`.

        >>> from repro.core import ExperimentSpec, build
        >>> run = build(ExperimentSpec(compressor="qsgd:16", n=4, d=256))
        >>> round(run.algo.lam, 4), round(run.algo.nu, 4)
        (0.5, 0.8)
    """
    if isinstance(spec, dict):
        spec = ExperimentSpec.from_dict(spec)
    if not isinstance(spec, ExperimentSpec):
        raise SpecError(f"build() wants an ExperimentSpec (or its dict "
                        f"form), got {type(spec).__name__}")
    return Run(spec)
