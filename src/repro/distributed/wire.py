"""Wire codecs: payload layouts, exact bit accounting, and the pack /
unpack / scatter-add helpers shared by the reference and shard_map paths.

The paper's accounting ("number of bits sent by each node ... proportional to
t*k", Sect. 6) only holds if the bytes that cross the wire are the payload,
not a dense mask-compressed tensor.  This module is the single source of
truth for what that payload IS, for EVERY compressor in the C(eta, omega)
zoo -- each compressor declares a :class:`LeafCodec` via ``Compressor.codec``
and :func:`format_for` assembles the per-pytree :class:`WireFormat`:

  codec           compressors                       payload (one leaf, d elems)
  --------------  --------------------------------  ---------------------------
  LeafWire        block-top-k                       (values, local idx) (nb, kb)
  FlatSparse      top-k, rand-k, scaled-rand-k,     (values, global idx) (k,)
                  comp-(k,k'), mix-(k,k'), frac-*
  SignPack        sign (L1-norm scaled)             f32 scale + uint32 bitmap
  QsgdQuant       QSGD(s)                           f32 norm + int8/16 levels
  NaturalPack     natural compression               int8 exponents + sign bitmap
  DensePack       identity, m-nice                  raw values (wire dtype)

``val_dtype`` (float32 / bfloat16 / float16) is an orthogonal knob on the
value-carrying codecs (sparse values, dense streams); scales, norms, signs
and exponents are dtype-fixed.  ``payload_bits`` is EXACT for every codec:
the wire tests assert ``8 * payload_nbytes == payload_bits``, equality, not
proportionality.

Three producers of the block-sparse layout are pinned bit-identical by the
differential harness (tests/harness.py) -- jnp oracle, fused Pallas kernel in
interpret mode, and the same kernel compiled on TPU -- and the rand-k and
QSGD codecs have their own fused kernels (kernels/pack.py) pinned the same
way.  See docs/wire_format.md and docs/compressor_zoo.md.

Federated rounds (per-round client sampling, docs/algorithms.md) gate
messages through :meth:`LeafCodec.mask_message` -- an absent worker's
payload decodes to exactly zero, a present worker's is bitwise untouched --
and ``WireFormat.bits_per_round(participants=...)`` /
:func:`federated_round_bits` account the variable-participant wire: an
n-worker participation bitmap plus only the |S_t| sampled payloads.

The wire is bidirectional: the master -> worker broadcast
(core/efbv.py::Downlink) reuses the same codecs -- ONE message per round
regardless of n or S_t, ``WireFormat.downlink_bits_per_round()`` exact --
and :func:`total_round_bits` composes uplink + downlink with the federated
accounting.  Heterogeneous fleets (per-worker compressors) account their
mixed payloads through :func:`fleet_formats` / :func:`fleet_bits_per_round`.
See docs/wire_format.md#the-downlink-payload.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import math
import os
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
PyTree = Any

# kernel dispatch for the fused pack paths: 'auto' uses the compiled Pallas
# kernel on TPU and the jnp oracle elsewhere; 'interpret' forces the Pallas
# kernel in interpret mode (slow -- differential testing only); 'oracle'
# forces jnp.  Codecs without a fused kernel always take the oracle under
# 'auto' and reject an *explicit* kernel request.
KERNEL_MODES = ("auto", "pallas", "interpret", "oracle")

VAL_DTYPES = ("float32", "bfloat16", "float16")
_VAL_BITS = {"float32": 32, "bfloat16": 16, "float16": 16}


def _kernel_mode(kernel: Optional[str]) -> str:
    mode = kernel or os.environ.get("REPRO_WIRE_KERNEL", "auto")
    if mode not in KERNEL_MODES:
        raise ValueError(f"wire kernel {mode!r} not in {KERNEL_MODES}")
    if mode == "auto":
        mode = "pallas" if jax.default_backend() == "tpu" else "oracle"
    return mode


def _val_bits(val_dtype: str) -> int:
    if val_dtype not in _VAL_BITS:
        raise ValueError(f"wire value dtype {val_dtype!r} not in {VAL_DTYPES}")
    return _VAL_BITS[val_dtype]


# ---------------------------------------------------------------------------
# bit packing helpers (sign bitmaps)
# ---------------------------------------------------------------------------

def bitmap_words(nbits: int) -> int:
    return -(-nbits // 32)


def pack_bits(bits: Array) -> Array:
    """(m,) boolean -> (ceil(m/32),) uint32, LSB-first within each word."""
    m = bits.shape[0]
    w = bitmap_words(m)
    b = jnp.pad(bits.astype(jnp.uint32), (0, 32 * w - m)).reshape(w, 32)
    return jnp.sum(b << jnp.arange(32, dtype=jnp.uint32), axis=1,
                   dtype=jnp.uint32)


def unpack_bits(words: Array, m: int) -> Array:
    """(w,) uint32 -> (m,) boolean, inverse of :func:`pack_bits`."""
    b = (words[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return b.reshape(-1)[:m].astype(jnp.bool_)


# ---------------------------------------------------------------------------
# codec base class
# ---------------------------------------------------------------------------

class LeafCodec:
    """Wire codec of one pytree leaf: how a compressed message is laid out
    on the wire, with exact bit accounting.

    Subclasses are frozen dataclasses carrying at least ``shape`` and
    ``size``.  A payload is a tuple of arrays; ``encode`` consumes the flat
    f32 innovation ``delta`` (compress-and-pack in one step, losslessly
    representing the compressor's dense output), ``decode`` reproduces that
    dense output bit-for-bit (the property tests assert equality, not
    closeness), and ``decode_sum`` additionally accepts worker-stacked
    payloads (leading axis n) and returns the scatter-SUM -- the local
    combine of the sparse_allgather collective.
    """

    kind: str = "abstract"
    #: ndim of the first payload component in a single (un-stacked) message
    MSG_NDIM: int = 1

    # -- accounting ---------------------------------------------------------
    @property
    def payload_bits(self) -> int:
        """Exact bits of one worker's message for this leaf."""
        raise NotImplementedError

    @property
    def has_kernel(self) -> bool:
        """True if a fused Pallas compress-and-pack kernel exists."""
        return False

    @property
    def kernel_gap(self) -> Optional[str]:
        """Why this leaf cannot use its codec's fused kernel (it then takes
        the jnp oracle, on a TPU too); None where the kernel runs or the
        codec has none."""
        return None

    # -- pack / unpack ------------------------------------------------------
    def encode(self, key: Optional[Array], delta: Array) -> Tuple[Array, ...]:
        """Flat f32 innovation -> payload tuple."""
        raise NotImplementedError

    # -- partial participation ---------------------------------------------
    def mask_message(self, payload: Sequence[Array], m: Array
                     ) -> Tuple[Array, ...]:
        """Gate a message on a participation mask: an absent worker's
        (m = 0) payload must decode to exactly zero so the federated round's
        decode-sum only sees the sampled subset S_t.

        ``m`` broadcasts: a scalar gates one un-stacked message, an (n,)
        mask gates the worker-stacked all-gather form.  Default: scale the
        leading value-carrying component (sparse values / sign scale / QSGD
        norm / dense stream) in ITS dtype, so m = 1 is a bitwise identity --
        full participation stays bit-identical to the unmasked wire.
        Codecs whose zero is a sentinel (NaturalPack) override.
        """
        head, *rest = payload
        mm = jnp.asarray(m, head.dtype)
        mm = mm.reshape(mm.shape + (1,) * (head.ndim - mm.ndim))
        return (head * mm, *rest)

    def decode(self, payload: Sequence[Array]) -> Array:
        """One payload -> dense flat f32 (size,) vector, bit-equal to the
        dense compressor output."""
        raise NotImplementedError

    def decode_sum(self, payload: Sequence[Array]) -> Array:
        """Payload (possibly worker-stacked on a leading axis) -> dense flat
        (size,) sum over workers (divide by n for the master mean)."""
        if jax.tree.leaves(payload)[0].ndim > self.MSG_NDIM:
            return jnp.sum(jax.vmap(self.decode)(tuple(payload)), axis=0)
        return self.decode(payload)

    # -- fused worker update ------------------------------------------------
    def encode_update(self, key: Optional[Array], g: Array, h: Array,
                      lam: float, *, kernel: Optional[str] = None,
                      stream: bool = False
                      ) -> Tuple[Tuple[Array, ...], Array]:
        """(payload, h') with d = C(g - h) packed and h' = h + lam d.

        The base implementation is the jnp oracle (encode, scatter back,
        update); codecs with a fused Pallas kernel override it and stay
        bit-identical to this oracle.  ``stream`` asks codecs with an
        async-copy kernel variant to DMA the payload out while the h
        update computes; everyone else ignores it (results are
        bit-identical either way).
        """
        mode = _kernel_mode(kernel)
        if mode in ("pallas", "interpret") and kernel in ("pallas", "interpret"):
            raise ValueError(
                f"{type(self).__name__} has no fused kernel; use kernel="
                f"'oracle' or 'auto'")
        delta = g.astype(jnp.float32) - h.astype(jnp.float32)
        payload = self.encode(key, delta.reshape(-1))
        d = self.decode(payload).reshape(g.shape)
        h_new = (h.astype(jnp.float32) + float(lam) * d).astype(h.dtype)
        return payload, h_new


# ---------------------------------------------------------------------------
# block-sparse codec (block-top-k; the PR-1 format)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafWire(LeafCodec):
    """Block-sparse layout: per-block (values, block-LOCAL indices), shapes
    (nb, kb) each.  Local indices keep every index < block (no int32
    overflow on 4e10-element stacked expert tensors) and make the payload
    independent of the leaf's global offset, so the same scatter-add decodes
    one message and the worker-stacked (n, nb, kb) all-gather result."""

    shape: Tuple[int, ...]
    size: int
    block: int
    kb: int
    val_dtype: str = "float32"

    kind = "block_sparse"
    MSG_NDIM = 2

    @property
    def nb(self) -> int:
        return -(-self.size // self.block)

    @property
    def payload_bits(self) -> int:
        """Exact bits of one worker's message for this leaf: values +
        int32 local indices, (nb, kb) each."""
        return self.nb * self.kb * (_val_bits(self.val_dtype) + 32)

    @property
    def has_kernel(self) -> bool:
        return self.kernel_gap is None

    @property
    def kernel_gap(self) -> Optional[str]:
        # the Pallas kernel tiles 128-lane slabs, at least 128 blocks a grid
        # step, and that tile must fit its VMEM.  Non-f32 value payloads
        # take the oracle: the control variate must track the DECODED
        # payload (what the master adds), and the fused kernel updates h
        # with the pre-cast f32 values
        if self.block % 128:
            return f"block {self.block} is not a multiple of 128"
        from repro.kernels import pack
        gap = pack.pack_vmem_gap(self.block, self.kb)
        if gap is not None:
            return gap
        if self.val_dtype != "float32":
            return f"{self.val_dtype} wire values"
        return None

    def encode(self, key, delta):
        vals, idx = pack_oracle(self, delta)
        return vals.astype(jnp.dtype(self.val_dtype)), idx

    def decode(self, payload):
        vals, idx = payload
        return scatter_add(self, vals.astype(jnp.float32), idx)

    decode_sum = decode  # scatter_add natively handles the stacked form

    def encode_update(self, key, g, h, lam, *, kernel=None, stream=False):
        # the fused path emits payload values in g's dtype and updates h with
        # the f32 scatter; both equal the decoded payload only for f32 wires.
        # kernel= is forwarded so an explicit kernel request on a non-f32
        # wire errors (base class) instead of silently taking the oracle.
        if self.val_dtype != "float32" or g.dtype != jnp.float32:
            return LeafCodec.encode_update(self, key, g, h, lam,
                                           kernel=kernel)
        return fused_pack(self, g, h, lam, kernel=kernel, stream=stream)


# ---------------------------------------------------------------------------
# flat-sparse codec (top-k / rand-k / comp-(k,k') / mix-(k,k') families)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlatSparse(LeafCodec):
    """(values, global int32 indices), (k,) each.  ``selector`` is the
    compressor whose ``encode`` picks the k kept coordinates (and applies
    any unbiasedness scaling); it is a frozen dataclass, so the codec stays
    hashable/jit-static.  Global flat indices require size < 2**31 -- the
    block-sparse codec is the one that scales past int32 leaves."""

    shape: Tuple[int, ...]
    size: int
    k: int
    selector: Any
    val_dtype: str = "float32"

    kind = "flat_sparse"
    MSG_NDIM = 1

    @property
    def payload_bits(self) -> int:
        return self.k * (_val_bits(self.val_dtype) + 32)

    def encode(self, key, delta):
        vals, idx = self.selector.encode(key, delta)
        return vals.astype(jnp.dtype(self.val_dtype)), idx.astype(jnp.int32)

    def decode(self, payload):
        vals, idx = payload
        return jnp.zeros((self.size,), jnp.float32).at[idx.reshape(-1)].add(
            vals.astype(jnp.float32).reshape(-1))

    # the flat scatter-add natively handles the worker-stacked (n, k) form:
    # one (size,) scatter of n*k pairs, never an (n, size) dense intermediate
    decode_sum = decode


@dataclasses.dataclass(frozen=True)
class RandKSparse(FlatSparse):
    """FlatSparse specialised to rand-k: index selection is data-independent,
    which is what makes the fused Pallas h-update kernel possible (the k
    selected positions are drawn outside, the kernel does the dense-free
    h <- h + lam d pass, and the payload values are an O(k) gather)."""

    kind = "randk_sparse"

    @property
    def has_kernel(self) -> bool:
        return self.kernel_gap is None

    @property
    def kernel_gap(self) -> Optional[str]:
        # the kernel compares f32 linear positions (exact below 2**24) and
        # updates h with the unquantized f32 values (== the decoded payload
        # only for f32 wires)
        if self.size >= 2 ** 24:
            return f"size {self.size} >= 2**24"
        if self.val_dtype != "float32":
            return f"{self.val_dtype} wire values"
        return None

    def encode_update(self, key, g, h, lam, *, kernel=None, stream=False):
        del stream  # the rand-k gather kernel has no streaming variant
        mode = _kernel_mode(kernel)
        if mode in ("pallas", "interpret") and not self.has_kernel:
            if kernel in ("pallas", "interpret"):
                raise ValueError(
                    "rand-k fused kernel requires size < 2**24 and a float32"
                    f" wire, got size={self.size} val_dtype={self.val_dtype}")
            mode = "oracle"
        if mode == "oracle":
            return LeafCodec.encode_update(self, key, g, h, lam,
                                           kernel="oracle")
        from repro.kernels import ops
        gf, hf = g.reshape(-1), h.reshape(-1)
        scale = self.size / self.k
        idx = jax.random.choice(key, self.size, shape=(self.k,), replace=False)
        # gather-of-difference == difference-of-gathers, bitwise; the dense
        # delta is never materialized (the kernel recomputes it in VMEM)
        vals = (gf[idx].astype(jnp.float32)
                - hf[idx].astype(jnp.float32)) * scale
        h_new = ops.randk_update(g, h, idx.astype(jnp.int32), float(lam),
                                 float(scale),
                                 interpret=(mode == "interpret"))
        return ((vals.astype(jnp.dtype(self.val_dtype)),
                 idx.astype(jnp.int32)), h_new)


# ---------------------------------------------------------------------------
# 1-bit sign codec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SignPack(LeafCodec):
    """L1-norm-scaled sign: one f32 scale + an LSB-first uint32 sign bitmap
    (bit set <=> coordinate is negative).  32 + 32*ceil(d/32) bits, i.e.
    ~1 bit per coordinate."""

    shape: Tuple[int, ...]
    size: int

    kind = "sign_pack"
    MSG_NDIM = 1

    @property
    def payload_bits(self) -> int:
        return 32 + 32 * bitmap_words(self.size)

    def encode(self, key, delta):
        scale = jnp.sum(jnp.abs(delta)) / delta.shape[0]
        return scale.reshape(1).astype(jnp.float32), pack_bits(delta < 0)

    def decode(self, payload):
        scale, words = payload
        sgn = jnp.where(unpack_bits(words, self.size), -1.0, 1.0)
        return scale[0] * sgn


# ---------------------------------------------------------------------------
# QSGD quantized codec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QsgdQuant(LeafCodec):
    """QSGD(s): one f32 L2 norm + a signed integer level stream, level in
    [-s, s] (int8 when s <= 127, int16 otherwise).  32 + 8*d (or 16*d) bits
    -- <= 1/3 of the fp32 dense tensor, measured, not estimated."""

    shape: Tuple[int, ...]
    size: int
    s: int

    kind = "qsgd_quant"
    MSG_NDIM = 1

    @property
    def level_dtype(self):
        return jnp.int8 if self.s <= 127 else jnp.int16

    @property
    def payload_bits(self) -> int:
        return 32 + self.size * (8 if self.s <= 127 else 16)

    @property
    def has_kernel(self) -> bool:
        return True

    def _levels(self, key, delta, norm):
        """Replicates QSGD.__call__'s stochastic rounding draw exactly."""
        safe = jnp.where(norm > 0, norm, 1.0)
        level = jnp.abs(delta) / safe * self.s
        low = jnp.floor(level)
        up = jax.random.uniform(key, delta.shape) < (level - low)
        return jnp.sign(delta) * (low + up.astype(jnp.float32))

    def encode(self, key, delta):
        norm = jnp.linalg.norm(delta)
        lv = self._levels(key, delta, norm)
        return norm.reshape(1).astype(jnp.float32), lv.astype(self.level_dtype)

    def decode(self, payload):
        norm, lv = payload
        lf = lv.astype(jnp.float32)
        # same op chain as QSGD.__call__: (norm * sign) * (level * 1/s).
        # The vector predicate (not the compressor's scalar norm > 0) only
        # changes zero-level lanes from +-0 to +0 -- value-equal -- and is
        # what lets the fused kernel's jitted tail avoid FMA contraction.
        return jnp.where(lf != 0,
                         (norm[0] * jnp.sign(lf))
                         * (jnp.abs(lf) * (1.0 / self.s)),
                         0.0)

    def encode_update(self, key, g, h, lam, *, kernel=None, stream=False):
        del stream  # the qsgd quantizer has no streaming variant
        mode = _kernel_mode(kernel)
        if mode == "oracle":
            return LeafCodec.encode_update(self, key, g, h, lam,
                                           kernel="oracle")
        from repro.kernels import ops
        norm = jnp.linalg.norm(g.reshape(-1).astype(jnp.float32)
                               - h.reshape(-1).astype(jnp.float32))
        u = jax.random.uniform(key, (self.size,))
        levels, h_new = ops.qsgd_pack_update(
            g, h, u, norm, float(lam), self.s,
            interpret=(mode == "interpret"))
        return (norm.reshape(1).astype(jnp.float32), levels), h_new


# ---------------------------------------------------------------------------
# natural-compression codec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NaturalPack(LeafCodec):
    """Natural compression: int8 power-of-two exponent stream (sentinel -128
    for exact zeros) + uint32 sign bitmap -- the paper's ~9 bits/coordinate.
    Exponents are clipped to [-126, 127]: the codec is exact on the normal
    fp32 range |x| in [2^-126, 2^126]; subnormal magnitudes (never produced
    by training-scale gradients) would clip."""

    shape: Tuple[int, ...]
    size: int

    kind = "natural_pack"
    MSG_NDIM = 1

    @property
    def payload_bits(self) -> int:
        return 8 * self.size + 32 * bitmap_words(self.size)

    def encode(self, key, delta):
        a = jnp.abs(delta)
        safe = jnp.where(a > 0, a, 1.0)
        e = jnp.floor(jnp.log2(safe))
        lo = jnp.exp2(e)
        up = jax.random.uniform(key, delta.shape) < (safe / lo - 1.0)
        es = jnp.clip(e + up.astype(jnp.float32), -126.0, 127.0)
        exps = jnp.where(a > 0, es, -128.0).astype(jnp.int8)
        return exps, pack_bits(delta < 0)

    def decode(self, payload):
        exps, words = payload
        mag = jnp.exp2(exps.astype(jnp.float32))
        sgn = jnp.where(unpack_bits(words, self.size), -1.0, 1.0)
        return jnp.where(exps == -128, 0.0, sgn * mag)

    def mask_message(self, payload, m):
        # zero is the sentinel exponent -128, not a scalable value: absent
        # workers' streams are forced to the sentinel (m = 1 keeps exps as-is)
        exps, words = payload
        mm = jnp.asarray(m)
        mm = mm.reshape(mm.shape + (1,) * (exps.ndim - mm.ndim))
        return jnp.where(mm > 0, exps, jnp.int8(-128)), words


# ---------------------------------------------------------------------------
# dense codec (identity / m-nice / fallback)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DensePack(LeafCodec):
    """Raw value stream in the wire dtype.  Used where the message is
    genuinely dense (identity, m-nice participation scaling); the exact
    accounting is size * value_bits -- honest, if unimpressive."""

    shape: Tuple[int, ...]
    size: int
    compressor: Any
    val_dtype: str = "float32"

    kind = "dense_pack"
    MSG_NDIM = 1

    @property
    def payload_bits(self) -> int:
        return self.size * _val_bits(self.val_dtype)

    def encode(self, key, delta):
        y = self.compressor(key, delta.reshape(self.shape))
        return (y.reshape(-1).astype(jnp.dtype(self.val_dtype)),)

    def decode(self, payload):
        (vals,) = payload
        return vals.astype(jnp.float32)


# ---------------------------------------------------------------------------
# format metadata
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireFormat:
    """Payload layout for a whole gradient pytree (leaf order = flatten
    order, which both aggregation paths use)."""

    leaves: Tuple[LeafCodec, ...]

    @staticmethod
    def for_tree(tree: PyTree, block: int, kb: int) -> "WireFormat":
        """Block-sparse format for every leaf (the PR-1 constructor)."""
        return WireFormat(tuple(
            LeafWire(shape=tuple(l.shape), size=int(l.size), block=block, kb=kb)
            for l in jax.tree.leaves(tree)))

    def bits_per_round(self, *, n_workers: int = 1,
                       participants: Optional[float] = None):
        """Exact uplink bits one round puts on the wire: per worker when
        n_workers == 1 (the paper's per-node accounting), total otherwise.

        ``participants`` switches to the variable-participant federated
        round: an n-worker participation bitmap (whole uint32 words, like
        every bitmap on this wire) plus only |S_t| payloads.  Pass the
        concrete |S_t| for exact ``int`` bits of one round; a fractional
        expected count p*n returns the expected accounting, explicitly a
        ``float`` (the ONLY case this method returns one).
        """
        per_worker = sum(l.payload_bits for l in self.leaves)
        if participants is None:
            return n_workers * per_worker
        bitmap = 32 * bitmap_words(n_workers)
        if float(participants).is_integer():
            # exact participant count: stay in int arithmetic end to end (a
            # float product silently rounds above 2**53, and the historical
            # int(float) round-trip leaked floats into BENCH rows and
            # `== bits/8` byte assertions)
            return bitmap + int(participants) * per_worker
        return bitmap + participants * per_worker

    def downlink_bits_per_round(self) -> int:
        """Exact bits of the ONE master -> worker broadcast message of a
        round.  The downlink is a single payload regardless of n or of the
        sampled subset S_t: present and absent workers decode the same
        broadcast, so no participation bitmap and no per-worker factor."""
        return sum(l.payload_bits for l in self.leaves)

    def dense_bits(self) -> int:
        """The fp32 dense baseline for this tree (one full copy)."""
        return 32 * sum(l.size for l in self.leaves)


def total_round_bits(up: "WireFormat", down: Optional["WireFormat"] = None, *,
                     n_workers: int, participants: Optional[float] = None):
    """Exact wire bits of one FULL round, both directions:

        uplink   -- n_workers payloads (or, federated, a participation
                    bitmap + the |S_t| sampled payloads), and
        downlink -- one broadcast message (``down``; None means the
                    uncompressed dense fp32 broadcast of the same tree).

    ``participants`` composes the PR-3 federated accounting into the uplink
    term only: the broadcast still goes out (and is decoded by absent
    workers) every round.
    """
    up_bits = up.bits_per_round(n_workers=n_workers, participants=participants)
    down_bits = (up.dense_bits() if down is None
                 else down.downlink_bits_per_round())
    return up_bits + down_bits


def federated_round_bits(fmt: "WireFormat", mask) -> int:
    """Exact wire bits of one federated round given its concrete (n,) mask:
    participation bitmap + the |S_t| sampled workers' payloads."""
    m = np.asarray(mask)
    return fmt.bits_per_round(n_workers=int(m.shape[0]),
                              participants=int(m.sum()))


# ---------------------------------------------------------------------------
# heterogeneous fleets: per-worker formats
# ---------------------------------------------------------------------------

def fleet_formats(fleet: Sequence[Any], tree: PyTree, *,
                  wire_dtype: str = "float32") -> Tuple["WireFormat", ...]:
    """One WireFormat per worker of a heterogeneous fleet (worker i's
    payload layout is its own compressor's)."""
    return tuple(format_for(c, tree, wire_dtype=wire_dtype) for c in fleet)


def fleet_bits_per_round(fmts: Sequence["WireFormat"],
                         mask: Optional[Any] = None) -> int:
    """Exact uplink bits of one mixed-fleet round: the sum of the
    participating workers' (heterogeneous) payloads.

    ``mask`` is the concrete (n,) participation mask of a federated round
    (adds the n-worker bitmap and drops absent workers' payloads); None is
    the full-participation round.
    """
    if mask is None:
        return sum(f.bits_per_round() for f in fmts)
    m = np.asarray(mask)
    if m.shape[0] != len(fmts):
        raise ValueError(f"mask of {m.shape[0]} workers for a fleet of "
                         f"{len(fmts)}")
    return 32 * bitmap_words(len(fmts)) + sum(
        f.bits_per_round() for f, mi in zip(fmts, m) if mi > 0)


# ---------------------------------------------------------------------------
# the serving downlink: versioned compressed-delta push envelopes
# ---------------------------------------------------------------------------

#: exact header bits of one versioned push envelope: two unsigned 64-bit
#: version fields (``version`` of the w this push produces, ``base_version``
#: of the w it must be applied to) -- the only metadata the replica protocol
#: needs beyond the payload itself.
PUSH_HEADER_BITS = 2 * 64

#: envelope kinds: a ``delta`` decodes to the model INNOVATION (the replica
#: applies w + lam * decode, the trainer-side Downlink arithmetic verbatim);
#: a ``snapshot`` decodes to the model itself (the replica assigns it --
#: lossless downlinks ship snapshots, which is what makes an identity-
#: downlink push bit-equal to a full checkpoint load).
PUSH_KINDS = ("delta", "snapshot")


@dataclasses.dataclass(frozen=True)
class DeltaEnvelope:
    """One versioned model push on the serving downlink.

    ``payloads`` is the per-leaf wire payload list of ONE broadcast message
    (exactly what :meth:`repro.core.efbv.Downlink.encode_push` emits and
    :meth:`~repro.core.efbv.Downlink.apply_push` consumes);
    :func:`payload_bytes` of it equals ``push_bits(fmt) / 8`` minus the
    header, exactly.  ``version`` is the model version the push produces,
    ``base_version`` the replica-side w it must be applied to -- a replica
    at any other version MUST refuse the push (stale or gapped) and resync
    from a checkpoint instead of silently drifting.
    """

    version: int
    base_version: int
    payloads: Any
    kind: str = "delta"

    def __post_init__(self):
        if self.kind not in PUSH_KINDS:
            raise ValueError(f"push kind {self.kind!r} not in {PUSH_KINDS}")
        if self.version <= self.base_version:
            raise ValueError(
                f"push version {self.version} must advance past its base "
                f"{self.base_version} (versions are strictly monotonic)")


def push_bits(fmt: "WireFormat") -> int:
    """Exact bits of one versioned delta push: the envelope header plus the
    ONE broadcast message of the downlink wire format (no n or |S_t|
    factor -- every replica decodes the same push)."""
    return PUSH_HEADER_BITS + fmt.downlink_bits_per_round()


def checkpoint_push_bits(fmt: "WireFormat") -> int:
    """Exact bits of shipping a FULL fp32 checkpoint of the same tree under
    the same envelope header -- the baseline a delta push is measured
    against (BENCH_bits ``serve_delta`` rows)."""
    return PUSH_HEADER_BITS + fmt.dense_bits()


def clamp_for_leaf(compressor, size: int):
    """Clamp a compressor's selection counts to one leaf's size.

    Fixed-k sparsifiers (top-k, rand-k, comp-(k,k'), mix-(k,k'), block-top-k)
    assume d >= k; on a pytree with size-1 or 0-d edge leaves that assumption
    breaks -- ``jax.lax.top_k(x, k)`` and ``jax.random.choice(..., (k,),
    replace=False)`` both reject k > d, so encode (and transitively
    :func:`zero_message`, the pipelined priming payload) crashes.  Clamping
    is per-leaf and returns the SAME object whenever no count changes, so
    every existing single-leaf/flat call site is bitwise (and hash-)
    untouched.  Quantizers, sign, natural, dense and the fraction-style
    compressors are size-adaptive already and pass through."""
    from repro.core import compressors as cz  # lazy: cz constructs codecs
    d = int(size)
    if isinstance(cmp := compressor, cz.MixKK):
        k = min(cmp.k, d)
        kp = min(cmp.kp, d - k)
        if (k, kp) != (cmp.k, cmp.kp):
            return dataclasses.replace(cmp, k=k, kp=kp)
    elif isinstance(cmp, cz.CompKK):
        kp = min(cmp.kp, d)
        k = min(cmp.k, kp)
        if (k, kp) != (cmp.k, cmp.kp):
            return dataclasses.replace(cmp, k=k, kp=kp)
    elif isinstance(cmp, (cz.TopK, cz.RandK, cz.ScaledRandK)):
        if cmp.k > d:
            return dataclasses.replace(cmp, k=d)
    elif isinstance(cmp, cz.BlockTopK):
        kb = min(cmp.kb, cmp.block, d)
        if kb != cmp.kb:
            return dataclasses.replace(cmp, kb=kb)
    return compressor


def codec_of(compressor, shape: Tuple[int, ...], size: int,
             wire_dtype: str = "float32") -> LeafCodec:
    """The codec ``compressor`` declares for one leaf (DensePack fallback
    for compressors that declare nothing).  Fixed-k sparsifiers are clamped
    to the leaf's size first (:func:`clamp_for_leaf`), so degenerate leaves
    get a well-formed -- if trivially dense -- payload instead of a crash."""
    compressor = clamp_for_leaf(compressor, size)
    fn = getattr(compressor, "codec", None)
    if fn is None:
        return DensePack(shape=tuple(shape), size=int(size),
                         compressor=compressor, val_dtype=wire_dtype)
    return fn(tuple(shape), wire_dtype=wire_dtype)


def format_for(compressor, tree: PyTree, *,
               wire_dtype: str = "float32") -> WireFormat:
    """WireFormat for ``compressor`` applied leaf-wise to ``tree``.

    Every compressor in the zoo declares a codec, so this never returns
    None: block-top-k gets the block-sparse layout, the top-k/rand-k family
    gets flat (values, indices), sign/QSGD/natural get their bit-packed /
    quantized streams, and identity/m-nice fall back to a dense value
    stream -- all with exact ``bits_per_round``.
    """
    return WireFormat(tuple(
        codec_of(compressor, tuple(l.shape), int(l.size), wire_dtype)
        for l in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# pytree-native wire: per-leaf codec rules composed into ONE accounting
# ---------------------------------------------------------------------------

def _key_str(entry) -> str:
    """One pytree path entry -> its path-string segment."""
    tu = jax.tree_util
    if isinstance(entry, tu.DictKey):
        return str(entry.key)
    if isinstance(entry, tu.SequenceKey):
        return str(entry.idx)
    if isinstance(entry, tu.GetAttrKey):
        return str(entry.name)
    if isinstance(entry, tu.FlattenedIndexKey):
        return str(entry.key)
    return str(entry)


def leaf_paths(tree: PyTree) -> Tuple[str, ...]:
    """'/'-joined path string of every leaf, in flatten order (dict keys,
    sequence indices and attribute names as segments; a bare array tree has
    the single path '')."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return tuple("/".join(_key_str(e) for e in kp) for kp, _ in flat)


def parse_leaf_rules(spec: str) -> Tuple[Tuple[str, Any], ...]:
    """Parse the ';'-separated per-leaf codec grammar into (pattern,
    Compressor) rules, first match wins.

    Each entry is ``pattern=compressor_spec`` -- the pattern is an fnmatch
    glob over the leaf's '/'-joined path -- and a bare ``compressor_spec``
    (no '=') is the default rule, pattern '*'.  Example::

        'embed*=qsgd:16;*norm*=identity;block_topk:256,16'

    Leaves matching no rule keep the experiment's base compressor, so the
    default entry is optional.  Jointly-defined compressors (m-nice) are
    rejected: their draws couple all workers, not leaves.

    Thin delegate into the unified spec grammar (repro.core.specgrammar),
    which also provides the lossless ``format_leaf_rules`` inverse; imported
    lazily because this module is layout-only."""
    from repro.core import specgrammar
    return specgrammar.parse_leaf_rules(spec)


def resolve_leaf(rules, path: str, default):
    """The compressor the rule list assigns to one leaf path (first matching
    fnmatch pattern wins; no match keeps the default compressor)."""
    for pat, comp in rules or ():
        if fnmatch.fnmatchcase(path, pat):
            return comp
    return default


@dataclasses.dataclass(frozen=True)
class TreeWire(WireFormat):
    """Pytree-native wire format: leaf-path -> codec, with the SAME composed
    accounting as every flat format (``bits_per_round`` et al. are inherited
    sums over leaves, so composed bits == sum of per-leaf bits exactly --
    the harness pins the equality).

    Mixed leaves reuse the fleet mixed-codec machinery leaf-wise: each leaf
    carries the (clamped) compressor a rule resolved for it plus that
    compressor's own codec, and encode/decode/zero/mask walk the leaves with
    the per-leaf ``fold_in(key, j)`` convention the aggregation paths and
    ``init_inflight`` already use.  With no rules and one leaf this is the
    flat-vector wire, payload-bitwise."""

    paths: Tuple[str, ...]
    compressors: Tuple[Any, ...]
    treedef: Any

    @staticmethod
    def for_tree(compressor, tree: PyTree, *, wire_dtype: str = "float32",
                 rules: Tuple[Tuple[str, Any], ...] = ()) -> "TreeWire":
        """TreeWire for ``tree``: every leaf's compressor is resolved through
        ``rules`` (falling back to ``compressor``), clamped to the leaf's
        size, and asked for its codec."""
        flat, treedef = jax.tree_util.tree_flatten(tree)
        paths = leaf_paths(tree)
        comps = tuple(
            clamp_for_leaf(resolve_leaf(rules, p, compressor), int(l.size))
            for p, l in zip(paths, flat))
        codecs = tuple(
            codec_of(c, tuple(l.shape), int(l.size), wire_dtype)
            for c, l in zip(comps, flat))
        return TreeWire(leaves=codecs, paths=paths, compressors=comps,
                        treedef=treedef)

    # -- keys ---------------------------------------------------------------
    def leaf_keys(self, keys) -> Tuple[Optional[Array], ...]:
        """Normalize the key argument: an explicit per-leaf sequence is used
        as-is (the harness's single-leaf flat-parity leg), one base key is
        folded per leaf index -- fold_in(key, j) -- the convention every
        aggregation path already uses."""
        if keys is None or not isinstance(keys, (tuple, list)):
            return tuple(jax.random.fold_in(keys, j) if keys is not None
                         else None for j in range(len(self.leaves)))
        if len(keys) != len(self.leaves):
            raise ValueError(f"{len(keys)} leaf keys for a tree of "
                             f"{len(self.leaves)} leaves")
        return tuple(keys)

    # -- pack / unpack, leaf-wise -------------------------------------------
    def encode_update(self, keys, grads: PyTree, h: PyTree, lam: float, *,
                      kernel: Optional[str] = None, stream: bool = False):
        """Per-leaf fused worker update: (payload list, h' pytree) with
        d_j = C_j(g_j - h_j) packed and h'_j = h_j + lam d_j -- no flat
        vector is ever materialized."""
        gl = self.treedef.flatten_up_to(grads)
        hl = self.treedef.flatten_up_to(h)
        ks = self.leaf_keys(keys)
        payloads, h_new = [], []
        for codec, kj, gj, hj in zip(self.leaves, ks, gl, hl):
            # an explicit kernel request applies leaf-wise where a fused
            # kernel exists; kernel-less leaves (dense, sign, ...) run their
            # jnp oracle -- which IS their only backend, so the mixed-tree
            # differential legs stay bit-identical across backends
            kj_kernel = kernel
            if (kernel in ("pallas", "interpret")
                    and not getattr(codec, "has_kernel", False)):
                kj_kernel = "oracle"
            p, hn = codec.encode_update(kj, gj, hj, lam, kernel=kj_kernel,
                                        stream=stream)
            payloads.append(p)
            h_new.append(hn)
        return payloads, jax.tree_util.tree_unflatten(self.treedef, h_new)

    def decode(self, payloads) -> PyTree:
        """One worker's payload list -> dense f32 pytree (leaf shapes)."""
        dense = [c.decode(p).reshape(c.shape)
                 for c, p in zip(self.leaves, payloads)]
        return jax.tree_util.tree_unflatten(self.treedef, dense)

    def decode_sum(self, payloads, *, chunks: int = 1) -> PyTree:
        """Worker-stacked payload list -> dense f32 pytree of scatter-SUMS
        (divide by n for the master mean); ``chunks`` splits the worker axis
        exactly like the flat path's :func:`chunked_decode_sum`."""
        dense = [chunked_decode_sum(c, p, chunks).reshape(c.shape)
                 for c, p in zip(self.leaves, payloads)]
        return jax.tree_util.tree_unflatten(self.treedef, dense)

    def mask_messages(self, payloads, m):
        """Participation-gate every leaf's message (list in, list out)."""
        return [c.mask_message(p, m) for c, p in zip(self.leaves, payloads)]

    def zero_messages(self, base_key: Array):
        """The pipelined schedule's priming payloads, one per leaf, keyed
        fold_in(base_key, j) -- exactly the init_inflight convention."""
        return [zero_message(c, jax.random.fold_in(base_key, j))
                for j, c in enumerate(self.leaves)]

    # -- accounting ---------------------------------------------------------
    def bits_by_leaf(self) -> Tuple[int, ...]:
        """Exact per-leaf payload bits, in flatten order (their sum IS
        ``bits_per_round()``; the harness asserts the equality)."""
        return tuple(c.payload_bits for c in self.leaves)


def tree_format_for(compressor, tree: PyTree, *, wire_dtype: str = "float32",
                    rules=None):
    """The wire format of ``tree``: a plain :class:`WireFormat` when no
    per-leaf rules are given (bit-compatible with every existing call site)
    and a :class:`TreeWire` otherwise."""
    if not rules:
        return format_for(compressor, tree, wire_dtype=wire_dtype)
    return TreeWire.for_tree(compressor, tree, wire_dtype=wire_dtype,
                             rules=tuple(rules))


def kernel_gaps(fmt: WireFormat, tree: PyTree) -> Tuple[Tuple[str, str], ...]:
    """(leaf path, reason) for every leaf of ``tree`` whose codec has a
    fused Pallas kernel that the leaf cannot use: under kernel 'auto' these
    leaves take the jnp oracle on a TPU too."""
    return tuple((path or "<root>", codec.kernel_gap)
                 for path, codec in zip(leaf_paths(tree), fmt.leaves)
                 if codec.kernel_gap is not None)


def payload_bytes(payload: PyTree) -> int:
    """Measured bytes of a payload pytree (what actually crosses the wire)."""
    return sum(a.nbytes for a in jax.tree.leaves(payload))


def encode_update(codec: LeafCodec, key: Optional[Array], g: Array, h: Array,
                  lam: float, *, kernel: Optional[str] = None,
                  stream: bool = False) -> Tuple[Tuple[Array, ...], Array]:
    """Fused compress-and-pack worker update through ``codec`` (module-level
    convenience; dispatches to the codec's fused kernel when it has one).

    ``stream=True`` requests the async-copy variant of the fused kernel
    (payload DMAs out while the control-variate update still computes --
    the pipelined trainer's hot path); codecs without a streaming kernel
    ignore it, and the streamed payload is bit-identical either way."""
    return codec.encode_update(key, g, h, lam, kernel=kernel, stream=stream)


def zero_message(codec: LeafCodec, key: Array) -> Tuple[Array, ...]:
    """The decode-zero payload of ``codec``: a REAL wire message (encode of
    the zero vector, then participation-masked to zero, so stochastic codecs
    decode to exactly zero too).  Primes the pipelined schedule's round-0
    in-flight buffer -- every execution path (trainer, harness) builds it
    from the same fold_in(key(0), PIPELINE_FOLD) key, so they agree
    bit-for-bit."""
    payload = codec.encode(key, jnp.zeros((codec.size,), jnp.float32))
    return codec.mask_message(payload, jnp.zeros((), jnp.float32))


def pipeline_chunks(n_workers: int) -> int:
    """Worker-axis chunk count of the pipelined (depth >= 1) exchange:
    gcd(n, 4) splits the stacked payload into equal slices so the decode of
    early chunks overlaps the transfer of late ones.  Below four workers a
    chunk degenerates to a single worker's slice of the worker-sharded
    payload -- the partitioner reshards every slice and the permutes cost
    more than the overlap buys -- so the exchange stays whole.  ONE rule
    shared by the trainer and the differential harness, so their depth-1
    trajectories chunk -- and therefore sum -- identically."""
    n = int(n_workers)
    return math.gcd(n, 4) if n >= 4 else 1


def chunked_decode_sum(codec: LeafCodec, payload, chunks: int) -> Array:
    """decode_sum of a worker-stacked payload with the worker axis split
    into ``chunks`` equal slices, partial sums accumulated in FIXED
    ascending chunk order.

    ``chunks=1`` is literally ``codec.decode_sum`` (the sequential path's
    byte-identity is preserved).  The fixed order is load-bearing: the ring
    exchange delivers chunks in a device-dependent order, and float sums
    only stay replica-identical if every device accumulates them the same
    way."""
    if chunks <= 1:
        return codec.decode_sum(payload)
    n = jax.tree.leaves(payload)[0].shape[0]
    if n % chunks:
        raise ValueError(f"{n} stacked messages do not split into {chunks} "
                         "equal chunks")
    cs = n // chunks
    total = None
    for c in range(chunks):
        part = jax.tree.map(lambda a: a[c * cs:(c + 1) * cs], tuple(payload))
        dec = codec.decode_sum(part)
        total = dec if total is None else total + dec
    return total


# ---------------------------------------------------------------------------
# block-sparse pack / unpack / scatter-add (jnp; the layout spec)
# ---------------------------------------------------------------------------

def _pad2d(xf: Array, lw: LeafWire) -> Array:
    pad = lw.nb * lw.block - lw.size
    return jnp.pad(xf, (0, pad)).reshape(lw.nb, lw.block)


def pack_oracle(lw: LeafWire, delta: Array) -> Tuple[Array, Array]:
    """jnp oracle: (values, local indices), (nb, kb) each -- the layout every
    fused producer must match bit-for-bit."""
    xp = _pad2d(delta.reshape(-1), lw)
    _, idx = jax.lax.top_k(jnp.abs(xp), lw.kb)
    vals = jnp.take_along_axis(xp, idx, axis=1)
    return vals, idx.astype(jnp.int32)


def scatter_add(lw: LeafWire, vals: Array, idx: Array, *,
                kernel: Optional[str] = None) -> Array:
    """Payload -> dense flat (size,) vector.

    Accepts one message (nb, kb) or the worker-stacked all-gather result
    (n, nb, kb); the stacked form is scatter-SUMMED per block (the local
    combine of the sparse_allgather collective -- divide by n for the mean),
    each position's terms in ascending worker, then slot, order.

    Dispatches like :func:`fused_pack`: the sort-free Pallas kernel
    (kernels/pack.py) on TPU; the jnp scatter elsewhere, for a block the
    kernel refuses (``pack.unpack_gap``), and for values that are not f32,
    which the scatter sums in their own dtype.  Kernel and scatter give the
    same bits.
    """
    mode = _kernel_mode(kernel)
    if mode in ("pallas", "interpret"):
        from repro.kernels import pack
        gap = (f"{vals.dtype} values" if vals.dtype != jnp.float32
               else pack.unpack_gap(lw.block, vals.size // lw.nb))
        if gap is not None:
            if kernel in ("pallas", "interpret"):
                raise ValueError(f"Pallas decode kernel: {gap}")
            mode = "oracle"
    if mode in ("pallas", "interpret"):
        from repro.kernels import ops
        out = ops.block_decode_sum(vals, idx, block=lw.block,
                                   interpret=(mode == "interpret"))
        return out.reshape(-1)[:lw.size]
    if vals.ndim == 3:  # (n, nb, kb) -> (nb, n*kb)
        vals = jnp.moveaxis(vals, 0, 1).reshape(vals.shape[1], -1)
        idx = jnp.moveaxis(idx, 0, 1).reshape(idx.shape[1], -1)
    rows = jnp.arange(lw.nb)[:, None]
    out = jnp.zeros((lw.nb, lw.block), vals.dtype).at[rows, idx].add(vals)
    return out.reshape(-1)[:lw.size]


def unpack(lw: LeafWire, vals: Array, idx: Array) -> Array:
    """One message -> dense tensor of the leaf's original shape."""
    return scatter_add(lw, vals, idx).reshape(lw.shape)


# ---------------------------------------------------------------------------
# fused compress-and-pack (the block-top-k worker hot path)
# ---------------------------------------------------------------------------

def fused_pack(lw: LeafWire, g: Array, h: Array, lam: float, *,
               kernel: Optional[str] = None, stream: bool = False
               ) -> Tuple[Tuple[Array, Array], Array]:
    """d = block_topk(g - h) packed as (values, indices); h' = h + lam d.

    Dispatches to the Pallas kernel (one HBM pass, dense d never leaves
    VMEM) or the jnp oracle; all backends produce bit-identical results.
    ``stream=True`` selects the async-copy kernel variant -- the payload
    slab DMAs toward HBM while the h update still computes (same bits, the
    pipelined trainer just stops waiting for them).
    """
    mode = _kernel_mode(kernel)
    if mode in ("pallas", "interpret"):
        from repro.kernels import pack
        # the Pallas kernel tiles 128-lane slabs, at least 128 blocks a grid
        # step; other leaves take the bit-identical oracle.  Only an
        # *explicit* per-call request errors.
        gap = (f"requires block % 128 == 0, got {lw.block}" if lw.block % 128
               else pack.pack_vmem_gap(lw.block, lw.kb))
        if gap is not None:
            if kernel in ("pallas", "interpret"):
                raise ValueError(f"Pallas pack kernel {gap}")
            mode = "oracle"
    if mode in ("pallas", "interpret"):
        from repro.kernels import ops
        return ops.efbv_pack_update(g, h, float(lam), block=lw.block,
                                    kb=lw.kb, interpret=(mode == "interpret"),
                                    stream=stream)
    # jnp oracle: same arithmetic, same order of operations as the kernel
    delta = g.astype(jnp.float32) - h.astype(jnp.float32)
    vals, idx = pack_oracle(lw, delta)
    d = scatter_add(lw, vals, idx).reshape(lw.shape)
    h_new = (h.astype(jnp.float32) + float(lam) * d).astype(h.dtype)
    return (vals.astype(g.dtype), idx), h_new
