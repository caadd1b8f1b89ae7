"""Compiled-artifact analysis: dense-free proofs of the fused pack kernels.

:func:`dense_free` statically proves that a registered pack kernel never
materializes a d-sized dense buffer outside its tile-granular VMEM working
set, which makes it a CI *gate* rather than a per-PR ritual.  The proof
traces the kernel wrapper to a jaxpr (no lowering, no TPU needed) and checks

  1. the wrapper stages exactly into a ``pallas_call`` -- no top-level eqn
     creates a new >= d buffer around it (a stray ``astype`` or mask there
     would be a dense HBM pass the fusion docs promised away), and
  2. every value inside the kernel jaxpr (including fori_loop bodies) is
     bounded by the tile size, which itself is a strict fraction of d.

Together these say: the dense compressed delta exists only one tile at a
time, in VMEM -- the EF-BV payload path is O(payload), not O(d), in HBM.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple


@dataclasses.dataclass
class DenseFreeReport:
    """The evidence behind one dense-free verdict (``as_dict`` goes to CI)."""

    kernel: str
    d: int                    #: dense element count of the full problem
    tile: int                 #: largest kernel-visible ref (elements)
    max_inner: int            #: largest value inside the kernel jaxpr
    n_pallas_calls: int
    violations: List[str]

    @property
    def ok(self) -> bool:
        return not self.violations

    def as_dict(self) -> dict:
        return {"kernel": self.kernel, "d": self.d, "tile": self.tile,
                "max_inner": self.max_inner, "ok": self.ok,
                "violations": list(self.violations)}


def _aval_size(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(math.prod(shape)) if shape else 1


def _inner_jaxprs(params: dict):
    """Every jaxpr-valued entry of an eqn's params (scan/while bodies,
    pallas kernels, custom_* wrappers), across jax versions."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                yield getattr(v, "jaxpr", v)


def _walk_sizes(jaxpr, out: List[int]) -> None:
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.append(_aval_size(v))
        for sub in _inner_jaxprs(eqn.params):
            _walk_sizes(sub, out)


def dense_free(name: str) -> DenseFreeReport:
    """Statically prove the registered pack kernel ``name`` materializes no
    d-sized dense buffer: trace to a jaxpr (no lowering; runs on CPU) and
    bound every intermediate by the tile size.

    The dense inputs (g, h) and the dense state output h_new are exempt by
    construction -- they are the algorithm's state, written one tile per
    grid step; what must never exist is a NEW dense buffer holding the
    compressed delta d = C(g - h)."""
    import jax

    fn, example_args, d = PACK_KERNELS[name]()
    jaxpr = jax.make_jaxpr(fn)(*example_args).jaxpr
    violations: List[str] = []

    pallas_eqns = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    if not pallas_eqns:
        violations.append("no pallas_call primitive in the traced jaxpr")
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.outvars:
            if _aval_size(v) >= d:
                violations.append(
                    f"top-level {eqn.primitive.name} materializes a "
                    f"{_aval_size(v)}-element buffer (d = {d}) outside "
                    "the kernel")

    tile = 0
    max_inner = 0
    for eqn in pallas_eqns:
        inners = list(_inner_jaxprs(eqn.params))
        if not inners:
            violations.append("pallas_call carries no inner jaxpr to check")
            continue
        kernel_jaxpr = inners[0]
        tile = max(tile, max((_aval_size(v) for v in kernel_jaxpr.invars),
                             default=0))
        sizes: List[int] = []
        _walk_sizes(kernel_jaxpr, sizes)
        max_inner = max([max_inner] + sizes)
    if pallas_eqns and not violations:
        if tile >= d:
            violations.append(
                f"tile covers the whole problem (tile = {tile} >= d = {d}); "
                "grid must split d so only a fraction is live at once")
        if max_inner > tile:
            violations.append(
                f"kernel-internal value of {max_inner} elements exceeds the "
                f"tile ({tile}) -- the kernel builds something denser than "
                "its VMEM working set")

    return DenseFreeReport(kernel=name, d=d, tile=tile, max_inner=max_inner,
                           n_pallas_calls=len(pallas_eqns),
                           violations=violations)


def _block_topk_case():
    import jax.numpy as jnp
    from repro.kernels import pack

    # 384 blocks: pack_tile takes 256 rows a step, so the grid has two
    # steps (the second ragged) and no step holds the whole leaf
    nb, block, kb = 384, 128, 4
    g = jnp.zeros((nb, block), jnp.float32)
    h = jnp.zeros((nb, block), jnp.float32)
    fn = lambda g, h: pack.pack_update_pallas(g, h, 0.5, kb)
    return fn, (g, h), nb * block


def _randk_case():
    import jax.numpy as jnp
    from repro.kernels import pack

    nr, cols, k = 32, 128, 16
    g = jnp.zeros((nr, cols), jnp.float32)
    h = jnp.zeros((nr, cols), jnp.float32)
    idx = jnp.zeros((k,), jnp.int32)
    fn = lambda g, h, idx: pack.randk_update_pallas(g, h, idx, 2.0, 0.5)
    return fn, (g, h, idx), nr * cols


def _qsgd_case():
    import jax.numpy as jnp
    from repro.kernels import pack

    nr, cols, s = 64, 128, 16
    g = jnp.zeros((nr, cols), jnp.float32)
    h = jnp.zeros((nr, cols), jnp.float32)
    u = jnp.zeros((nr, cols), jnp.float32)
    norm = jnp.ones((1, 1), jnp.float32)
    fn = lambda g, h, u, norm: pack.qsgd_pack_update_pallas(g, h, u, norm,
                                                            s, 0.5)
    return fn, (g, h, u, norm), nr * cols


#: name -> zero-arg builder returning (traceable fn, example args, d).
#: Every fused pack kernel MUST be registered here: the CI lint job runs
#: ``python -m repro.analysis --hlo-gate`` which proves each one dense-free.
PACK_KERNELS: Dict[str, Callable[[], Tuple[Callable, tuple, int]]] = {
    "block_topk_pack": _block_topk_case,
    "randk_update": _randk_case,
    "qsgd_pack": _qsgd_case,
}


def gate(names: Optional[List[str]] = None) -> List[DenseFreeReport]:
    """Run the dense-free proof over (a subset of) the registry."""
    return [dense_free(n) for n in (names or sorted(PACK_KERNELS))]
