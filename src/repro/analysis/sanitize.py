"""``--sanitize`` runtime mode: debug_nans + Pallas interpret everywhere.

The static rules catch what is visible in source; this is the dynamic
half.  Enabling sanitize mode before any jax work:

  * turns on ``jax_debug_nans`` -- the first NaN/Inf produced anywhere in
    a jitted computation raises at the producing primitive instead of
    poisoning the trajectory silently;
  * forces every Pallas kernel through interpret mode (kernels/ops.py's
    ``_interpret_default`` consults :func:`active`), where out-of-bounds
    ref indexing raises instead of wrapping -- on TPU hardware an OOB
    access is silently clamped, which is exactly the bug class interpret
    mode exists to surface;
  * exports ``REPRO_SANITIZE=1`` so subprocesses (the spec-file drivers
    spawn workers) inherit the mode.

Both drivers (launch/train.py, launch/finetune.py) expose this as
``--sanitize``; ``make sanitize-smoke`` runs a smoke step of each under it.
"""

from __future__ import annotations

import os

_ENV = "REPRO_SANITIZE"
_active = False


def active() -> bool:
    """Sanitize mode on?  True once :func:`enable` ran in this process or
    the ``REPRO_SANITIZE`` env var marks an enabling parent process."""
    return _active or os.environ.get(_ENV, "") == "1"


def enable() -> None:
    """Idempotently switch this process (and children) into sanitize mode.

    Must run before the first jitted computation: debug_nans only rewraps
    computations compiled after the flag flips.
    """
    global _active
    _active = True
    os.environ[_ENV] = "1"
    import jax

    from jax.experimental.pallas import tpu as pltpu

    jax.config.update("jax_debug_nans", True)
    # interpret at the source: process-lifetime scope, deliberately unexited
    pltpu.force_tpu_interpret_mode().__enter__()
