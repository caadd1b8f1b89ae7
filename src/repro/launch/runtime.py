"""Process set-up that every entry point runs before its first compile.

    from repro.launch import runtime
    runtime.compile_cache()
    runtime.cpu_devices(4)      # only the CPU backend sees the count
"""

from __future__ import annotations

import os

#: the persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed path inside the checkout (the path is part of every cache key,
#: so it must not move between runs)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache lives at :data:`CACHE_DIR`."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def cpu_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices, so that a CPU run can build an
    n-device mesh.  Accelerator backends are untouched: on a chip the mesh
    must fit the chips there are.  A no-op where ``XLA_FLAGS`` already
    forces a host device count, or once JAX's backends are up (the mesh
    check then names the device count)."""
    import jax

    if n <= 1 or "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        return
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backends already initialized: the device count is fixed
