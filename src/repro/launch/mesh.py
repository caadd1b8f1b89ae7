"""Production mesh geometry.

Defined as FUNCTIONS so that importing this module never touches jax device
state (jax locks the device count on first backend init, so a caller that
forces host devices must set XLA_FLAGS before anything else).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


POD_CHIPS = 256  # one v5e pod slice: 16 x 16
DATA_AXIS = "data"
MODEL_AXIS = "model"
POD_AXIS = "pod"


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) ('data','model') single pod; (2,16,16) ('pod','data','model')
    across two pods."""
    from repro import compat

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Optional[Sequence[str]] = None):
    """Arbitrary mesh for tests/smoke runs; axes default to trailing names of
    ('pod','data','model'), so shapes with more than 3 dims need explicit
    axes."""
    from repro import compat

    if axes is None:
        defaults = ("pod", "data", "model")
        if len(shape) > len(defaults):
            # the trailing-names slice cannot grow past 3 axes; silently
            # recycling it would hand jax a short/duplicate axis tuple
            raise ValueError(
                f"make_mesh has default axis names for up to {len(defaults)} "
                f"mesh dims {defaults}, got shape {tuple(shape)} with "
                f"{len(shape)} dims -- pass axes= explicitly")
        axes = defaults[-len(shape):]
    import jax

    need, devices = int(np.prod(shape)), jax.devices()
    if need > len(devices):
        raise ValueError(
            f"mesh {'x'.join(map(str, shape))} needs {need} devices, but this "
            f"process has {len(devices)} {devices[0].platform} device(s)")
    return compat.make_mesh(tuple(shape), tuple(axes))


def multihost_worker_shape(n_workers: int, num_processes: int
                           ) -> Tuple[int, int]:
    """Split a worker count into (num_processes, workers_per_process).

    The leading worker axis of a multi-host mesh must tile exactly across
    processes -- a worker shard that straddled two hosts would turn every
    phase-1 shard_map into a cross-host collective."""
    if num_processes < 1:
        raise ValueError(f"num_processes must be >= 1, got {num_processes}")
    if n_workers % num_processes:
        raise ValueError(
            f"{n_workers} workers cannot tile {num_processes} processes: "
            f"the leading worker axis must be divisible by the process "
            f"count so each host owns whole workers")
    return num_processes, n_workers // num_processes


def make_multihost_mesh(shape: Sequence[int],
                        axes: Optional[Sequence[str]] = None, *,
                        num_processes: int = 1,
                        devices: Optional[Sequence] = None):
    """A mesh whose device layout is PROCESS-MAJOR: process p's devices fill
    rows [p * rows_per_process, (p+1) * rows_per_process) of the leading
    mesh axis, contiguously.

    On a real multi-host cluster every jax process contributes its local
    devices; sorting the global device list by (process_index, id) and
    reshaping row-major means each host's devices land in one contiguous
    block of the leading (worker) axis -- so the phase-1 worker collectives
    of the EF-BV trainers stay host-local wherever the axis splits cleanly.
    On a single process with fake XLA host devices (CPU CI) the same
    construction simulates the multi-host layout: pass ``num_processes`` to
    validate the geometry, the device order is already process-major.

    Axis-name defaults match :func:`make_mesh`.  Requires the leading axis
    divisible by ``num_processes`` (each process owns whole rows) and
    ``prod(shape)`` total devices.
    """
    import jax
    from jax.sharding import Mesh

    shape = tuple(shape)
    if axes is None:
        defaults = ("pod", "data", "model")
        if len(shape) > len(defaults):
            raise ValueError(
                f"make_multihost_mesh has default axis names for up to "
                f"{len(defaults)} mesh dims {defaults}, got shape {shape} "
                f"with {len(shape)} dims -- pass axes= explicitly")
        axes = defaults[-len(shape):]
    axes = tuple(axes)
    multihost_worker_shape(shape[0], num_processes)

    if devices is None:
        devices = sorted(jax.devices(),
                         key=lambda d: (d.process_index, d.id))
    devices = list(devices)
    total = int(np.prod(shape))
    if len(devices) != total:
        raise ValueError(
            f"mesh shape {shape} needs {total} devices, got {len(devices)}")
    per_process = total // num_processes
    owners = [getattr(d, "process_index", 0) for d in devices]
    if len(set(owners)) > 1:
        # real multi-host: device i must belong to process i // per_process.
        # (Single-process fake host devices -- the simulated multi-process
        # CPU regime -- all report process 0; there the contiguous blocks
        # ARE the simulated processes and only the geometry is checked.)
        for i, owner in enumerate(owners):
            if owner != i // per_process:
                raise ValueError(
                    f"device list is not process-major: device {i} belongs "
                    f"to process {owner}, expected process "
                    f"{i // per_process} -- sort by (process_index, id) "
                    f"before building the mesh")
    dev_array = np.asarray(devices, dtype=object).reshape(shape)
    return Mesh(dev_array, axes,
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def process_worker_slice(shape: Sequence[int], num_processes: int,
                         process_index: int) -> range:
    """The linear worker indices process ``process_index`` owns under the
    process-major layout of :func:`make_multihost_mesh` (its slice of the
    global batch, for per-host data pipelines).  The model axis, if any, is
    the trailing mesh dim and does not change worker numbering."""
    shape = tuple(shape)
    # all axes except the trailing 'model' axis are worker axes; a 1-d mesh
    # is all workers (mesh_worker_count convention in core/spec.py)
    n = int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]
    multihost_worker_shape(shape[0], num_processes)
    if not 0 <= process_index < num_processes:
        raise ValueError(f"process_index {process_index} out of range for "
                         f"{num_processes} processes")
    per = n // num_processes
    return range(process_index * per, (process_index + 1) * per)


def worker_axes(mesh) -> Tuple[str, ...]:
    """The EF-BV 'worker' axes of a mesh = every axis except 'model'.

    The paper's n = product of these axis sizes."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def num_workers(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in worker_axes(mesh)]))
