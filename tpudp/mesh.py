"""Device-mesh and multi-host bootstrap.

TPU-native replacement for the reference's process-group layer
(``src/Part 2a/main.py:148-153``: MASTER_ADDR/MASTER_PORT env vars +
``dist.init_process_group('gloo', rank, world_size)``).  In the SPMD world
there is no process group: a single :class:`jax.sharding.Mesh` spans every
device, collectives ride the ICI/DCN fabric, and multi-host rendezvous is
``jax.distributed.initialize`` whose coordinator address plays the role of
the reference's ``--master`` flag.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def initialize_distributed(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    port: int = 6585,
) -> None:
    """Multi-host rendezvous — the ``--master``/``--rank`` analogue.

    Maps the reference CLI (``src/Part 2a/main.py:158-165``: ``--master``,
    ``--num-nodes``, ``--rank``; hardcoded port 6585 at ``:172``) onto
    ``jax.distributed.initialize``.  On a single host (all arguments None)
    this is a no-op: one process already sees every local device.
    """
    if coordinator is None and num_processes in (None, 1):
        return
    _enable_cpu_cross_process_collectives()
    jax.distributed.initialize(
        coordinator_address=f"{coordinator}:{port}",
        num_processes=num_processes,
        process_id=process_id,
    )


def _enable_cpu_cross_process_collectives() -> None:
    """Multi-process runs on the CPU backend (the dry-run/soak rungs:
    N OS processes, each with virtual CPU devices) need a cross-process
    collectives implementation — the default ``'none'`` computes only
    intra-process and a 2-process psum silently reduces half the mesh.
    Select gloo unless a non-CPU platform was EXPLICITLY requested
    (those bring their own fabric): an unset platform on a CPU-only
    machine auto-selects the cpu backend, and skipping it there would
    leave the silent half-mesh psum in place.  The option only
    configures the CPU backend, so setting it under a TPU auto-select
    is inert."""
    platforms = jax.config.jax_platforms or ""  # JAX_PLATFORMS lands here
    if platforms and "cpu" not in platforms:
        return
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def make_mesh(num_devices: int | None = None, axis_name: str = DATA_AXIS) -> Mesh:
    """Build a 1-D data-parallel mesh over (the first ``num_devices``) devices.

    The mesh is the TPU-native "world": its size is the reference's
    ``world_size`` (``--num-nodes``), and the ``data`` axis is the axis all
    sync strategies reduce over.
    """
    devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def axis_is_bound(axis_name: str | None) -> bool:
    """True when tracing inside shard_map/pmap with this named axis bound.
    Model init happens outside any mapped context — axis-aware layers (ring
    attention, MoE all_to_all) use this to fall back to their dense path so
    ``model.init`` works without a mesh (param shapes are identical)."""
    if axis_name is None:
        return False
    try:
        jax.lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def make_mesh_nd(shape: dict[str, int], devices=None) -> Mesh:
    """Build an N-D mesh from ``{axis_name: size}`` (insertion-ordered).

    Multi-axis analogue of :func:`make_mesh` for the DPxTP / DPxSP / DPxPP
    rungs — e.g. ``make_mesh_nd({"data": 2, "model": 4})``.  Axis order
    matters on real hardware: put the fastest-communicating axis (tensor/
    sequence parallel) innermost so its collectives ride the shortest ICI
    links.
    """
    explicit = devices is not None
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(list(shape.values())))
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    if n < len(devices) and not explicit:
        import warnings

        warnings.warn(
            f"make_mesh_nd({shape}) uses {n} of {len(devices)} devices; the "
            f"other {len(devices) - n} idle. Pass devices= explicitly to "
            "silence.", stacklevel=2)
    grid = np.asarray(devices[:n]).reshape(tuple(shape.values()))
    return Mesh(grid, tuple(shape.keys()))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a global batch: split along the leading (batch) axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for replicated state (params, optimizer state)."""
    return NamedSharding(mesh, P())
