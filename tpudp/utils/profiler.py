"""Profiling and tracing.

The reference's entire observability story is ``time.time()`` brackets and
``print`` (SURVEY.md §5: no profiler, no traces).  tpudp keeps those
parity metrics (the Trainer's window prints) and adds
the TPU-native layer the reference never had:

  * :func:`trace` — capture a real XLA/TPU profile (TensorBoard `trace
    viewer` format) around any region.
  * :func:`measure_collective` — the north-star "grad all-reduce wall-time"
    metric (BASELINE.json:2): times a jitted shard_map psum over a pytree
    shaped exactly like the model's gradients, with
    ``jax.block_until_ready`` at the timing edges (a true barrier on the
    chip — measured, PR 21: it returns when the dispatched work has run,
    and a device->host fetch after it adds only the transfer).
"""

from __future__ import annotations

import time
from typing import Any

import jax
from jax.sharding import Mesh, PartitionSpec as P

from tpudp.mesh import DATA_AXIS
# trace moved to tpudp.obs (PR 11 folded the one-off timing/tracing APIs
# under the telemetry package); re-exported here so existing
# `from tpudp.utils.profiler import trace` imports keep working.
from tpudp.obs.tracing import trace  # noqa: F401


def measure_collective(
    mesh: Mesh,
    grad_tree: Any,
    *,
    axis: str = DATA_AXIS,
    steps: int = 20,
    warmup: int = 3,
) -> dict:
    """Wall-time one mean-all-reduce of ``grad_tree`` over ``mesh``.

    Returns ``{"allreduce_wall_time_s", "bytes", "gbps"}`` — the measured
    cost of exactly the collective every DP sync strategy issues per step
    (reference analogue: the Gloo ``all_reduce`` in
    ``src/Part 2b/main.py:118``, there paid once PER PARAMETER; here one
    fused all-reduce over the whole tree).
    """
    size = mesh.shape[axis]

    def body(tree):
        return jax.tree.map(
            lambda g: jax.lax.psum(g, axis) / size, tree)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(),), out_specs=P(), check_vma=False))

    tree = jax.device_put(
        grad_tree, jax.sharding.NamedSharding(mesh, P()))
    out = fn(tree)
    jax.block_until_ready(out)  # compile + warm
    for _ in range(warmup):
        out = fn(tree)
    jax.block_until_ready(out)

    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(out)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / steps

    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(grad_tree))
    # ring all-reduce moves 2(n-1)/n of the payload per device
    wire = 2 * (size - 1) / size * nbytes if size > 1 else 0
    return {
        "allreduce_wall_time_s": dt,
        "bytes": nbytes,
        "gbps": (wire / dt / 1e9) if dt > 0 else 0.0,
    }
