"""The gradient-synchronization strategy ladder — the reference's core.

Each strategy is a function ``(grad_tree, axis_name) -> grad_tree`` applied
between backward and optimizer step inside a ``shard_map``-ed train step,
mirroring where the reference calls its sync
(between ``loss.backward()`` and ``optimizer.step()``,
``src/Part 2a/main.py:94-96``).  All strategies produce the *mean* gradient
on every device — the observable contract of every rung of the ladder.

  none        Part 1  — no collective; single-device baseline
              (src/Part 1/main.py:32-58 has no sync call).
  coordinator Part 2a — semantics of gather-to-rank-0 → mean → scatter
              (src/Part 2a/main.py:117-127).  SPMD has no privileged rank, so
              every device all-gathers and means — numerically identical,
              without the rank-0 serialization bottleneck, but NOT the same
              traffic shape: all_gather lands N× the gradient payload on
              every device (vs 2 wire crossings per non-root rank in the
              hub pattern), and BASELINE.md measures it at ~10.5× psum's
              wall time on the 8-device mesh.  It exists for semantic
              parity with the reference's rung, not as a fast path.
  allreduce   Part 2b — built-in collective: psum then divide by world size
              (src/Part 2b/main.py:116-119: all_reduce(SUM); grad /= size).
  ring        north-star extra — hand-rolled ring all-reduce from ppermute,
              single-direction (the schedule that measures fastest on every
              mesh timed so far — BASELINE.md sweep; round-3 VERDICT #5
              reverted the faith-based bidirectional default).  ring_uni is
              a kept alias of the same schedule; ring_bidir selects the two
              counter-rotating half-buffers (both ICI directions of a real
              torus — a hypothesis benchmarks/collective_bench.py will
              test the moment a multi-chip window exists).
  allreduce_hd / allreduce_a2a  beyond-reference manual flavors —
              Rabenseifner halving-doubling (2*log2 N pairwise exchanges)
              and all_to_all+local-sum reduce-scatter (2 dispatches); same
              bandwidth-optimal wire bytes, different latency profiles
              (measured head-to-head in BASELINE.md).
  allreduce_bf16  beyond-reference extra — gradients cross the wire as
              bfloat16 (half the collective bytes), restored after the mean.
  allreduce_int8  beyond-reference extra — int8 on the wire via the
              ppermute ring (quarter the bytes; exact integer accumulation;
              effective precision log2(127 // N) bits; lossy, opt-in).
  auto        Part 3  — like DDP (src/Part 3/main.py:61), sync is *implicit*:
              the strategy is still psum/N, but the step is compiled as one
              XLA program so the compiler schedules/overlaps the collective
              with the backward pass — the TPU equivalent of DDP's bucketed
              overlap, obtained from the compiler rather than hand-written
              C++ hooks.  Also selectable as a GSPMD path (jit + sharding
              annotations, no explicit collectives) via Trainer(spmd_mode=
              'gspmd').
"""

from __future__ import annotations

from typing import Callable

import jax
from jax import lax

from tpudp.parallel.ring import (a2a_all_reduce, all_reduce_mean_tree,
                                 hd_all_reduce, ring_all_reduce_mean)

SyncFn = Callable[[object, str], object]


def sync_none(grads, axis_name: str):
    """Part 1: no synchronization."""
    del axis_name
    return grads


def sync_coordinator(grads, axis_name: str):
    """Part 2a semantics: every device ends with the mean gradient via
    all-gather + local mean (rank-0 asymmetry is a Gloo API artifact, not
    observable behavior — SURVEY.md §7 hard parts).  Traffic cost is N×
    the gradient payload per device — measured ~10.5× psum (BASELINE.md);
    see the module docstring."""
    def gather_mean(g):
        return lax.all_gather(g, axis_name).mean(axis=0)
    return jax.tree.map(gather_mean, grads)


def sync_allreduce(grads, axis_name):
    """Part 2b: all-reduce(SUM) then divide by world size.  ``axis_name``
    may be a tuple of mesh axes (DP x SP meshes reduce over both)."""
    n = lax.psum(1, axis_name)  # product of axis sizes, handles tuples
    return jax.tree.map(lambda g: lax.psum(g, axis_name) / n, grads)


def sync_ring(grads, axis_name: str):
    """North-star: hand-rolled ppermute ring all-reduce over one flat
    buffer — single-direction, the schedule that measures fastest on
    every mesh timed so far (BASELINE.md sweep; see the module
    docstring for why the bidirectional default was reverted)."""
    return ring_all_reduce_mean(grads, axis_name)


# Kept alias: round-2/3 CLIs, banked bench rows, and examples refer to the
# single-direction schedule by this name.
sync_ring_uni = sync_ring


def sync_ring_bidir(grads, axis_name: str):
    """Two counter-rotating half-buffers — both ICI directions of a TPU
    torus in flight at once.  Unmeasured on real multi-chip hardware (the
    torus-overlap win is a hypothesis; on the simulated mesh the doubled
    ppermute dispatch count makes it ~1.6x slower than the single ring,
    BASELINE.md) — selectable for benchmarks, not the default."""
    return ring_all_reduce_mean(grads, axis_name, bidirectional=True)


def sync_allreduce_hd(grads, axis_name):
    """Manual collective, latency-optimal flavor: recursive
    halving-doubling (Rabenseifner) — same bandwidth-optimal wire bytes
    as the ring in 2*log2(N) steps instead of 2*(N-1).  See
    tpudp.parallel.ring.hd_all_reduce for the schedule trade-offs."""
    return all_reduce_mean_tree(grads, axis_name, hd_all_reduce)


def sync_allreduce_a2a(grads, axis_name):
    """Manual collective, collective-fusion flavor: reduce-scatter from
    ``all_to_all`` + local sum, then all-gather — two dispatches moving
    the same bandwidth-optimal bytes as the ring.  See
    tpudp.parallel.ring.a2a_all_reduce."""
    return all_reduce_mean_tree(grads, axis_name, a2a_all_reduce)


def sync_allreduce_bf16(grads, axis_name):
    """Bandwidth-compressed all-reduce (beyond-reference): gradients cross
    the interconnect as bfloat16 — half the bytes of the fp32 ladder rungs —
    and are restored to their original dtype after the mean.

    bf16 keeps fp32's exponent range, so the cast cannot overflow the way
    fp16 compression does (no loss scaling needed); what it costs is
    mantissa precision (~8 bits) on the cast AND in the reduction — the
    psum's add runs on the bf16 operands, so rounding error grows with the
    axis size (O(sqrt(N) ulp for random signs).  Forward/backward math and
    the optimizer update stay in the model's compute dtype; on CIFAR-scale
    meshes the trajectory tracks fp32 closely (equivalence tested to loose
    tolerance in tests/test_sync.py).  For very large meshes where bf16
    tree accumulation is a concern, prefer the uncompressed ``allreduce``
    rung — this one trades precision for exactly the wire/reduce bytes.
    """
    import jax.numpy as jnp

    n = lax.psum(1, axis_name)

    def compress_reduce(g):
        total = lax.psum(g.astype(jnp.bfloat16), axis_name)
        return (total / n).astype(g.dtype)

    return jax.tree.map(compress_reduce, grads)


def sync_allreduce_int8(grads, axis_name):
    """8-bit **wire** compression (beyond-reference): the whole gradient
    pytree rides the ppermute ring as ONE flat int8 buffer — every hop of
    both ring phases moves 1 byte/element, a quarter of the fp32 rungs'
    wire traffic (a psum of upcast integers would move 4 bytes/element and
    save nothing; the ring is what makes the claim real).

    Scheme: one shared scale for the flat buffer (``pmax`` of the max-abs,
    one scalar collective), then each device quantizes onto a grid clipped
    to ``+/-(127 // N)`` — so the worst-case ring sum, N devices all at the
    clip bound with the same sign, is ``N * (127 // N) <= 127``: every
    partial sum along the reduce-scatter ring stays strictly within int8
    and accumulation is EXACT (integer adds; no bf16-style accumulation
    rounding).  Clipping at the *quantized* level is what provides the
    guarantee: with plain round, N near-identical max-magnitude gradients
    each rounding 127/N UP (e.g. round(63.5)=64 at N=2) would sum to 128
    and wrap to -128, sign-flipping the largest gradient element.  The
    cost is quantization resolution: effective precision is
    ``log2(127 // N)`` bits of the buffer's max-abs (~6 bits at N=2, ~4 at
    N=8).  Stateless, no error feedback — a lossy opt-in for
    bandwidth-bound meshes (the torch-DDP compress-hook idea pushed to 8
    bits); tested for mean-accuracy bounds, training closeness, and the
    no-wraparound guarantee in tests/test_sync.py.
    """
    import jax.numpy as jnp

    from tpudp.parallel.ring import (flatten_tree, int8_headroom_quantize,
                                     ring_all_reduce)

    n = lax.axis_size(axis_name)
    if n == 1:
        return grads
    flat, unflatten = flatten_tree(grads, dtype=jnp.float32)
    q, unit = int8_headroom_quantize(flat, axis_name)
    total = ring_all_reduce(q, axis_name)  # int8 on the wire, exact adds
    mean = total.astype(jnp.float32) * (unit / n)
    return unflatten(mean)


# 'auto' shares the allreduce math; the difference is scheduling, which XLA
# owns because the whole train step (fwd+bwd+sync+update) is one jitted
# program.  Kept as a distinct name so the CLI ladder maps 1:1 to the parts.
sync_auto = sync_allreduce

# Wire-schedule provenance for evidence rows (round-4 advisor): the label
# "ring" changed meaning in round 4 (bidirectional -> single-direction,
# per the measured sweep in parallel/ring.py), so bench.py and
# benchmarks/collective_bench.py stamp their rows with the direction the
# labeled rung actually ran; a ring row WITHOUT the stamp is a pre-flip
# capture and measured a different schedule.
RING_DIRECTION: dict[str, str] = {
    "ring": "uni",
    "ring_uni": "uni",
    "ring_bidir": "bidir",
}

SYNC_STRATEGIES: dict[str, SyncFn] = {
    "none": sync_none,
    "coordinator": sync_coordinator,
    "allreduce": sync_allreduce,
    "allreduce_bf16": sync_allreduce_bf16,
    "allreduce_int8": sync_allreduce_int8,
    "ring": sync_ring,
    "ring_uni": sync_ring_uni,
    "ring_bidir": sync_ring_bidir,
    "allreduce_hd": sync_allreduce_hd,
    "allreduce_a2a": sync_allreduce_a2a,
    "auto": sync_auto,
}


# What the example CLIs offer as --sync choices: the full ladder minus
# 'none', which under multi-device DP silently trains divergent replicas.
# One definition so every example stays in lockstep.
EXAMPLE_SYNC_CHOICES = tuple(sorted(set(SYNC_STRATEGIES) - {"none"}))


def get_sync(name: str) -> SyncFn:
    try:
        return SYNC_STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown sync strategy {name!r}; choose from {sorted(SYNC_STRATEGIES)}"
        ) from None
