"""Headline benchmark: VGG-11/CIFAR-10 training throughput (images/sec).

Runs the fused jitted DP train step (sync=allreduce by default; BENCH_SYNC
selects another rung on multi-chip slices) at the reference's global batch
size 256 and prints ONE JSON line.

``vs_baseline`` compares against the north-star denominator — the reference's
"4-node Gloo images/sec" (BASELINE.json:5).  The reference publishes no
numbers, so the denominator is re-measured on this machine:
``benchmarks/torch_reference_bench.py`` (torch CPU, 4 threads, batch 256)
times the identical workload, and 4-node Gloo is bounded above by 4x that
single-process number (perfect scaling, zero comm cost — a *generous*
baseline).  See BASELINE.md "Measured values".

The measurement runs in THIS process (one process per chip: a parent that
touched JAX would hold the device a child needs).  There is no fallback:
without an accelerator, or when the step fails, the script exits non-zero
and prints no value — a stored number is never re-emitted.

Env knobs: BENCH_BATCH, BENCH_STEPS, BENCH_WARMUP, BENCH_DTYPE,
BENCH_PARAM_DTYPE (bfloat16 casts params + momentum), BENCH_DONATE=0, BENCH_SYNC (gradient-sync rung,
validated against the ladder minus 'none').
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Measured by benchmarks/torch_reference_bench.py on this machine (1-core
# CPU host; reference config: batch 256, 4 torch threads).  Recorded in
# BASELINE.md.  4-node Gloo upper bound = 4 * single-process.  Two
# measurements exist (66.17 on 2026-07-29 under session load, 92.42 on
# 2026-07-31 on an idle host); the FASTER one is used — the conservative
# choice for our ratio, since a stronger baseline lowers vs_baseline.
TORCH_CPU_IMAGES_PER_SEC = 92.42
BASELINE_4NODE_GLOO_IPS = 4 * TORCH_CPU_IMAGES_PER_SEC

# Most ADVERSE defensible denominator (round-5, VERDICT r4 #6): the 92.42
# measurement comes from a 1-core VM, so a real 4-core reference node
# would beat it by an unknown host factor.  Arithmetic bound instead:
# measured host SINGLE-THREAD dense-GEMM peak (139.7 GFLOP/s fp32,
# highest of the 2026-08-01 runs of
# `benchmarks/torch_reference_bench.py --gemm-check`) x 4 reference
# threads with a full turbo core each and ZERO parallelization loss,
# / analytic 916.6 MFLOP/image train cost -> <=609.7 img/s/node; x4
# nodes with zero Gloo comm cost.  Every efficiency assumption favors
# the reference (convs at GEMM peak, BN/ReLU free, perfect scaling), so
# a real cluster sits strictly below this.  vs_baseline_adverse is the
# ratio no host correction can overturn.  Kept at the HIGHEST bound ever
# measured; the --gemm-check drift guard flags any upward divergence.
ADVERSE_4NODE_GLOO_IPS = 2438.98

METRIC = "vgg11_cifar10_images_per_sec_per_chip"



def main() -> None:
    """One measurement; prints the JSON line on success."""
    sync = _requested_sync()  # fail fast on a bad BENCH_SYNC
    param_dtype = _requested_param_dtype()  # fail fast on a bad dtype

    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit(
            "error: bench.py measures an accelerator and JAX found only "
            f"{dev.platform!r} devices; no value printed (CPU smoke runs: "
            "the Part CLIs with --platform cpu)")
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax.numpy as jnp
    import numpy as np

    from tpudp.mesh import make_mesh
    from tpudp.models.vgg import VGG11
    from tpudp.train import init_state, make_optimizer, make_train_step
    from tpudp.utils.flops import mfu, train_step_flops, vgg_fwd_flops

    batch = int(os.environ.get("BENCH_BATCH", 256))
    steps = int(os.environ.get("BENCH_STEPS", 50))
    warmup = int(os.environ.get("BENCH_WARMUP", 5))
    dtype_name = os.environ.get("BENCH_DTYPE", "bfloat16")
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else jnp.float32

    mesh = make_mesh()
    n_dev = mesh.size
    device_kind = dev.device_kind
    model = VGG11(dtype=dtype)
    tx = make_optimizer()
    state = init_state(model, tx)
    # BENCH_PARAM_DTYPE=bfloat16 casts params AND momentum to bf16 —
    # halves weight-side HBM traffic; its effect on the chip is not
    # measured.
    if param_dtype == "bfloat16":
        state = state.replace(
            params=jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                state.params),
            opt_state=jax.tree.map(
                lambda a: (a.astype(jnp.bfloat16)
                           if isinstance(a, jax.Array)
                           and a.dtype == jnp.float32 else a),
                state.opt_state))
    # Donated state buffers: XLA updates params/momentum in place instead of
    # copying the full TrainState every step (the loop always rebinds
    # ``state`` to the step's output, so the invalidated input is never
    # reused).  BENCH_DONATE=0 opts out for A/B comparison.
    donate = os.environ.get("BENCH_DONATE", "1") != "0"
    # BENCH_SYNC selects the gradient-sync rung (default the Part 2b
    # psum); on a multi-chip slice this lets the headline bench compare
    # ring/hd/a2a/int8 wire flavors without code edits.
    step = make_train_step(model, tx, mesh, sync=sync, donate=donate)

    rng = np.random.default_rng(0)
    images = jax.device_put(
        jnp.asarray(rng.normal(size=(batch, 32, 32, 3)), jnp.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")),
    )
    labels = jax.device_put(
        jnp.asarray(rng.integers(0, 10, size=batch), jnp.int32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")),
    )


    # The first step compiles, so it always runs outside the window (its
    # loss, next to the final one, shows the timed program really trains).
    # Timing edges: jax.block_until_ready on the step's output state.
    state, loss = step(state, images, labels)
    initial_loss = float(loss)
    for _ in range(max(warmup - 1, 0)):
        state, loss = step(state, images, labels)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state, images, labels)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0

    ips = steps * batch / dt
    ips_per_chip = ips / n_dev
    sec_per_step = dt / steps

    # Single-chip perf criterion: analytic model FLOPs / (time * peak).
    flops_per_step = train_step_flops(vgg_fwd_flops(batch))
    step_mfu = mfu(flops_per_step, sec_per_step, device_kind, n_dev)
    # Independent cross-check: XLA's own FLOPs count for the compiled
    # step.  Post-fusion and PER PARTITION (the SPMD program of one
    # device), so on an N-device mesh it is ~analytic/N.  Sanity signal,
    # not the MFU basis.
    from tpudp.utils.flops import xla_cost_flops

    xla_flops = xla_cost_flops(step, state, images, labels)

    # North-star companion metric (BASELINE.json:2): wall-time of the DP
    # gradient all-reduce over this mesh, on a pytree shaped like the
    # model's gradients.  On a 1-device mesh the all-reduce compiles to a
    # no-op, so a wall time would measure only dispatch overhead — report
    # n/a instead of a misreadable number.
    coll = {"allreduce_wall_time_s": None, "bytes": None, "gbps": None}
    if n_dev == 1:
        coll_note = ("n/a (1 chip: DP all-reduce compiles to a no-op; a "
                     "wall time here would be dispatch overhead only)")
    else:
        coll_note = None
        from tpudp.utils.profiler import measure_collective

        grad_shaped = jax.tree.map(jnp.zeros_like, state.params)
        coll = measure_collective(mesh, grad_shaped, steps=10, warmup=2)

    print(json.dumps({
        "metric": METRIC,
        "value": round(ips_per_chip, 1),
        "unit": "images/sec/chip",
        "measured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        "git_rev": _git_rev(),
        "vs_baseline": round(ips / BASELINE_4NODE_GLOO_IPS, 2),
        "vs_baseline_adverse": round(ips / ADVERSE_4NODE_GLOO_IPS, 2),
        "baseline_adverse_4node_gloo_images_per_sec": ADVERSE_4NODE_GLOO_IPS,
        "images_per_sec_total": round(ips, 1),
        "platform": dev.platform,
        "devices": n_dev,
        "device_kind": device_kind,
        "global_batch": batch,
        "dtype": dtype_name,
        "param_dtype": param_dtype,
        "sync": sync,
        # Which wire schedule a ring-family label measured (round-4
        # advisor: the 'ring' label flipped bidirectional->uni, so rows
        # must say which one ran); None for non-ring rungs.
        "ring_direction": _ring_direction(sync),
        "sec_per_step": round(sec_per_step, 5),
        "mfu": round(step_mfu, 4) if step_mfu is not None else None,
        "model_flops_per_step": flops_per_step,
        "xla_flops_per_partition": xla_flops,
        "baseline_4node_gloo_images_per_sec": BASELINE_4NODE_GLOO_IPS,
        "initial_loss": round(initial_loss, 4),
        "final_loss": round(float(loss), 4),
        "loss_decreased": bool(float(loss) < initial_loss),
        "grad_allreduce_wall_time_s": (
            round(coll["allreduce_wall_time_s"], 6)
            if coll["allreduce_wall_time_s"] is not None else None),
        "grad_bytes": coll["bytes"],
        "allreduce_gbps": (round(coll["gbps"], 2)
                           if coll["gbps"] is not None else None),
        "allreduce_note": coll_note,
    }))


def _git_rev() -> str | None:
    """Short rev of the code being measured, stamped into the row (None
    outside a git checkout)."""
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=here)
        if out.returncode == 0 and out.stdout.strip():
            rev = out.stdout.strip()
            # Scope the dirty check to CODE: result files under
            # bench_results/ change with every run.
            dirty = subprocess.run(
                ["git", "status", "--porcelain", "--", ".",
                 ":!bench_results"],
                capture_output=True, text=True, timeout=10, cwd=here)
            if dirty.returncode == 0 and dirty.stdout.strip():
                rev += "-dirty"
            return rev
    except Exception:  # noqa: BLE001 — provenance stamp must never kill a run
        pass
    return None


def _requested_param_dtype() -> str:
    """Validated before any device work for the same reason as
    ``_requested_sync``: a typo (e.g. ``bf16``) must fail fast, not
    silently measure fp32 params while the row claims otherwise."""
    pd = os.environ.get("BENCH_PARAM_DTYPE", "float32")
    if pd not in ("float32", "bfloat16"):
        raise SystemExit(
            f"error: BENCH_PARAM_DTYPE={pd!r} is not a valid param dtype; "
            "choose float32 or bfloat16")
    return pd


def _requested_sync() -> str:
    """The sync rung this run measures — validated before any device work
    so a typo fails fast.  'none' is rejected: on a multi-chip mesh it
    trains divergent replicas and its zero-comm throughput would read as
    a real DP number."""
    sync = os.environ.get("BENCH_SYNC", "allreduce")
    from tpudp.parallel.sync import EXAMPLE_SYNC_CHOICES

    if sync not in EXAMPLE_SYNC_CHOICES:
        raise SystemExit(
            f"error: BENCH_SYNC={sync!r} is not a benchmarkable rung; "
            f"choose from {', '.join(EXAMPLE_SYNC_CHOICES)}")
    return sync


def _ring_direction(sync: str) -> str | None:
    """Wire-schedule stamp for ring-family rungs (see
    tpudp.parallel.sync.RING_DIRECTION); None for every other rung."""
    from tpudp.parallel.sync import RING_DIRECTION

    return RING_DIRECTION.get(sync)


if __name__ == "__main__":
    main()
