"""Where do the non-MXU 57% of the VGG bench step go? (VERDICT r2 #4)

bench.py's headline MFU is 0.43; the north-star asks either to lift it
past 0.5 or to document the ceiling with trace evidence.  This bench
produces that evidence two independent ways:

1. ABLATION TIMING — the fused step re-measured with pieces removed, so
   each piece's share is a subtraction of fenced wall times:
     full        fwd + bwd + (no-op 1-chip sync) + SGD update, donated
     fwd_bwd     gradient computation only (no optimizer update)
     fwd_only    training-mode forward only
     no_bn       full step on a BN-free VGG clone — BatchNorm's share
                 (BN is elementwise + reductions: pure non-MXU time)
     bf16_params full step with bf16 params AND momentum — halves the
                 per-step param/momentum HBM traffic; if this moves the
                 needle the step is partly weight-bandwidth-bound
2. XLA TRACE — jax.profiler around the full step, parsed with
   jax.profiler.ProfileData: per-op self-time aggregated by op name,
   classified MXU (convolution/dot) vs other (fusions, reductions,
   copies).  Name-based classification is approximate but it is the
   on-device schedule, not a model.

One JSON line per variant plus one ``trace_ops`` line (redirect to
bench_results/mfu.jsonl).  Knobs: MFU_BATCH (256), MFU_STEPS
(30), MFU_WARMUP (3), MFU_PLATFORM (cpu smoke), MFU_TRACE=0 (skip trace),
MFU_VARIANTS (comma-separated subset of
``full,fwd_bwd,fwd_only,no_bn,bf16_params``; default all).

MFU_VARIANTS selects a subset when chip time is short (``full,bf16_params``
is the denominator plus the one actionable lever).  ``full`` always runs
even when not listed: every other variant's share/speedup field is a ratio
against the same-run ``sec_full`` (cross-run ratios would mix conditions).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax

    if os.environ.get("MFU_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["MFU_PLATFORM"])
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # no-op on the CPU backend (smoke mode)
    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.vgg import CONFIGS, VGG11
    from tpudp.train import init_state, make_optimizer, make_train_step
    from tpudp.utils.flops import mfu, train_step_flops, vgg_fwd_flops

    batch = int(os.environ.get("MFU_BATCH", 256))
    steps = int(os.environ.get("MFU_STEPS", 30))
    # >=1: the pre-timing fence needs at least one completed dispatch
    warmup = max(int(os.environ.get("MFU_WARMUP", 3)), 1)
    # Single-sourced from the gap helper: the watcher pipes bench_gaps.py
    # output straight into MFU_VARIANTS, so a variant list that drifted
    # between the two files would make the strict validation below kill
    # the stage on every window (bench_gaps is stdlib-only — importing it
    # here costs nothing).
    from tools.bench_gaps import MFU_VARIANTS as all_variants

    raw = os.environ.get("MFU_VARIANTS", "")
    selected = {v.strip() for v in raw.split(",") if v.strip()} or set(
        all_variants)
    unknown = selected - set(all_variants)
    if unknown:
        raise SystemExit(f"error: MFU_VARIANTS contains unknown variants "
                         f"{sorted(unknown)}; choose from {all_variants}")
    kind = jax.devices()[0].device_kind
    flops = train_step_flops(vgg_fwd_flops(batch))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=batch), jnp.int32)

    class VGGNoBN(nn.Module):
        """BN-ablated clone of the bench model (attribution only)."""

        @nn.compact
        def __call__(self, inp, train=False):
            h = inp.astype(jnp.bfloat16)
            for v in CONFIGS["VGG11"]:
                if v == "M":
                    h = nn.max_pool(h, (2, 2), strides=(2, 2))
                else:
                    h = nn.relu(nn.Conv(int(v), (3, 3), padding=1,
                                        dtype=jnp.bfloat16)(h))
            h = h.reshape((h.shape[0], -1))
            return nn.Dense(10, dtype=jnp.bfloat16)(h).astype(jnp.float32)

    def timed(fn, fence_tree):
        for _ in range(warmup):
            out = fn()
        jax.block_until_ready(fence_tree(out))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn()
        jax.block_until_ready(fence_tree(out))
        return (time.perf_counter() - t0) / steps, out

    def emit(variant, sec, extra=None):
        row = {"variant": variant, "sec_per_step": round(sec, 6),
               "mfu": (round(m, 4)
                       if (m := mfu(flops, sec, kind, 1)) is not None
                       else None),
               "images_per_sec": round(batch / sec, 1),
               "device_kind": kind, "global_batch": batch}
        if extra:
            row.update(extra)
        print(json.dumps(row), flush=True)
        return row

    model = VGG11(dtype=jnp.bfloat16)
    tx = make_optimizer()

    # full step (the bench.py configuration, mesh-free single device)
    state = init_state(model, tx)
    step = make_train_step(model, tx, None, "none", spmd_mode="single",
                           donate=True)
    st = state

    def full():
        nonlocal st
        st, loss = step(st, x, y)
        return st

    sec_full, _ = timed(full, lambda s: s.params)
    emit("full", sec_full)

    # Pipeline bubble attribution (analytic, free): the 1F1B schedule's
    # idle fraction (P-1)/(V*M + P-1) for every geometry registered in
    # tools/bench_gaps.PIPELINE_CONFIGS, reported alongside MFU so the
    # pipeline rung's measured throughput gap to PP=1 can be attributed
    # — a geometry whose measured gap exceeds its bubble is losing time
    # to transport or the sharded update, not the schedule.  Always
    # emitted (no timing involved); `ideal_mfu_scale` is the factor the
    # bubble alone would take off the full step's MFU.
    from benchmarks.pipeline_bench import _cfg as _pipe_cfg
    from benchmarks.pipeline_bench import parse_config
    from tools.bench_gaps import PIPELINE_CONFIGS
    from tpudp.utils.flops import pipeline_bubble_fraction

    micro = _pipe_cfg()["micro"]
    print(json.dumps({
        "kind": "pipeline_bubble", "n_microbatches": micro,
        "geometries": [
            {"config": name, "stages": pp, "dp": dp, "interleave": v,
             "bubble_fraction": round(
                 pipeline_bubble_fraction(pp, micro, v), 4),
             "ideal_mfu_scale": round(
                 1.0 - pipeline_bubble_fraction(pp, micro, v), 4)}
            for name, (pp, dp, v) in
            ((n, parse_config(n)) for n in PIPELINE_CONFIGS)],
    }), flush=True)

    if {"fwd_bwd", "fwd_only"} & selected:
        state2 = init_state(model, tx)

    if "fwd_bwd" in selected:
        # fwd+bwd only (no optimizer update)
        def loss_fn(params, batch_stats):
            variables = {"params": params, "batch_stats": batch_stats}
            logits, upd = model.apply(variables, x, train=True,
                                      mutable=["batch_stats"])
            one = jax.nn.one_hot(y, 10)
            return (-jnp.mean(jnp.sum(one * jax.nn.log_softmax(logits), -1)),
                    upd)

        grad_fn = jax.jit(jax.grad(loss_fn, has_aux=True))

        def fwd_bwd():
            return grad_fn(state2.params, state2.batch_stats)

        sec_gb, _ = timed(fwd_bwd, lambda out: out[0])
        emit("fwd_bwd", sec_gb,
             {"optimizer_share_of_full": round(1 - sec_gb / sec_full, 4)})

    if "fwd_only" in selected:
        # fwd only (train mode, batch_stats mutable — the bench's fwd path)
        fwd = jax.jit(lambda p, b: model.apply(
            {"params": p, "batch_stats": b}, x, train=True,
            mutable=["batch_stats"]))

        def fwd_only():
            return fwd(state2.params, state2.batch_stats)

        sec_f, _ = timed(fwd_only, lambda out: out[0])
        emit("fwd_only", sec_f,
             {"share_of_full": round(sec_f / sec_full, 4)})

    if "no_bn" in selected:
        # BN ablated
        nobn = VGGNoBN()
        state3 = init_state(nobn, tx)
        step3 = make_train_step(nobn, tx, None, "none", spmd_mode="single",
                                donate=True)
        st3 = state3

        def full_nobn():
            nonlocal st3
            st3, _ = step3(st3, x, y)
            return st3

        sec_nobn, _ = timed(full_nobn, lambda s: s.params)
        emit("no_bn", sec_nobn,
             {"bn_share_of_full": round(1 - sec_nobn / sec_full, 4)})

    if "bf16_params" in selected:
        # bf16 params + momentum: halve weight-side HBM traffic
        state4 = init_state(model, tx)
        state4 = state4.replace(
            params=jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                state4.params),
            opt_state=jax.tree.map(
                lambda a: (a.astype(jnp.bfloat16)
                           if isinstance(a, jax.Array)
                           and a.dtype == jnp.float32 else a),
                state4.opt_state))
        st4 = state4

        def full_bf16p():
            nonlocal st4
            st4, _ = step(st4, x, y)
            return st4

        try:
            sec_bf16, _ = timed(full_bf16p, lambda s: s.params)
            emit("bf16_params", sec_bf16,
                 {"speedup_vs_full": round(sec_full / sec_bf16, 4)})
        except Exception as exc:  # noqa: BLE001 — attribution row only
            print(json.dumps({"variant": "bf16_params",
                              "error": f"{type(exc).__name__}: {exc}"[:300]}),
                  flush=True)

    # XLA trace of the full step, parsed per-op
    if os.environ.get("MFU_TRACE", "1") != "0":
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            jax.profiler.start_trace(td)
            for _ in range(3):
                # rebind: the step donates its input state buffers
                st, _ = step(st, x, y)
            jax.block_until_ready(st.params)
            jax.profiler.stop_trace()
            ops = _parse_trace(td)
        if ops:
            total = sum(d for _, d in ops)
            mxu = sum(d for n, d in ops
                      if "conv" in n.lower() or "dot" in n.lower())
            print(json.dumps({
                "kind": "trace_ops",
                "mxu_named_share": round(mxu / total, 4) if total else None,
                "top_ops": [{"name": n[:80],
                             "share": round(d / total, 4)}
                            for n, d in ops[:12]],
            }), flush=True)


def _parse_trace(trace_dir: str):
    """Aggregate per-op self durations from the newest xplane file;
    returns [(name, total_duration)] sorted descending, [] on failure."""
    import glob

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        return []
    try:
        data = ProfileData.from_file(files[-1])
        agg: dict[str, float] = {}

        def eat(line):
            for ev in line.events:
                name = ev.name
                # runtime/bookkeeping markers, python frames, region ends
                if (name.startswith(("$", "end:", "ThreadpoolListener",
                                     "TaskDispatcher", "ThunkExecutor"))):
                    continue
                agg[name] = agg.get(name, 0.0) + (ev.duration_ns or 0)

        device_planes = [p for p in data.planes
                         if "/device:" in p.name.lower()
                         or "/tpu:" in p.name.lower()]
        if device_planes:
            for plane in device_planes:
                for line in plane.lines:
                    eat(line)
        else:
            # CPU backend: op events live in tf_XLAPjRt* executor lines of
            # the host plane (the 'python' line is host frames — skip).
            for plane in data.planes:
                for line in plane.lines:
                    if line.name.startswith("tf_XLAPjRt"):
                        eat(line)
        return sorted(agg.items(), key=lambda kv: -kv[1])
    except Exception:  # noqa: BLE001 — trace parsing is best-effort
        return []


if __name__ == "__main__":
    main()
