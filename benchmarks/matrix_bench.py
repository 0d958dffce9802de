"""Full benchmark matrix — the BASELINE.json config list, measured.

Covers (BASELINE.json configs[0-4] + the GSPMD/coordinator rungs):

  part1_single   VGG-11 single-device baseline (reference Part 1)
  dp_psum        VGG-11 DP, fused psum all-reduce (Part 2b analogue)
  dp_ring        VGG-11 DP, manual ppermute ring all-reduce (north star)
  dp_coordinator VGG-11 DP, gather->mean->broadcast (Part 2a analogue)
  dp_gspmd       VGG-11 DP, XLA-partitioned (Part 3 analogue)
  resnet50       ResNet-50 at ImageNet geometry, synthetic data, DP psum
  gpt2_small     GPT-2-small (124M) DP, tokens/sec/chip
  gpt2_flash     GPT-2 with the owned Pallas flash kernel at t=2048
  llama_gqa      LLaMA family (RoPE/RMSNorm/SwiGLU, 4:1 GQA), tokens/sec/chip

Prints one JSON line per config (machine-readable) and a final summary
line.  Steps donate their state buffers (in-place param/momentum update on
device, as real training does).  Each VGG DP config also reports the measured wall-time of its
gradient collective so ring-vs-psum is a direct comparison.  Run on the
TPU chip by default; MATRIX_PLATFORM=cpu (+ forced device count) for the
simulated-mesh smoke mode.  Knobs: MATRIX_STEPS, MATRIX_WARMUP,
MATRIX_CONFIGS (comma-separated subset).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.bench_gaps import MATRIX_CONFIGS  # noqa: E402 (stdlib-only import)

# (name, distributed?, sync, spmd_mode) — mesh is bound at runtime.
VGG_LADDER = (
    ("part1_single", False, "none", "single"),
    ("dp_psum", True, "allreduce", "shard_map"),
    ("dp_ring", True, "ring", "shard_map"),
    ("dp_coordinator", True, "coordinator", "shard_map"),
    ("dp_gspmd", True, "allreduce", "gspmd"),
)

# The watcher resumes by diffing result rows against the canonical registry
# (tools.bench_gaps); a config added on one side but not the other would
# silently never be measured.  Checked at import time, before any jax/TPU
# work, and raising (not assert) so `python -O` can't strip it.
if [n for n, *_ in VGG_LADDER] + ["resnet50", "gpt2_small", "gpt2_flash",
                                  "llama_gqa"] != list(MATRIX_CONFIGS):
    raise ValueError("matrix configs out of sync with tools.bench_gaps")


def measure(step, state, args, steps, warmup):
    """Fenced sec/step for a (state, *args) -> (state, loss) step."""
    import jax

    for _ in range(warmup):
        state, loss = step(state, *args)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, loss = step(state, *args)
    jax.block_until_ready(state.params)
    return (time.perf_counter() - t0) / steps, float(loss)


def main() -> None:
    import jax

    if os.environ.get("MATRIX_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["MATRIX_PLATFORM"])
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # no-op on the CPU backend (smoke mode)
    import jax.numpy as jnp
    import numpy as np

    from tpudp.mesh import make_mesh
    from tpudp.models import VGG11, ResNet50
    from tpudp.models.gpt2 import gpt2_small
    from tpudp.train import init_state, make_optimizer, make_train_step
    from tpudp.utils.flops import (gpt2_fwd_flops, mfu, resnet_fwd_flops,
                                   train_step_flops, vgg_fwd_flops)
    from tpudp.utils.profiler import measure_collective

    steps = int(os.environ.get("MATRIX_STEPS", 30))
    warmup = int(os.environ.get("MATRIX_WARMUP", 3))
    only = os.environ.get("MATRIX_CONFIGS")
    only = set(only.split(",")) if only else None

    mesh = make_mesh()
    n_dev = mesh.size
    kind = jax.devices()[0].device_kind

    def config_rng(name):
        """Per-config seeded stream (round-5 advisor): a MATRIX_CONFIGS
        subset run (the watcher's gap-resume path) must train each config
        on the SAME tokens as a full sweep, so no config's draws may
        depend on which other configs ran before it.  crc32, not hash():
        str hash is salted per interpreter, which would reshuffle every
        config's data on every relaunch."""
        import zlib

        return np.random.default_rng(zlib.crc32(name.encode()))

    # The VGG ladder's shared batch keeps its historical seed-0 stream
    # (drawn unconditionally before any config runs, so it never depended
    # on subset selection — banked VGG loss rows stay comparable).
    rng = np.random.default_rng(0)
    results = []

    def emit(name, sec_per_step, loss, *, unit, per_sec, flops,
             extra=None, devices=None):
        # devices defaults to the mesh size; single-device configs
        # (part1_single) pass devices=1 so per-chip numbers aren't divided
        # by chips they never used.
        nd = n_dev if devices is None else devices
        row = {
            "config": name,
            "sec_per_step": round(sec_per_step, 5),
            "unit": unit,
            "value": round(per_sec / nd, 1),
            "total_per_sec": round(per_sec, 1),
            "devices": nd,
            "device_kind": kind,
            "mfu": (round(m, 4)
                    if (m := mfu(flops, sec_per_step, kind, nd))
                    is not None else None),
            "final_loss": round(loss, 4),
        }
        if extra:
            row.update(extra)
        results.append(row)
        print(json.dumps(row), flush=True)

    # ---- VGG-11 ladder -------------------------------------------------
    vgg_batch = int(os.environ.get("MATRIX_VGG_BATCH", 256))
    vgg_flops = train_step_flops(vgg_fwd_flops(vgg_batch))
    images = jnp.asarray(rng.normal(size=(vgg_batch, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=vgg_batch), jnp.int32)
    data_sh = jax.sharding.NamedSharding(mesh,
                                         jax.sharding.PartitionSpec("data"))

    vgg_ladder = [(name, mesh if dist else None, sync, mode)
                  for name, dist, sync, mode in VGG_LADDER]
    def run_config(name, fn):
        """One config crashing (OOM, transient backend fault) must not
        cost the remaining rows; the run still exits non-zero."""
        try:
            fn()
        except Exception as exc:  # noqa: BLE001
            row = {"config": name,
                   "error": f"{type(exc).__name__}: {exc}"[:500]}
            results.append(row)
            print(json.dumps(row), flush=True)

    grad_tree = None

    def run_vgg(name, m, sync, mode):
        nonlocal grad_tree
        model = VGG11(dtype=jnp.bfloat16)
        tx = make_optimizer()
        state = init_state(model, tx)
        step = make_train_step(model, tx, m, sync, spmd_mode=mode,
                               donate=True)
        x = images if m is None else jax.device_put(images, data_sh)
        y = labels if m is None else jax.device_put(labels, data_sh)
        sec, loss = measure(step, state, (x, y), steps, warmup)
        extra = {"sync": sync, "spmd_mode": mode}
        # Wire-schedule stamp for ring-family rungs (round-4 advisor: the
        # 'ring' label flipped bidirectional->uni; a row must say which
        # schedule it measured, and the matrix resume gate refuses
        # unstamped dp_ring rows as measurements of the renamed rung).
        from tpudp.parallel.sync import RING_DIRECTION

        if sync in RING_DIRECTION:
            extra["ring_direction"] = RING_DIRECTION[sync]
        if m is not None and n_dev > 1:
            if grad_tree is None:
                grad_tree = jax.tree.map(jnp.zeros_like, state.params)
            coll = measure_collective(mesh, grad_tree, steps=10, warmup=2)
            extra["grad_allreduce_wall_time_s"] = round(
                coll["allreduce_wall_time_s"], 6)
        emit(name, sec, loss, unit="images/sec/chip",
             per_sec=vgg_batch / sec, flops=vgg_flops, extra=extra,
             devices=1 if m is None else None)

    for name, m, sync, mode in vgg_ladder:
        if only and name not in only:
            continue
        run_config(name, lambda: run_vgg(name, m, sync, mode))

    # ---- ResNet-50 at ImageNet geometry --------------------------------
    def run_resnet():
        rn_batch = int(os.environ.get("MATRIX_RESNET_BATCH", 256))
        image_size = int(os.environ.get("MATRIX_RESNET_IMAGE", 224))
        model = ResNet50(dtype=jnp.bfloat16)
        tx = make_optimizer()
        state = init_state(model, tx,
                           input_shape=(1, image_size, image_size, 3))
        step = make_train_step(model, tx, mesh, "allreduce", donate=True)
        rrng = config_rng("resnet50")
        x = jax.device_put(
            jnp.asarray(rrng.normal(size=(rn_batch, image_size, image_size,
                                          3)),
                        jnp.float32), data_sh)
        y = jax.device_put(
            jnp.asarray(rrng.integers(0, 1000, size=rn_batch), jnp.int32),
            data_sh)
        sec, loss = measure(step, state, (x, y), steps, warmup)
        emit("resnet50", sec, loss, unit="images/sec/chip",
             per_sec=rn_batch / sec,
             flops=train_step_flops(
                 resnet_fwd_flops(rn_batch, image_size=image_size)),
             extra={"global_batch": rn_batch, "image_size": image_size})

    if only is None or "resnet50" in only:
        run_config("resnet50", run_resnet)

    # ---- LM configs: one harness, three model builds -------------------
    # Each config draws its tokens from its OWN config_rng(name) stream,
    # so dispatch order and MATRIX_CONFIGS subsets cannot change what any
    # config trains on (round-5 advisor: the old shared stream made
    # subset-run loss values incomparable with full-sweep banked rows).
    def run_lm(name, batch_env, seq_env, default_batch, default_seq,
               build, flops_fn, extra_fn):
        g_batch = int(os.environ.get(batch_env, default_batch))
        seq = int(os.environ.get(seq_env, default_seq))
        model = build(seq)
        cfg = model.config
        tx = make_optimizer(learning_rate=0.01)
        state = init_state(model, tx, input_shape=(1, seq))
        step = make_train_step(model, tx, mesh, "allreduce", donate=True)
        toks = jax.device_put(
            jnp.asarray(config_rng(name).integers(0, cfg.vocab_size,
                                                  size=(g_batch, seq)),
                        jnp.int32), data_sh)
        tgts = jax.device_put(jnp.roll(toks, -1, axis=1), data_sh)
        sec, loss = measure(step, state, (toks, tgts), steps, warmup)
        emit(name, sec, loss, unit="tokens/sec/chip",
             per_sec=g_batch * seq / sec,
             flops=train_step_flops(flops_fn(cfg, g_batch, seq)),
             extra={"global_batch": g_batch, "seq_len": seq,
                    **extra_fn(cfg)})

    def gpt2_flops(cfg, b, t):
        return gpt2_fwd_flops(b, t, num_layers=cfg.num_layers,
                              d_model=cfg.d_model,
                              vocab_size=cfg.vocab_size,
                              mlp_ratio=cfg.mlp_ratio)

    # GPT-2-small (124M) DP
    if only is None or "gpt2_small" in only:
        run_config("gpt2_small", lambda: run_lm(
            "gpt2_small", "MATRIX_GPT2_BATCH", "MATRIX_GPT2_SEQ", 8, 1024,
            lambda seq: gpt2_small(dtype=jnp.bfloat16),
            gpt2_flops, lambda cfg: {}))

    # GPT-2 with the owned Pallas flash kernel inside a real training step
    # (not a micro-bench) at t=2048 where the dense (t, t) score tensor
    # starts to hurt; tokens/sec/chip comparable against gpt2_small.
    if only is None or "gpt2_flash" in only:
        run_config("gpt2_flash", lambda: run_lm(
            "gpt2_flash", "MATRIX_GPT2FLASH_BATCH", "MATRIX_GPT2FLASH_SEQ",
            4, 2048,
            lambda seq: gpt2_small(
                dtype=jnp.bfloat16, attn_impl="flash", max_seq_len=seq,
                num_layers=int(os.environ.get("MATRIX_GPT2FLASH_LAYERS",
                                              12)),
                d_model=(dm := int(os.environ.get(
                    "MATRIX_GPT2FLASH_DMODEL", 768))),
                num_heads=dm // 64),
            gpt2_flops, lambda cfg: {"attn_impl": "flash"}))

    # LLaMA family (round 5: RoPE/RMSNorm/SwiGLU, 4:1 GQA) in the same DP
    # harness — tokens/sec/chip comparable against gpt2_small.
    if only is None or "llama_gqa" in only:
        from tpudp.models.llama import llama_small
        from tpudp.utils.flops import llama_fwd_flops

        run_config("llama_gqa", lambda: run_lm(
            "llama_gqa", "MATRIX_LLAMA_BATCH", "MATRIX_LLAMA_SEQ", 8, 1024,
            lambda seq: llama_small(dtype=jnp.bfloat16, max_seq_len=seq,
                                    num_layers=12, d_model=768,
                                    num_heads=12, num_kv_heads=3),
            lambda cfg, b, t: llama_fwd_flops(
                b, t, num_layers=cfg.num_layers, d_model=cfg.d_model,
                vocab_size=cfg.vocab_size, hidden=cfg.hidden,
                num_heads=cfg.num_heads, kv_heads=cfg.kv_heads),
            lambda cfg: {"num_kv_heads": cfg.kv_heads}))

    print(json.dumps({"matrix": results}))
    failed = [r["config"] for r in results if "error" in r]
    if failed:
        raise SystemExit(f"error: matrix configs failed: {failed}")


if __name__ == "__main__":
    main()
