"""Train an LFM2-MoE LM (tpudp/models/lfm2.py) from a config file.

The file is a ``config.json`` of the ``lfm2_moe`` model type, with this
repo's keys for the expert share beside it (``num_experts`` held of
``num_experts_routed``, from ``first_expert``); the benchmark's own
``perf/configs/lfm2_8b_a1b.json`` is one (chip 0 of four at the published
widths, 507.8 M parameters held).  Data-parallel over all devices, AdamW,
a host-drawn batch before every step as examples/train_gpt2.py, synthetic
tokens (no egress).

  # the benchmark's model and step (one v5e chip: 13.1 GB by the AOT compile):
  python examples/train_lfm2.py --batch-size 4 --seq-len 8192 --track-moe

  # CPU smoke at the file's tiny preset:
  python examples/train_lfm2.py --platform cpu --preset rehearsal \
      --batch-size 8 --seq-len 128 --steps 4 --log-every 2 --track-moe
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", default=os.path.join(
        ROOT, "perf", "configs", "lfm2_8b_a1b.json"))
    p.add_argument("--preset", default=None,
                   help="a key of the file whose mapping overrides its "
                        "top-level keys (e.g. 'rehearsal')")
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--log-every", type=int, default=5)
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--attn-impl", choices=["dense", "flash"], default="flash")
    p.add_argument("--moe-impl", choices=["dense", "gmm"], default="gmm")
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--track-moe", action="store_true",
                   help="accumulate the expert layers' counters on the "
                        "device (TrainState.obs_moe) and print their ratios")
    p.add_argument("--platform", type=str, default=None)
    args = p.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # no-op on the CPU backend
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudp.models.lfm2 import Lfm2, Lfm2Config
    from tpudp.train import (init_state, make_optimizer, make_train_step,
                             moe_metrics)
    from tpudp.utils.watchdog import check_finite

    with open(args.config) as f:
        config = json.load(f)
    if args.preset:
        config.update(config[args.preset])
    cfg = Lfm2Config.from_dict(
        config, attn_impl=args.attn_impl, moe_impl=args.moe_impl,
        remat=not args.no_remat, dtype=jnp.dtype(args.dtype))
    model = Lfm2(cfg)
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("data",))
    tx = make_optimizer(learning_rate=args.lr, weight_decay=0.0,
                        optimizer="adamw")
    state = jax.device_put(
        init_state(model, tx, input_shape=(1, 16), track_moe=args.track_moe),
        NamedSharding(mesh, P()))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    print(f"[lfm2] params={n_params / 1e6:.1f}M layers={cfg.layer_types} "
          f"experts {cfg.first_expert}..{cfg.first_expert + cfg.num_experts - 1}"
          f" of {cfg.num_experts_routed or cfg.num_experts} "
          f"devices={len(devices)} batch={args.batch_size} "
          f"seq_len={args.seq_len} dtype={args.dtype}")
    step = make_train_step(model, tx, mesh, "allreduce")
    sharding = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(1)

    prev_cum, t0 = 0.0, time.perf_counter()
    for it in range(1, args.steps + 1):
        tokens = rng.integers(0, cfg.vocab_size,
                              (args.batch_size, args.seq_len + 1), np.int32)
        state, _ = step(state, jax.device_put(tokens[:, :-1], sharding),
                        jax.device_put(tokens[:, 1:], sharding))
        if it % args.log_every == 0:
            jax.block_until_ready(state.params)  # honest timing edge
            cum = check_finite(float(state.loss_sum), step=it)
            dt = time.perf_counter() - t0
            tok_s = args.log_every * args.batch_size * args.seq_len / dt
            print(f"step {it}: loss {(cum - prev_cum) / args.log_every:.4f} "
                  f"({tok_s:,.0f} tok/s)")
            prev_cum, t0 = cum, time.perf_counter()
    if args.track_moe:
        print("[lfm2] " + " ".join(f"{k}={v:.4f}" for k, v in
                                   moe_metrics(state.obs_moe).items()))


if __name__ == "__main__":
    main()
