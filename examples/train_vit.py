"""Train a Vision Transformer with tpudp's DP harness.

Beyond-parity example: the reference's only model family is a CNN
(``src/Part 1/model.py:30-46``); this drives the ViT family — the
architecture that maps best onto the MXU — through the same sync ladder,
with the owned Pallas flash-attention kernel engaged at ImageNet geometry
(``--image-size 224 --patch-size 14`` -> 256 tokens, 128-aligned).

  # CIFAR-geometry ViT-S on one TPU chip, synthetic data:
  python examples/train_vit.py --steps 30

  # ViT-B at ImageNet geometry with the flash kernel:
  python examples/train_vit.py --variant base --image-size 224 \
      --patch-size 14 --num-classes 1000 --attn flash --batch-size 128

  # simulated 8-chip DP on CPU (tiny sizes):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_vit.py --platform cpu --batch-size 16 --steps 4 \
      --train-size 64 --layers 2 --d-model 64
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--variant", choices=["tiny", "small", "base"],
                   default="small")
    p.add_argument("--layers", type=int, default=None,
                   help="override the variant's depth")
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--patch-size", type=int, default=4)
    p.add_argument("--num-classes", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=256,
                   help="GLOBAL batch, split across devices")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--train-size", type=int, default=2048,
                   help="synthetic train-set size")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--optimizer", choices=["adamw", "sgd"], default="adamw")
    # ladder-derived choices (see EXAMPLE_SYNC_CHOICES for rationale)
    from tpudp.parallel.sync import EXAMPLE_SYNC_CHOICES

    p.add_argument("--sync", choices=EXAMPLE_SYNC_CHOICES,
                   default="allreduce")
    p.add_argument("--attn", choices=["dense", "flash"], default="dense")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--save-checkpoint", type=str, default=None,
                   metavar="DIR",
                   help="save the final TrainState to DIR/step_<steps> (orbax)")
    p.add_argument("--platform", type=str, default=None)
    args = p.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    if args.save_checkpoint:
        # Fail fast on a missing orbax / unwritable DIR before
        # any compute is spent (tpudp/utils/checkpoint.py).
        from tpudp.utils.checkpoint import ensure_writable

        ensure_writable(args.save_checkpoint)
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # no-op on the CPU backend (smoke mode)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.data.cifar10 import Dataset
    from tpudp.data.loader import DataLoader
    from tpudp.mesh import batch_sharding, make_mesh
    from tpudp.models.vit import ViT, ViTConfig
    from tpudp.train import init_state, make_optimizer, make_train_step

    mesh = make_mesh()
    n_dev = mesh.size
    if args.batch_size % n_dev:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"{n_dev} devices")

    geometry = {"tiny": (6, 3, 192), "small": (12, 6, 384),
                "base": (12, 12, 768)}[args.variant]
    layers = args.layers or geometry[0]
    d_model = args.d_model or geometry[2]
    heads = geometry[1] if args.d_model is None else max(1, d_model // 64)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = ViT(ViTConfig(
        image_size=args.image_size, patch_size=args.patch_size,
        num_classes=args.num_classes, num_layers=layers, num_heads=heads,
        d_model=d_model, dtype=dtype, attn_impl=args.attn))
    tx = make_optimizer(learning_rate=args.lr, optimizer=args.optimizer)
    state = init_state(
        model, tx, input_shape=(1, args.image_size, args.image_size, 3))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    step = make_train_step(model, tx, mesh, args.sync, donate=False,
                           remat=args.remat)
    print(f"[vit-{args.variant}] params={n_params/1e6:.1f}M devices={n_dev} "
          f"tokens={model.config.num_patches} attn={args.attn} "
          f"sync={args.sync} batch={args.batch_size} dtype={args.dtype}")

    rng = np.random.default_rng(0)
    ds = Dataset(
        rng.integers(0, 256, size=(args.train_size, args.image_size,
                                   args.image_size, 3)).astype(np.uint8),
        rng.integers(0, args.num_classes,
                     size=args.train_size).astype(np.int32),
    )
    # ImageNet normalization at ImageNet geometry (as train_resnet.py does);
    # the loader's CIFAR-10 defaults apply only at CIFAR geometry.
    norm = {}
    if args.image_size != 32:
        norm = dict(mean=np.asarray((0.485, 0.456, 0.406), np.float32),
                    std=np.asarray((0.229, 0.224, 0.225), np.float32))
    loader = DataLoader(ds, args.batch_size, train=True, seed=0, **norm)
    if len(loader) == 0:
        raise SystemExit(
            f"error: --train-size {args.train_size} yields zero full batches "
            f"of --batch-size {args.batch_size} (drop_last training loader)")
    sharding = batch_sharding(mesh)

    it = iter(loader)
    prev_cum, t0 = 0.0, time.perf_counter()
    for i in range(1, args.steps + 1):
        try:
            images, labels, _w = next(it)
        except StopIteration:
            loader.set_epoch(i)
            it = iter(loader)
            images, labels, _w = next(it)
        images = jax.device_put(images, sharding)
        labels = jax.device_put(labels, sharding)
        state, _ = step(state, images, labels)
        if i % args.log_every == 0:
            jax.block_until_ready(state.params)  # honest timing edge
            cum = float(state.loss_sum)
            dt = time.perf_counter() - t0
            ips = args.log_every * args.batch_size / dt
            print(f"step {i}: loss {(cum - prev_cum) / args.log_every:.4f} "
                  f"({ips:,.1f} images/s)")
            prev_cum, t0 = cum, time.perf_counter()

    if args.save_checkpoint:
        from tpudp.utils.checkpoint import save_checkpoint

        ckpt = save_checkpoint(
            os.path.join(args.save_checkpoint, f"step_{args.steps}"), state)
        print(f"[vit] saved checkpoint {ckpt}")


if __name__ == "__main__":
    main()
