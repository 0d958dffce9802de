"""Train ResNet-50/101/152 with tpudp's DP harness at ImageNet geometry.

Beyond-parity example (BASELINE.json configs[3]: "ResNet-50 on ImageNet-1k
under the same DDP harness").  Zero-egress environment: ImageNet itself is
not downloadable, so the pipeline trains on an ImageNet-*shaped* synthetic
set by default (224x224x3 uint8, 1000 classes) through the SAME host data
path as CIFAR (native/numpy fused crop-flip-normalize at 224, sharded
sampler, background prefetch) — point --imagenet-root at an
`{train,val}/<class>/*.npy` tree to use real data.

  # one TPU chip:
  python examples/train_resnet.py --steps 30

  # simulated 8-chip DP on CPU (tiny sizes):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_resnet.py --platform cpu --batch-size 16 --steps 4 \
      --train-size 64 --image-size 64 --depth 50
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def load_npy_tree(root: str, split: str, image_size: int):
    """Load a ``root/{split}/<class>/*.npy`` tree into one uint8 Dataset.

    Each ``.npy`` holds a single HWC uint8 image (or an (N, H, W, 3) stack);
    labels are assigned by sorted class-directory order.  Images must already
    be ``image_size`` square — decode/resize happens offline, keeping this
    loader dependency-free in the zero-egress image."""
    import numpy as np

    from tpudp.data.cifar10 import Dataset

    split_dir = os.path.join(root, split)
    classes = sorted(d for d in os.listdir(split_dir)
                     if os.path.isdir(os.path.join(split_dir, d)))
    if not classes:
        raise SystemExit(f"no class directories under {split_dir}")
    images, labels = [], []
    for label, cls in enumerate(classes):
        cls_dir = os.path.join(split_dir, cls)
        for fname in sorted(os.listdir(cls_dir)):
            if not fname.endswith(".npy"):
                continue
            arr = np.load(os.path.join(cls_dir, fname))
            if arr.ndim == 3:
                arr = arr[None]
            if arr.shape[1:] != (image_size, image_size, 3):
                raise SystemExit(
                    f"{cls_dir}/{fname}: expected ({image_size}, "
                    f"{image_size}, 3) images, got {arr.shape[1:]}")
            images.append(arr.astype(np.uint8))
            labels.append(np.full(arr.shape[0], label, np.int32))
    if not images:
        raise SystemExit(f"no .npy files under {split_dir}")
    return Dataset(np.concatenate(images), np.concatenate(labels))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--depth", type=int, choices=[50, 101, 152], default=50)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--batch-size", type=int, default=256,
                   help="GLOBAL batch, split across devices")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--train-size", type=int, default=2048,
                   help="synthetic train-set size")
    p.add_argument("--lr", type=float, default=0.1)
    # ladder-derived so new rungs are selectable without touching every
    # example (see EXAMPLE_SYNC_CHOICES for the 'none' exclusion rationale)
    from tpudp.parallel.sync import EXAMPLE_SYNC_CHOICES

    p.add_argument("--sync", choices=EXAMPLE_SYNC_CHOICES,
                   default="allreduce")
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="bfloat16")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--save-checkpoint", type=str, default=None,
                   metavar="DIR",
                   help="save the final TrainState to DIR/step_<steps> (orbax)")
    p.add_argument("--platform", type=str, default=None)
    p.add_argument("--imagenet-root", type=str, default=None)
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
    from tpudp.models import ResNet50, ResNet101, ResNet152
    from tpudp.train import init_state, make_optimizer, make_train_step

    mesh = make_mesh()
    n_dev = mesh.size
    if args.batch_size % n_dev:
        raise SystemExit(f"--batch-size {args.batch_size} must divide by "
                         f"{n_dev} devices")

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = {50: ResNet50, 101: ResNet101, 152: ResNet152}[args.depth](
        num_classes=args.num_classes, dtype=dtype)
    tx = make_optimizer(learning_rate=args.lr)
    state = init_state(
        model, tx, input_shape=(1, args.image_size, args.image_size, 3))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    step = make_train_step(model, tx, mesh, args.sync, donate=False)
    print(f"[resnet{args.depth}] params={n_params/1e6:.1f}M devices={n_dev} "
          f"sync={args.sync} image={args.image_size} batch={args.batch_size} "
          f"dtype={args.dtype}")

    if args.imagenet_root:
        ds = load_npy_tree(args.imagenet_root, "train", args.image_size)
        if int(ds.labels.max()) >= args.num_classes:
            raise SystemExit(
                f"--imagenet-root has {int(ds.labels.max()) + 1} class "
                f"directories but --num-classes is {args.num_classes}")
        print(f"[resnet{args.depth}] loaded {len(ds.images)} images / "
              f"{int(ds.labels.max()) + 1} classes from {args.imagenet_root}")
    else:
        rng = np.random.default_rng(0)
        ds = Dataset(
            rng.integers(0, 256, size=(args.train_size, args.image_size,
                                       args.image_size, 3)).astype(np.uint8),
            rng.integers(0, args.num_classes,
                         size=args.train_size).astype(np.int32),
        )
    loader = DataLoader(ds, args.batch_size, train=True, seed=0,
                        mean=np.asarray(IMAGENET_MEAN, np.float32),
                        std=np.asarray(IMAGENET_STD, np.float32))
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
        print(f"[resnet] saved checkpoint {ckpt}")


if __name__ == "__main__":
    main()
