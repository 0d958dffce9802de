"""CLI shared by the Part entrypoints.

Maps the reference's flags (``src/Part 2a/main.py:156-175``: ``--master``
required IP, ``--num-nodes``, ``--rank``, ``--epochs``; hardcoded port 6585
and global batch 256 at ``:172-173``) onto the SPMD world:

  * ``--master``/``--rank``/``--num-nodes`` become the
    ``jax.distributed.initialize`` coordinator/process_id/num_processes —
    OPTIONAL on a single host, where one process already owns all devices
    (the reference requires one manually-launched process per node).
  * world size for gradient math is the device-mesh size, not a process
    count; ``--num-devices`` restricts the mesh for ladder comparisons.
"""

from __future__ import annotations

import argparse

import jax

from tpudp.data import DataLoader, ShardedSampler, load_cifar10
from tpudp.mesh import DATA_AXIS, initialize_distributed, make_mesh
from tpudp.train import Trainer

GLOBAL_BATCH_SIZE = 256  # reference constant, src/Part 2a/main.py:173
PORT = 6585  # reference constant, src/Part 2a/main.py:172


def build_parser(description: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--master", type=str, default=None,
                   help="coordinator IP for multi-host (reference --master)")
    p.add_argument("--num-nodes", type=int, default=None,
                   help="number of host processes (reference --num-nodes)")
    p.add_argument("--rank", type=int, default=None,
                   help="this host's process id (reference --rank)")
    p.add_argument("--epochs", type=int, default=1,
                   help="epochs to train (reference default 1)")
    p.add_argument("--num-devices", type=int, default=None,
                   help="restrict the mesh to N devices (default: all)")
    p.add_argument("--batch-size", type=int, default=GLOBAL_BATCH_SIZE,
                   help="GLOBAL batch size (split across devices)")
    p.add_argument("--data-root", type=str, default="./data")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timing-mode", choices=["fused", "split"], default="fused")
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--model", choices=["vgg11", "vgg13", "vgg16", "vgg19"],
                   default="vgg11",
                   help="VGG variant (reference default VGG-11; the "
                        "reference's config table defines 13/16/19 but "
                        "never exports them — src/Part 1/model.py:3-8,49-50 "
                        "— tpudp makes the whole table launchable)")
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="save TrainState each epoch and auto-resume from the "
                        "latest checkpoint (beyond-reference capability)")
    p.add_argument("--checkpoint-async", action="store_true",
                   help="overlap checkpoint writes with the next epoch's "
                        "training (orbax async; the epoch barrier no longer "
                        "waits for filesystem IO)")
    p.add_argument("--keep-checkpoints", type=int, default=None, metavar="N",
                   help="retain only the newest N epoch checkpoints, "
                        "deleting older step_* dirs after each save")
    p.add_argument("--platform", type=str, default=None,
                   help="force a JAX platform (e.g. 'cpu' with "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                        "to simulate an N-chip mesh on one host)")
    p.add_argument("--synthetic-train-size", type=int, default=50_000,
                   help="synthetic-fallback train set size (smoke runs)")
    p.add_argument("--synthetic-test-size", type=int, default=10_000)
    p.add_argument("--data-backend", choices=["auto", "native", "numpy"],
                   default="auto",
                   help="host augmentation backend: fused C++/OpenMP kernel "
                        "(tpudp/native) or bit-identical numpy")
    p.add_argument("--eval-only", action="store_true",
                   help="restore the latest checkpoint from "
                        "--checkpoint-dir, run the test-set evaluation "
                        "(reference eval loop, src/Part 2a/main.py:130-145) "
                        "and exit without training")
    p.add_argument("--sync-bn", action="store_true",
                   help="cross-replica BatchNorm (torch SyncBatchNorm "
                        "analogue): psum batch statistics over the data "
                        "axis so N devices at batch B/N normalize exactly "
                        "like one device at batch B. Default keeps the "
                        "reference's local-stats semantics (src/Part "
                        "2a/main.py:59-68). shard_map rungs only")
    p.add_argument("--spmd-mode", choices=["shard_map", "gspmd"],
                   default=None,
                   help="Part 3 (auto rung) only: how the compiler-"
                        "scheduled sync is obtained. 'shard_map' (default) "
                        "runs per-device with an explicit psum XLA overlaps "
                        "— BatchNorm keeps the reference's LOCAL per-rank "
                        "batch statistics (DDP syncs gradients only, src/"
                        "Part 3/main.py:61). 'gspmd' lets XLA's partitioner "
                        "insert the collectives from sharding annotations; "
                        "note BatchNorm then normalizes over the GLOBAL "
                        "batch (SyncBN-like semantics — pinned by tests/"
                        "test_train.py::test_gspmd_bn_is_syncbn_semantics)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize activations during backward "
                        "(jax.checkpoint): identical gradients, lower peak "
                        "HBM, one extra forward's FLOPs")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="split each device batch into N sequential "
                        "microbatches, accumulating gradients before the "
                        "sync+update (trade steps for activation memory; "
                        "beyond-reference capability)")
    p.add_argument("--prefetch", type=int, default=2,
                   help="batches prepared ahead on a background thread "
                        "(reference DataLoader num_workers=2 analogue); "
                        "0 disables")
    p.add_argument("--verify-replicas", action="store_true",
                   help="after each epoch, assert every replicated "
                        "param/BN-stat shard is bit-identical across "
                        "devices (torch DDP's parameter-verification "
                        "analogue; catches silent DP desync — "
                        "tpudp/utils/consistency.py)")
    p.add_argument("--metrics-jsonl", type=str, default=None, metavar="PATH",
                   help="append machine-readable metrics (one JSON line per "
                        "train window / eval / epoch) to PATH, alongside the "
                        "reference-format prints; process 0 only")
    p.add_argument("--profile-dir", type=str, default=None,
                   help="capture an XLA/TPU profiler trace of the training "
                        "run into this directory (TensorBoard trace-viewer "
                        "format; beyond-reference capability)")
    p.add_argument("--step-timeout", type=float, default=None,
                   help="failure detection: exit if training makes no "
                        "iteration progress for this many seconds (wedged "
                        "collective, dead peer host) so the scheduler can "
                        "restart + --checkpoint-dir resume. Must exceed one "
                        "full log window (log-every steps) plus first-step "
                        "compile time. The reference hangs forever in this "
                        "case (SURVEY.md §5); default: disabled. With "
                        "--resilience the hang recovers IN-PROCESS instead "
                        "of exiting")
    p.add_argument("--resilience", action="store_true",
                   help="run training under the in-process fault supervisor "
                        "(tpudp/resilience.py, docs/RESILIENCE.md): NaN/"
                        "spike windows roll back to the last verified "
                        "checkpoint and replay deterministically, step "
                        "faults and hangs retry in-process after an "
                        "emergency dump, loader failures restart the "
                        "pipeline at the exact batch offset. Requires "
                        "--checkpoint-dir; the trajectory stays "
                        "bit-identical to an uninterrupted run")
    p.add_argument("--max-rollbacks", type=int, default=None, metavar="N",
                   help="divergence-rollback budget before the original "
                        "error escalates (--resilience only; default 3)")
    p.add_argument("--spike-factor", type=float, default=None, metavar="X",
                   help="roll back when a window loss exceeds X times the "
                        "trailing-median window loss (--resilience only; "
                        "default: spike detection off, NaN windows still "
                        "roll back)")
    p.add_argument("--flight-dir", type=str, default=None, metavar="DIR",
                   help="observability (tpudp.obs): dump the flight "
                        "recorder — the last N train/eval spans and "
                        "recovery events — into per-host "
                        "flightrec-*.json under DIR on watchdog "
                        "timeouts, rollbacks, and vote timeouts "
                        "(default: the TPUDP_FLIGHT_DIR env var; unset "
                        "= dumps disabled)")
    p.add_argument("--metrics-port", type=int, default=None, metavar="N",
                   help="observability (tpudp.obs): serve a Prometheus-"
                        "style text endpoint with the live Trainer."
                        "metrics() snapshot on localhost:N/metrics "
                        "(process 0 only; 0 picks a free port)")
    return p


def run_part(sync: str, description: str, *, spmd_mode: str = "shard_map",
             single_device: bool = False, argv=None) -> Trainer:
    """Shared Part-N driver: parse flags, build mesh/data/model, fit."""
    import jax.numpy as jnp

    from tpudp.models import VGG11, VGG13, VGG16, VGG19

    args = build_parser(description).parse_args(argv)
    if args.spmd_mode is not None:
        if sync != "auto":
            raise SystemExit(
                "error: --spmd-mode applies only to the Part 3 'auto' rung "
                "(the other Parts' sync strategies are explicit shard_map "
                "collectives by definition)")
        spmd_mode = args.spmd_mode
    if args.checkpoint_async and not args.checkpoint_dir:
        raise SystemExit(
            "error: --checkpoint-async requires --checkpoint-dir (nothing "
            "would be checkpointed otherwise)")
    if args.keep_checkpoints is not None and args.keep_checkpoints < 1:
        raise SystemExit(
            f"error: --keep-checkpoints must be >= 1 "
            f"(got {args.keep_checkpoints})")
    if args.keep_checkpoints and not args.checkpoint_dir:
        raise SystemExit(
            "error: --keep-checkpoints requires --checkpoint-dir")
    if args.sync_bn and (single_device or spmd_mode != "shard_map"):
        # Decidable from flags alone — fail before distributed init /
        # dataset load, next to the other pure-argument checks.
        raise SystemExit(
            "error: --sync-bn needs a shard_map rung (Parts 2a/2b) — the "
            "mesh axis is not bound in single-device or gspmd modes")
    if args.eval_only and not args.checkpoint_dir:
        raise SystemExit(
            "error: --eval-only requires --checkpoint-dir (there is no "
            "model to evaluate otherwise)")
    if args.resilience and not args.checkpoint_dir:
        raise SystemExit(
            "error: --resilience requires --checkpoint-dir (rollback and "
            "step recovery restore from the step_N series under it)")
    if (args.max_rollbacks is not None or args.spike_factor is not None) \
            and not args.resilience:
        raise SystemExit(
            "error: --max-rollbacks/--spike-factor configure the "
            "--resilience supervisor; pass --resilience too")
    if args.max_rollbacks is not None and args.max_rollbacks < 0:
        raise SystemExit(
            f"error: --max-rollbacks must be >= 0 (got {args.max_rollbacks})")
    if args.spike_factor is not None and args.spike_factor <= 1.0:
        raise SystemExit(
            f"error: --spike-factor must be > 1.0 (got {args.spike_factor}) "
            "— a window loss always 'exceeds' a sub-unit multiple of the "
            "median and every window would roll back")
    if args.platform:  # must precede the first device query
        jax.config.update("jax_platforms", args.platform)
    initialize_distributed(args.master, args.num_nodes, args.rank, PORT)
    # Persistent executable cache (see tpudp/utils/compile_cache.py): a
    # relaunched trainer skips the train-step compile after the first
    # successful run.  No-ops on the CPU backend (--platform cpu smoke
    # runs).  AFTER distributed init — the helper resolves the backend,
    # and jax.distributed.initialize must precede the first backend touch
    # on multi-host.
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    mesh = None if single_device else make_mesh(args.num_devices)
    world = 1 if mesh is None else mesh.size
    num_hosts = jax.process_count()
    host_id = jax.process_index()
    # --resilience runs multi-host too: the supervisor's recovery
    # decisions are COORDINATED (allgathered outcome votes, worst
    # severity wins; the verified-restore walk votes per step dir), so
    # every host resumes the same state — docs/RESILIENCE.md
    # "Multi-host recovery".

    if args.batch_size % world or args.batch_size % num_hosts:
        raise SystemExit(
            f"error: --batch-size {args.batch_size} must be divisible by the "
            f"device count ({world}) and host count ({num_hosts}) — "
            f"per-device batches need equal static shapes"
        )

    train_set, test_set, synthetic = load_cifar10(
        args.data_root,
        synthetic_train_size=args.synthetic_train_size,
        synthetic_test_size=args.synthetic_test_size,
    )
    if synthetic:
        print("[tpudp] CIFAR-10 not found on disk; using synthetic stand-in data")

    # Per-host batch: the reference computes per-rank batch = global/world
    # (src/Part 2a/main.py:22); here host-level sharding divides by process
    # count and the mesh sharding divides across local devices.
    host_batch = args.batch_size // num_hosts
    train_loader = DataLoader(
        train_set, host_batch,
        sampler=ShardedSampler(len(train_set.images), num_hosts, host_id,
                               shuffle=True, seed=args.seed),
        train=True, seed=args.seed, backend=args.data_backend,
    )
    test_loader = DataLoader(
        test_set, host_batch,
        sampler=ShardedSampler(len(test_set.images), num_hosts, host_id,
                               shuffle=False),
        train=False, backend=args.data_backend,
    )
    data_backend = train_loader.backend  # before any wrapper hides it
    if args.prefetch > 0:
        from tpudp.data.prefetch import Prefetcher

        train_loader = Prefetcher(train_loader, depth=args.prefetch)
        test_loader = Prefetcher(test_loader, depth=args.prefetch)

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    factory = {"vgg11": VGG11, "vgg13": VGG13, "vgg16": VGG16,
               "vgg19": VGG19}[args.model]
    model = factory(dtype=dtype,
                    bn_axis=DATA_AXIS if args.sync_bn else None)
    watchdog = None
    if args.step_timeout:
        from tpudp.utils.watchdog import Watchdog

        # Under --resilience the watchdog must NOT kill: the hang surfaces
        # as StepHangError at the next beat and the supervisor recovers
        # in-process (dump, restore, re-arm) instead of a full relaunch.
        outcome = ("recovering in-process" if args.resilience
                   else "exiting for scheduler restart")
        watchdog = Watchdog(
            timeout_s=args.step_timeout,
            kill=not args.resilience,
            on_hang=[lambda: print(
                f"[tpudp] FAILURE DETECTED: step exceeded "
                f"{args.step_timeout}s (wedged collective or dead peer); "
                f"{outcome}", flush=True)],
        ).start()
    trainer = Trainer(model, mesh, sync, seed=args.seed,
                      spmd_mode=spmd_mode, timing_mode=args.timing_mode,
                      watchdog=watchdog, grad_accum=args.grad_accum,
                      remat=args.remat, metrics_jsonl=args.metrics_jsonl,
                      verify_replicas=args.verify_replicas,
                      flight_dir=args.flight_dir)
    metrics_server = None
    if args.metrics_port is not None and jax.process_index() == 0:
        from tpudp.obs import MetricsServer

        metrics_server = MetricsServer(args.metrics_port, trainer.metrics)
        print(f"[tpudp] metrics endpoint: "
              f"http://127.0.0.1:{metrics_server.port}/metrics")
    print(f"[tpudp] model={args.model} sync={sync} devices={world} "
          f"hosts={num_hosts} "
          f"global_batch={args.batch_size} dtype={args.dtype} "
          f"data={data_backend}+prefetch{args.prefetch}")
    print(f"[tpudp] train samples={len(train_set.images)} "
          f"test samples={len(test_set.images)}")

    start_epoch = 0
    skip_first = 0  # mid-epoch fast-forward (emergency-dump resume)
    restored = False
    epoch_end_fn = None
    async_writer = None
    if args.checkpoint_dir:
        import os

        from tpudp.utils.checkpoint import (coordinated_any, emergency_dir,
                                            latest_step_dir,
                                            restore_latest_verified,
                                            save_checkpoint)

        # Entry into each collective restore protocol is itself a
        # collective decision (coordinated_any): a per-host listing probe
        # deciding entry would leave the host that sees a checkpoint
        # alone inside an allgather its stale-listing peer never joins.
        if coordinated_any(latest_step_dir(args.checkpoint_dir)
                           is not None):
            # Verified restore with fallback: a torn or bit-flipped newest
            # checkpoint (killed mid-save, disk rot) must never crash-loop
            # the resume — walk back to the newest intact step dir
            # (tpudp/utils/checkpoint.py::restore_latest_verified).
            # Multi-host, the walk is COORDINATED: hosts align on the
            # newest step every host sees, then vote per step dir
            # (unanimity) so every process resumes the SAME checkpoint —
            # a shard corrupt on one host rejects the dir for all, and
            # process 0 alone quarantines it.
            trainer.state, used, _skipped = restore_latest_verified(
                args.checkpoint_dir, trainer.state, log=print)
            start_epoch = int(used.rsplit("_", 1)[1])
            restored = True
            print(f"[tpudp] resumed from {used} (epoch {start_epoch})")
        # An emergency dump (watchdog-triggered, mid-epoch) is newer than any
        # epoch checkpoint: prefer its weights, then consume it so later
        # resumes fall back to the regular epoch series.
        emerg = emergency_dir(args.checkpoint_dir)
        if coordinated_any(emerg is not None) and emerg is None:
            # Stale listing on this host; the dump's location is fixed,
            # and the voted restore below decides its fate for all hosts.
            emerg = os.path.join(args.checkpoint_dir, "emergency")
        # tpudp: lint-ok(protocol-early-exit): `emerg` is host-uniform
        # by protocol at this point — coordinated_any above agreed on
        # whether a dump exists, and hosts with a stale listing were
        # fixed up to the shared dump path, so every host takes the
        # same arm here (the voted restore inside decides its fate).
        if emerg:
            # Refuse a mismatched relaunch BEFORE the dump is consumed:
            # the fast-forward below maps the optimizer-step counter onto
            # the loader's batch grid, which only works if this relaunch
            # has the same batches/epoch as the interrupted run (a changed
            # --batch-size or train-set size would silently re-train or
            # drop batches — round-3 advisor).  Old sentinels without the
            # field skip the check (nothing to compare against).
            from tpudp.utils.checkpoint import read_emergency_sentinel

            sent = read_emergency_sentinel(args.checkpoint_dir) or {}
            dumped_pe = sent.get("per_epoch_batches")
            if (not args.eval_only and dumped_pe is not None
                    and dumped_pe != len(train_loader)):
                # tpudp: lint-ok(protocol-early-exit): every host reads
                # the SAME sentinel file and computes the same loader
                # length from the same dataset/--batch-size, so a
                # batch-grid mismatch aborts the whole pod together —
                # no peer proceeds to the voted restore alone.
                raise SystemExit(
                    f"error: emergency dump at {emerg} was written with "
                    f"{dumped_pe} batches/epoch but this relaunch has "
                    f"{len(train_loader)} (different --batch-size or "
                    "train-set size) — the dump's step counter cannot be "
                    "mapped to a resume position on this batch grid. "
                    "Relaunch with the original configuration, or remove "
                    "the dump directory to restart the epoch from the "
                    "last step_N checkpoint.")
            # verify=True: the dump carries a checksum manifest (per-host
            # shard manifests on multi-host); a dump whose sentinel
            # committed but whose bytes rotted must fall back to the step
            # series, never crash-loop the resume.  Multi-host, the
            # accept/quarantine decision is UNANIMOUS: a shard corrupt on
            # one host rejects the dump for all, so no per-process
            # decision can leave hosts resuming different states
            # (tpudp/utils/checkpoint.py::restore_emergency_voted — the
            # same protocol auto_resume uses).
            from tpudp.utils.checkpoint import restore_emergency_voted

            dump_state = restore_emergency_voted(
                args.checkpoint_dir, emerg, trainer.state, log=print)
            if dump_state is not None:
                trainer.state = dump_state
            else:
                emerg = None
        # tpudp: lint-ok(protocol-early-exit): same justification as
        # the first `if emerg:` above — after the coordinated_any
        # fixup, emerg is None on every host or on none (and the voted
        # restore's outcome is collectively agreed), so all hosts take
        # the same arm into the consume barrier.
        if emerg:
            restored = True
            if args.eval_only:
                # Read-only use: evaluating the dump must not consume it —
                # the NEXT training restart still needs the mid-epoch state.
                print(f"[tpudp] evaluating emergency dump {emerg} "
                      "(left in place for the next training resume)")
            elif jax.process_count() > 1:
                # All processes must finish reading before rank 0 consumes
                # the directory.
                from jax.experimental import multihost_utils

                # tpudp: lint-ok(divergent-collective): the branch
                # condition is the OUTCOME of restore_emergency_voted —
                # a collectively-agreed value, identical on every host
                # by protocol, so all hosts take the same arm.
                multihost_utils.sync_global_devices("tpudp_emergency_restore")
            if not args.eval_only and jax.process_index() == 0:
                from tpudp.utils.checkpoint import consume_emergency

                consume_emergency(args.checkpoint_dir)
            if not args.eval_only:
                # Fast-forward instead of re-running the epoch head: the
                # dump's optimizer-step counter is one per loader batch
                # and the sampler order is deterministic per (seed,
                # epoch), so the counter alone fixes the resume position
                # — epoch = step // per_epoch, batches into it = step %
                # per_epoch.  Derived from the counter rather than the
                # step_N series on purpose: with --checkpoint-async the
                # dump can be AHEAD of the newest finalized epoch
                # checkpoint (the write was still in flight at the hang),
                # and anchoring on the stale series would silently
                # re-train the next epoch's head.  No batch is trained
                # twice, none is dropped.
                per_epoch = len(train_loader)
                start_epoch = int(trainer.state.step) // per_epoch
                skip_first = int(trainer.state.step) % per_epoch
                print(f"[tpudp] resumed mid-epoch state from emergency dump "
                      f"{emerg} (epoch {start_epoch}: fast-forwarding "
                      f"{skip_first}/{per_epoch} already-trained batches)")

        if args.checkpoint_async and not args.eval_only:
            # BEFORE the watchdog dump hook: the dump closure must drain
            # this writer's in-flight epoch-end save first — two orbax
            # writers interleaving in one root can tear both checkpoints.
            from tpudp.utils.checkpoint import AsyncCheckpointWriter

            async_writer = AsyncCheckpointWriter()

        if watchdog is not None and not args.resilience:
            # Failure recovery (VERDICT r1 #9): a detected hang dumps the
            # live TrainState before the process exits, so a wedged
            # collective loses at most the current epoch's progress since
            # the last completed step, not everything since the last epoch.
            # The closure (shared with the resilience supervisor's step
            # recovery) invalidates the previous dump's sentinel first,
            # waits out any overlapped async epoch-end write, saves, then
            # commits the sentinel only after orbax finalized.
            # NOT registered under --resilience: the supervisor dumps at
            # recovery time itself, and a second writer firing from the
            # watchdog thread into the same emergency root would race it
            # (two orbax writers in one root can tear both).
            from tpudp.resilience import make_emergency_dump

            _save = make_emergency_dump(
                args.checkpoint_dir, lambda: trainer.state,
                len(train_loader), async_writer=async_writer,
                log=lambda s: print(s, flush=True))

            def _emergency_dump() -> None:
                import threading

                # Bounded: saving fetches device buffers, and on a truly
                # wedged device that fetch can hang — the dump must never
                # stop the watchdog from killing the process.
                th = threading.Thread(target=_save, daemon=True)
                th.start()
                th.join(timeout=60.0)

            watchdog.on_hang.append(_emergency_dump)

        if watchdog is not None and args.resilience:
            # Hard-exit backstop: kill=False recovery only works for
            # stalls that RETURN (the StepHangError surfaces at the next
            # beat).  A truly wedged collective (dead peer) never returns
            # to a beat, so without this the process would hang forever —
            # strictly worse than the kill=True path it replaced.  If the
            # supervisor has not recovered (re-armed clears _hang_seen)
            # within a grace period, exit for the scheduler exactly like
            # the non-resilient watchdog.
            hang_gen = [0]  # per-hang generation: a stale backstop from
            # an already-recovered hang must not fire during a LATER
            # hang's still-in-grace recovery (that hang spawned its own
            # backstop with a fresh full grace period)

            def _hard_exit_backstop() -> None:
                import threading
                import time as _time

                hang_gen[0] += 1
                my_gen = hang_gen[0]

                def _backstop() -> None:
                    _time.sleep(max(args.step_timeout, 60.0))
                    if watchdog._hang_seen.is_set() and hang_gen[0] == my_gen:
                        print("[tpudp] hang NOT recovered in-process "
                              "(wedged collective?); exiting for "
                              "scheduler restart", flush=True)
                        os._exit(42)

                threading.Thread(target=_backstop, daemon=True).start()

            watchdog.on_hang.append(_hard_exit_backstop)

        def epoch_end_fn(epoch: int) -> None:
            path = os.path.join(args.checkpoint_dir, f"step_{epoch + 1}")
            if async_writer is not None:
                async_writer.save(path, trainer.state)
                print(f"[tpudp] checkpoint {path} writing in background")
            else:
                save_checkpoint(path, trainer.state)
                print(f"[tpudp] saved checkpoint {path}")
            if args.keep_checkpoints and jax.process_index() == 0:
                # By now the PREVIOUS step's write is durable (sync save, or
                # the async writer's serialized-saves guarantee), so pruning
                # older dirs always leaves a restorable latest checkpoint.
                from tpudp.utils.checkpoint import prune_step_dirs

                for gone in prune_step_dirs(args.checkpoint_dir,
                                            args.keep_checkpoints):
                    print(f"[tpudp] pruned old checkpoint {gone}")

    if args.eval_only:
        if not restored:
            raise SystemExit(
                f"error: --eval-only found no checkpoint under "
                f"{args.checkpoint_dir!r} — evaluating random weights "
                "would report meaningless metrics")
        from tpudp.utils.profiler import trace

        if watchdog is not None:
            watchdog.arm()  # fit() normally arms; eval-only must too
        try:
            with trace(args.profile_dir):
                trainer.evaluate(test_loader)
        finally:
            if watchdog is not None:
                watchdog.disarm()
                watchdog.stop()
        if args.profile_dir:
            print(f"[tpudp] profiler trace written to {args.profile_dir}")
        if metrics_server is not None:
            metrics_server.close()
        return trainer

    from tpudp.utils.profiler import trace

    resilience = None
    if args.resilience:
        from tpudp.resilience import ResiliencePolicy

        resilience = ResiliencePolicy(
            checkpoint_dir=args.checkpoint_dir,
            spike_factor=args.spike_factor,
            # epoch_end_fn above already saves step_{epoch+1} into the
            # same root; the supervisor must not double-write it.
            save_epoch_checkpoints=False,
            checkpoint_writer=async_writer,
            **({"max_rollbacks": args.max_rollbacks}
               if args.max_rollbacks is not None else {}),
        )

    try:
        with trace(args.profile_dir):
            trainer.fit(train_loader, test_loader, epochs=args.epochs,
                        start_epoch=start_epoch, epoch_end_fn=epoch_end_fn,
                        skip_batches_first_epoch=skip_first,
                        resilience=resilience)
    finally:
        if async_writer is not None:
            async_writer.close()  # join the last epoch's write
    if resilience is not None:
        s = trainer.stats
        print(f"[tpudp] resilience summary: {s.get('rollbacks', 0)} "
              f"rollbacks, {s.get('step_retries', 0)} step retries, "
              f"{s.get('ckpt_fallbacks', 0)} checkpoint fallbacks, "
              f"{s.get('loader_restarts', 0)} loader restarts")
    if watchdog is not None:
        watchdog.stop()
    if metrics_server is not None:
        metrics_server.close()
    if args.profile_dir:
        print(f"[tpudp] profiler trace written to {args.profile_dir}")
    return trainer
