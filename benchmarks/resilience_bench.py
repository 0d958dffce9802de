"""Kill/resume soak for the training resilience layer (tpudp/resilience.py).

The training-stack counterpart of ``serve_bench.py --soak``: a subprocess
trainer is driven through every failure mode the supervisor claims to
survive — injected NaN gradients, a finite loss spike, a raising train
step, a wedged (stalling) step under a kill=False watchdog, a dying
loader, SIGKILL at a random point, and a corrupted newest checkpoint
before a relaunch — with automatic relaunch until training completes.
The referee is merciless and binary:

  * the final parameters must be **bit-identical** to an uninterrupted
    run of the same configuration (every recovery path restores a
    checkpoint and deterministically replays, so recovery may cost wall
    time, never a different model), and
  * **every recovery is accounted** in the typed event log
    (``events.jsonl``, written by the supervisor's ``on_event`` hook and
    the relaunch resume): each injected fault kind must have a matching
    recovery event — rollback for NaN/spike, step_retry for raise/stall
    (``hang: true`` for the stall), loader_restart for the loader fault,
    ckpt_fallback for the corruption — and every SIGKILL a relaunch.

Chaos schedule per seed (deterministic; ``random.Random(seed)`` jitters
only WHERE within the launch each fault lands, never whether it fires):

  launch 1: loader fault + raising step in epoch 0; SIGKILLed shortly
            after the epoch-1 checkpoint lands
  (the newest step dir is then byte-flipped on disk)
  launch 2: resumes (falling back past the corrupt dir), NaN batch +
            stalling step; SIGKILLed after the epoch-2 checkpoint
  launch 3: resumes, loss spike in the final epoch, runs to completion

Emits one JSON row per seed (metric ``train_soak``) with the recovery
counts, ``parity_ok``, ``accounted``, and ``device_kind``; CPU smoke rows
are pinned by ``tests/test_bench_smoke.py``.

``--multihost`` runs the POD-SCALE variant instead (metric
``train_soak_multihost``, seeds via TRAIN_SOAK_MULTIHOST): each launch
is TRAIN_SOAK_HOSTS worker processes x TRAIN_SOAK_DEVICES_PER virtual
CPU devices under the COORDINATED supervisor (docs/RESILIENCE.md
"Multi-host recovery") — a NaN drives a voted all-host rollback, ONE
worker is SIGKILLed mid-epoch (the survivor must hard-exit via the
bounded vote instead of hanging), one host's checkpoint shard is
byte-flipped between relaunches (the per-host crc32 manifests must
reject the dir for ALL hosts), a stall exercises coordinated hang
recovery, and the final relaunch runs at a REDUCED host geometry
(elastic verified restore).  Same merciless referee: final params
bit-identical to an uninterrupted run, every fault accounted.

Env knobs: TRAIN_SOAK (comma seeds; default the mode's seeds below),
TRAIN_SOAK_PLATFORM (e.g. ``cpu``), TRAIN_SOAK_EPOCHS (3),
TRAIN_SOAK_PER_EPOCH (6 batches), TRAIN_SOAK_BATCH (8),
TRAIN_SOAK_KILLS (2), TRAIN_SOAK_WD_TIMEOUT (8s; the stall sleeps 1.75x
that), TRAIN_SOAK_LOG_EVERY (2); multihost adds TRAIN_SOAK_MULTIHOST
(seeds), TRAIN_SOAK_HOSTS (2), TRAIN_SOAK_DEVICES_PER (2),
TRAIN_SOAK_VOTE_TIMEOUT (30s).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The seeds each mode runs by default; a seed outside its mode's tuple is
# refused before anything runs.
TRAIN_SOAK_SEEDS = (0, 1, 2)
TRAIN_SOAK_MULTIHOST_SEEDS = (0, 1, 2)
SDC_SOAK_SEEDS = (0, 1, 2)


def _cfg() -> dict:
    return {
        "epochs": int(os.environ.get("TRAIN_SOAK_EPOCHS", 3)),
        "per_epoch": int(os.environ.get("TRAIN_SOAK_PER_EPOCH", 6)),
        "batch": int(os.environ.get("TRAIN_SOAK_BATCH", 8)),
        "kills": int(os.environ.get("TRAIN_SOAK_KILLS", 2)),
        "wd_timeout": float(os.environ.get("TRAIN_SOAK_WD_TIMEOUT", 8.0)),
        "log_every": int(os.environ.get("TRAIN_SOAK_LOG_EVERY", 2)),
        # Multi-host soak geometry: the pod runs TRAIN_SOAK_HOSTS OS
        # processes x TRAIN_SOAK_DEVICES_PER virtual CPU devices; the
        # reduced-geometry relaunch and the uninterrupted reference run
        # 1 process x (hosts * devices_per) devices — same global mesh,
        # fewer hosts, which the geometry-invariant config below keeps
        # bit-identical.
        "hosts": int(os.environ.get("TRAIN_SOAK_HOSTS", 2)),
        "devices_per": int(os.environ.get("TRAIN_SOAK_DEVICES_PER", 2)),
        "vote_timeout": float(os.environ.get("TRAIN_SOAK_VOTE_TIMEOUT",
                                             30.0)),
    }


# ---------------------------------------------------------------------------
# Worker: one trainer process (launched with --worker; config via env)
# ---------------------------------------------------------------------------

def _worker() -> int:
    # Pod mode (the multi-host soak): TRAIN_SOAK_NPROC names the host
    # count of THIS launch (1 = the reduced-geometry / reference shape).
    # Geometry env must land before the first backend touch.
    nproc = int(os.environ.get("TRAIN_SOAK_NPROC", 0))
    rank = int(os.environ.get("TRAIN_SOAK_RANK", 0))
    devices = int(os.environ.get("TRAIN_SOAK_DEVICES", 0))
    if devices:
        # --xla_cpu_multi_thread_eigen=false: Eigen's intra-op thread
        # pool splits conv/matmul reductions by the PER-PROCESS device
        # budget, so a 2-host x D and 1-host x 2D pod accumulate in
        # different orders (~1 ulp/step — measured) and the elastic
        # bit-exactness oracle would fail for reasons that have nothing
        # to do with recovery.  Single-threaded Eigen pins the reduction
        # order; CPU-smoke-only (a real TPU pod never sets
        # TRAIN_SOAK_DEVICES).
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices} "
            "--xla_cpu_multi_thread_eigen=false")
    if os.environ.get("TRAIN_SOAK_PLATFORM"):
        import jax

        jax.config.update("jax_platforms",
                          os.environ["TRAIN_SOAK_PLATFORM"])
    if nproc > 1:
        from tpudp.mesh import initialize_distributed

        initialize_distributed("127.0.0.1", nproc, rank,
                               port=int(os.environ["TRAIN_SOAK_PORT"]))
        # First collective of the pod, ALONE: establishes every gloo TCP
        # pair with one lone symmetric op before real work dispatches
        # possibly-concurrent, differently-sized collectives — racing
        # two fresh ops on a just-built pair intermittently dies with a
        # gloo preamble-size mismatch (observed ~1/10 launches at the
        # 2-proc CPU smoke geometry, always before the first event).
        from jax.experimental import multihost_utils

        # tpudp: lint-ok(divergent-collective): nproc comes from
        # TRAIN_SOAK_NPROC, which _launch_pod sets IDENTICALLY for every
        # worker it spawns — the condition is host-uniform by
        # construction, and this barrier exists precisely to serialize
        # the pod's first rendezvous.
        multihost_utils.sync_global_devices("tpudp_pod_startup")
    import flax.linen as nn
    import jax
    import numpy as np

    from tpudp.data.cifar10 import _synthetic
    from tpudp.data.loader import DataLoader
    from tpudp.data.prefetch import Prefetcher
    from tpudp.resilience import ResiliencePolicy, auto_resume
    from tpudp.train import Trainer
    from tpudp.training_faults import (CorruptingLoader, RaisingLoader,
                                       RaisingStep, StallingStep)
    from tpudp.utils.watchdog import Watchdog

    cfg = _cfg()
    outdir = os.environ["TRAIN_SOAK_OUT"]
    ckpt = os.path.join(outdir, "ckpt")
    # One event log per host; the referee reads rank 0's (recovery
    # decisions are coordinated, so rank 0's log accounts the pod).
    events_path = os.path.join(
        outdir, "events.jsonl" if rank == 0 else f"events.rank{rank}.jsonl")

    def emit(ev: dict) -> None:
        with open(events_path, "a") as f:
            f.write(json.dumps(ev) + "\n")

    def _idx(name):
        v = os.environ.get(name, "")
        return {int(x) for x in v.split(",") if x}

    class SoakNet(nn.Module):
        """Tiny BN-free conv net: trajectories are invariant to device
        placement and the compile stays in single-digit seconds."""

        @nn.compact
        def __call__(self, x, train=False):
            x = nn.relu(nn.Conv(4, (3, 3), padding=1)(x))
            x = nn.avg_pool(x, (8, 8), strides=(8, 8))
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(x)

    ds = _synthetic(cfg["per_epoch"] * cfg["batch"], seed=17)
    if nproc:
        # Pod mode must be GEOMETRY-INVARIANT so the kill-one-host story
        # can relaunch smaller and still bit-match the reference: the
        # batch-contiguous sampler keeps each assembled global batch a
        # pure function of (seed, epoch) regardless of host count, and
        # train=False drops augmentation (its host-local RNG stream
        # would differ by geometry).  The mesh'd trainer below completes
        # the invariance with the gather-based 'coordinator' sync.
        from tpudp.data.sampler import ShardedSampler

        loader = DataLoader(
            ds, cfg["batch"] // nproc,
            sampler=ShardedSampler(len(ds.images), nproc, rank,
                                   shuffle=True, seed=5,
                                   batch_contiguous=cfg["batch"]),
            train=False, backend="numpy")
    else:
        loader = DataLoader(ds, cfg["batch"], train=True, seed=5,
                            backend="numpy")
    nan_at, spike_at = _idx("TRAIN_SOAK_NAN_AT"), _idx("TRAIN_SOAK_SPIKE_AT")
    loader_at = _idx("TRAIN_SOAK_LOADER_AT")
    if nan_at or spike_at:
        loader = CorruptingLoader(loader, nan_at=nan_at, spike_at=spike_at,
                                  spike_scale=30.0)
    if loader_at:
        loader = RaisingLoader(loader, fail_at=loader_at)
    prefetch = Prefetcher(loader, depth=2)

    raise_at, stall_at = _idx("TRAIN_SOAK_RAISE_AT"), _idx("TRAIN_SOAK_STALL_AT")
    raiser = RaisingStep(fail_at=raise_at)
    staller = StallingStep(stall_at, delay_s=1.75 * cfg["wd_timeout"])
    # Per-step pacing (sleep only — the math is untouched): the harness's
    # SIGKILL lands a grace interval after a checkpoint appears, and the
    # post-compile epochs of this tiny net are otherwise fast enough for
    # a launch to FINISH inside that grace, dodging its kill.  0 on real
    # hardware where steps have honest duration.
    pace = float(os.environ.get("TRAIN_SOAK_PACE_S", 0.08))
    import time as _time

    def hook(kind, index):
        if pace:
            _time.sleep(pace)
        staller(kind, index)
        raiser(kind, index)

    watchdog = Watchdog(timeout_s=cfg["wd_timeout"], kill=False,
                        poll_s=0.2).start() if stall_at else None

    if nproc:
        from tpudp.mesh import make_mesh

        # 'coordinator' sync (all-gather -> local mean) is the
        # geometry-invariant reduction: no cross-device arithmetic in
        # flight, so a 2-host x D and 1-host x 2D mesh produce
        # bit-identical updates (psum's reduction order is not).
        trainer = Trainer(SoakNet(), make_mesh(), "coordinator",
                          log_every=cfg["log_every"], log_fn=lambda s: None,
                          watchdog=watchdog, step_fault_hook=hook)
    else:
        trainer = Trainer(SoakNet(), None, "none", spmd_mode="single",
                          log_every=cfg["log_every"], log_fn=lambda s: None,
                          watchdog=watchdog, step_fault_hook=hook)
    os.makedirs(ckpt, exist_ok=True)
    start_epoch, skip = auto_resume(trainer, ckpt, cfg["per_epoch"],
                                    log=lambda s: None, on_event=emit)
    emit({"kind": "relaunch_resume", "epoch": start_epoch, "skip": skip,
          "nproc": nproc or 1})
    policy = ResiliencePolicy(checkpoint_dir=ckpt, spike_factor=3.0,
                              spike_min_history=1, on_event=emit,
                              vote_timeout_s=cfg["vote_timeout"])

    def epoch_end(epoch: int) -> None:
        # The harness's kill marker: one line per epoch THIS launch
        # completed (the supervisor saves step_{epoch+1} right after this
        # fn returns; the harness's kill grace covers that write), so
        # SIGKILLs land after the launch's first full epoch — after its
        # in-process faults have fired and recovered — never during
        # startup.  Rank 0 only: one marker per pod.
        if rank == 0:
            with open(os.path.join(outdir, "epoch_end.marker"), "a") as f:
                f.write(f"{epoch}\n")

    trainer.fit(prefetch, epochs=cfg["epochs"], start_epoch=start_epoch,
                skip_batches_first_epoch=skip, epoch_end_fn=epoch_end,
                resilience=policy)
    prefetch.close()
    if watchdog is not None:
        watchdog.stop()

    if rank == 0:
        # Replicated params: rank 0's bytes are the pod's bytes (the
        # supervisor asserted the cross-host fingerprint after every
        # coordinated restore).
        flat = np.concatenate([np.asarray(leaf).ravel()
                               for leaf in jax.tree.leaves(
                                   trainer.state.params)])
        np.save(os.path.join(outdir, "params.npy"), flat)
        with open(os.path.join(outdir, "done.json"), "w") as f:
            json.dump({"device_kind": jax.devices()[0].device_kind,
                       "steps": int(trainer.state.step),
                       "nproc": nproc or 1,
                       "stats": {k: v for k, v in trainer.stats.items()
                                 if k != "events"}}, f)
    if nproc > 1:
        jax.distributed.shutdown()
    return 0


# ---------------------------------------------------------------------------
# Harness: reference run + chaos run + parity/accounting referee
# ---------------------------------------------------------------------------

def _launch(outdir: str, faults: dict[str, str]) -> subprocess.Popen:
    env = dict(os.environ)
    env["TRAIN_SOAK_OUT"] = outdir
    # Flight recorder (tpudp.obs): every worker banks its span/event
    # ring into flightrec-*.json on rollbacks/hangs/vote timeouts, so a
    # soak kill always leaves a readable black box next to the event
    # log.  Same dir for every relaunch of one soak — the dumps narrate
    # the whole chaos schedule.
    env.setdefault("TPUDP_FLIGHT_DIR", os.path.join(outdir, "flightrec"))
    for k in ("TRAIN_SOAK_NAN_AT", "TRAIN_SOAK_SPIKE_AT",
              "TRAIN_SOAK_RAISE_AT", "TRAIN_SOAK_STALL_AT",
              "TRAIN_SOAK_LOADER_AT"):
        env.pop(k, None)
    env.update(faults)
    # stderr to a file, never a pipe: nobody drains a pipe while the
    # worker runs, and libtpu/jax chatter past the ~64KB pipe buffer
    # would block the worker mid-write (a fake "wedge").  Truncated per
    # launch; _stderr_tail reads it on failure.
    with open(os.path.join(outdir, "worker.err"), "wb") as errf:
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            env=env, cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=errf)


def _stderr_tail(outdir: str, n: int = 400) -> str:
    try:
        with open(os.path.join(outdir, "worker.err"), "rb") as f:
            return f.read().decode(errors="replace")[-n:]
    except OSError:
        return ""


def _wait_for(predicate, proc: subprocess.Popen, timeout_s: float) -> bool:
    """Poll until ``predicate()`` or the worker exits; True if it fired
    while the worker was still alive."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return proc.poll() is None
        if proc.poll() is not None:
            return False
        time.sleep(0.05)
    return False


def _kill_after_first_epoch(proc: subprocess.Popen, outdir: str,
                            marker_len0: int, timeout_s: float) -> bool:
    """SIGKILL the worker shortly after THIS launch completes its first
    full epoch (the worker appends one line to ``epoch_end.marker`` per
    epoch end) — by then the launch's in-process faults have fired and
    recovered, and its epoch checkpoint is landing.  Keying on the
    launch's own progress (marker growth past ``marker_len0``) rather
    than on checkpoint files keeps pre-existing checkpoints from an
    earlier launch from arming the kill during startup.  Returns whether
    the kill was delivered (the worker may legitimately win the race)."""
    marker = os.path.join(outdir, "epoch_end.marker")

    def grew() -> bool:
        try:
            return os.path.getsize(marker) > marker_len0
        except OSError:
            return False

    if _wait_for(grew, proc, timeout_s):
        time.sleep(0.4)  # past the epoch-end save, into the next epoch
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait()
            return True
    proc.wait()
    return False


def _marker_len(outdir: str) -> int:
    try:
        return os.path.getsize(os.path.join(outdir, "epoch_end.marker"))
    except OSError:
        return 0


def _events(outdir: str) -> list[dict]:
    path = os.path.join(outdir, "events.jsonl")
    if not os.path.exists(path):
        return []
    out = []
    for line in open(path):
        line = line.strip()
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch_pod(outdir: str, faults: dict[str, str], nproc: int,
                devices_per: int) -> list[subprocess.Popen]:
    """Launch one pod: ``nproc`` worker processes (rank K's stderr to
    ``worker.r<K>.err``) that rendezvous over a fresh localhost port; a
    single-process pod (the reference / reduced-geometry shape) skips
    the rendezvous but keeps the mesh'd geometry-invariant config."""
    env = dict(os.environ)
    env["TRAIN_SOAK_OUT"] = outdir
    # Per-host flight-recorder dumps (tpudp.obs): the killed-host story
    # — a SIGKILLed worker cannot dump, but its SURVIVORS do (vote
    # timeout / coordinated recovery), and rank 0 merges after each
    # coordinated recovery, so every kill in the schedule leaves a
    # timeline naming the failing region.
    env.setdefault("TPUDP_FLIGHT_DIR", os.path.join(outdir, "flightrec"))
    for k in ("TRAIN_SOAK_NAN_AT", "TRAIN_SOAK_SPIKE_AT",
              "TRAIN_SOAK_RAISE_AT", "TRAIN_SOAK_STALL_AT",
              "TRAIN_SOAK_LOADER_AT"):
        env.pop(k, None)
    env.pop("XLA_FLAGS", None)  # workers pin their own device count
    # Pod workers always run the CPU backend: they are N co-located OS
    # processes, and two processes cannot share one host's libtpu — on a
    # TPU VM the second worker would fail to acquire the chips and the
    # stage could never pass.  The pod soak proves the COORDINATION
    # protocol (votes, two-phase commit, elastic restore), which is
    # platform-independent; real multi-VM TPU pods are launched by a
    # scheduler, not this script.
    env.setdefault("TRAIN_SOAK_PLATFORM", "cpu")
    env.update(faults)
    env["TRAIN_SOAK_NPROC"] = str(nproc)
    env["TRAIN_SOAK_DEVICES"] = str(devices_per)
    env["TRAIN_SOAK_PORT"] = str(_free_port())
    procs = []
    for r in range(nproc):
        renv = dict(env)
        renv["TRAIN_SOAK_RANK"] = str(r)
        with open(os.path.join(outdir, f"worker.r{r}.err"), "wb") as errf:
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--worker"],
                env=renv, cwd=REPO,
                stdout=subprocess.DEVNULL, stderr=errf))
    return procs


def _pod_stderr_tail(outdir: str, nproc: int, n: int = 500) -> str:
    parts = []
    for r in range(nproc):
        try:
            with open(os.path.join(outdir, f"worker.r{r}.err"), "rb") as f:
                parts.append(f"r{r}: "
                             + f.read().decode(errors="replace")[-n:])
        except OSError:
            pass
    return " | ".join(parts)


def _reap_pod(procs: list[subprocess.Popen], grace_s: float) -> list[int]:
    """Wait up to ``grace_s`` for every worker to exit, then SIGKILL the
    stragglers (a host wedged in a collective whose peer died — the
    scheduler-reap analogue).  Returns the return codes."""
    deadline = time.monotonic() + grace_s
    for p in procs:
        try:
            p.wait(timeout=max(deadline - time.monotonic(), 0.1))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
    return [p.returncode for p in procs]


def run_soak_multihost(seed: int, workdir: str) -> dict:
    """The pod-scale kill/resume soak (docs/RESILIENCE.md "Multi-host
    recovery").  One seed's schedule:

      launch 1 (H hosts): NaN batch in epoch 0 — the pmean'd loss makes
              every host see it, the vote agrees on DIVERGENCE, and all
              hosts roll back together; SIGKILL ONE worker after the
              epoch-1 checkpoint lands.  The survivor must NOT hang: its
              next collective (or recovery vote) fails against the dead
              peer and it hard-exits for relaunch.
      (one host's shard of the newest checkpoint is byte-flipped)
      launch 2 (H hosts, SAME geometry): the coordinated resume must
              reject the flipped dir for ALL hosts and fall back; a
              stalling step under the kill=False watchdog then exercises
              coordinated hang recovery; SIGKILL a different worker.
      launch 3 (1 host, REDUCED geometry): elastic verified restore of
              the H-host checkpoint, a loss spike in-process, runs to
              completion.

    Passes only if the final params are BIT-IDENTICAL to an
    uninterrupted single-launch run and every fault kind is accounted
    in rank 0's event log."""
    cfg = _cfg()
    rng = random.Random(seed * 6007 + 29)
    per, total_s = cfg["per_epoch"], 900.0
    hosts, devices_per = cfg["hosts"], cfg["devices_per"]
    all_devices = hosts * devices_per
    ref_dir = os.path.join(workdir, f"mh_ref_{seed}")
    chaos_dir = os.path.join(workdir, f"mh_chaos_{seed}")
    os.makedirs(ref_dir, exist_ok=True)
    os.makedirs(chaos_dir, exist_ok=True)

    # Uninterrupted oracle: the reduced geometry (1 process, full mesh).
    rcs = _reap_pod(_launch_pod(ref_dir, {}, 1, all_devices), total_s)
    if rcs != [0]:
        return {"seed": seed, "error": "reference run failed: "
                + _pod_stderr_tail(ref_dir, 1)}

    ckpt = os.path.join(chaos_dir, "ckpt")
    kills = 0
    survivor_exits = []

    # Launch 1: NaN early in epoch 0 (coordinated rollback), then kill
    # worker 1 after the first epoch checkpoint of this launch commits.
    # Launch 2: stall mid-way through the launch's FIRST epoch (device
    # calls restart at 1 per process, so index 2..per-1 always lands
    # before the epoch-end marker arms the kill — the hang recovery has
    # completed by the time the SIGKILL can fire), then kill worker 0 —
    # the coordinator this time, so both orphan-directions are covered.
    schedules = [
        ({"TRAIN_SOAK_NAN_AT": str(rng.randrange(1, per - 1))}, 1),
        ({"TRAIN_SOAK_STALL_AT": str(rng.randrange(2, per))}, 0),
    ]
    for i, (faults, victim) in enumerate(schedules):
        # The kill trigger is "a NEW committed step_N (N >= 1) landed
        # since this launch started" — NOT the epoch-end marker alone:
        # the marker can grow before the epoch's checkpoint finishes its
        # commit barrier, and a kill in that window can leave the series
        # at step_0 only (the reduced-geometry phase would then resume
        # from scratch — bit-exact, but proving nothing about elastic
        # restore).  Keying on the commit marker's mtime guarantees a
        # multi-host-saved checkpoint >= step_1 survives every launch,
        # so launch 3 ALWAYS has one to restore elastically (the launch's
        # in-process faults have fired and recovered by then too — the
        # first epoch checkpoint commits after the first full epoch).
        from tpudp.utils.checkpoint import (commit_marker_path,
                                            step_dirs_newest_first)

        start_ns = time.time_ns()
        procs = _launch_pod(chaos_dir, faults, hosts, devices_per)

        def grew() -> bool:
            for d in step_dirs_newest_first(ckpt):
                if int(os.path.basename(d).rsplit("_", 1)[1]) < 1:
                    continue
                try:
                    if os.stat(commit_marker_path(d)).st_mtime_ns > start_ns:
                        return True
                except OSError:
                    continue
            return False

        if _wait_for(grew, procs[victim], total_s):
            time.sleep(0.4)  # past the epoch-end save, into the epoch
            if procs[victim].poll() is None:
                procs[victim].send_signal(signal.SIGKILL)
                kills += 1
        rcs = _reap_pod(procs, grace_s=3 * cfg["vote_timeout"])
        survivor_exits.append([rc for r, rc in enumerate(rcs)
                               if r != victim])
        if kills != i + 1:
            return {"seed": seed, "error":
                    f"pod launch {i + 1} finished before its kill "
                    f"(rcs={rcs}): " + _pod_stderr_tail(chaos_dir, hosts)}
        if i == 0:
            # Byte-flip one host's shard payload of the newest COMMITTED
            # checkpoint (never the only one — the walk's all-corrupt
            # refusal would rightly abort the soak).
            from tpudp.utils.checkpoint import (is_committed,
                                                step_dirs_newest_first)

            committed = [d for d in step_dirs_newest_first(ckpt)
                         if is_committed(d)]
            if len(committed) >= 2:
                from tpudp.training_faults import corrupt_checkpoint

                corrupt_checkpoint(committed[0], mode="flip_shard")

    # Relaunch at the REDUCED geometry until done: elastic verified
    # restore of the 2-host series on 1 host, spike in the first resumed
    # epoch, fault-free after that.
    final_faults = {"TRAIN_SOAK_SPIKE_AT": str(rng.randrange(2, per - 1))}
    relaunches = 0
    while not os.path.exists(os.path.join(chaos_dir, "done.json")):
        relaunches += 1
        if relaunches > 6:
            return {"seed": seed, "error": "multihost soak did not "
                    "converge in 6 reduced-geometry relaunches"}
        rcs = _reap_pod(_launch_pod(
            chaos_dir, final_faults if relaunches == 1 else {},
            1, all_devices), total_s)
        if rcs != [0]:
            return {"seed": seed, "error":
                    f"reduced-geometry launch rc={rcs}: "
                    + _pod_stderr_tail(chaos_dir, 1)}

    # Referee: bit-exact parity + typed-event accounting (rank 0's log —
    # recovery decisions are coordinated, so it accounts the pod).
    ref_params = open(os.path.join(ref_dir, "params.npy"), "rb").read()
    chaos_params = open(os.path.join(chaos_dir, "params.npy"), "rb").read()
    parity_ok = ref_params == chaos_params
    events = _events(chaos_dir)
    counts = {}
    for e in events:
        counts[e["kind"]] = counts.get(e["kind"], 0) + 1
    nan_rollbacks = sum(1 for e in events if e["kind"] == "rollback"
                        and "FloatingPointError" in e.get("error", ""))
    spike_rollbacks = sum(1 for e in events if e["kind"] == "loss_spike")
    hang_retries = sum(1 for e in events
                       if e["kind"] == "step_retry" and e.get("hang"))
    coordinated = sum(1 for e in events if e.get("coordinated"))
    resumes = [e for e in events if e["kind"] == "relaunch_resume"]
    elastic = [e for e in resumes
               if e.get("nproc") == 1 and (e["epoch"] > 0 or e["skip"] > 0)]
    done = json.load(open(os.path.join(chaos_dir, "done.json")))
    accounted = (nan_rollbacks >= 1            # coordinated NaN rollback
                 and hang_retries >= 1         # coordinated hang recovery
                 and spike_rollbacks >= 1      # reduced-geometry spike
                 and counts.get("ckpt_fallback", 0) >= 1  # the shard flip
                 and coordinated >= 2
                 and kills == 2
                 and len(elastic) >= 1         # 2-host ckpt resumed at 1
                 and len(resumes) >= kills + 1)
    recoveries = (counts.get("rollback", 0) + counts.get("step_retry", 0)
                  + counts.get("ckpt_fallback", 0)
                  + counts.get("loader_restart", 0) + kills)
    return {
        "metric": "train_soak_multihost", "seed": seed, "value": recoveries,
        "unit": "recoveries", "parity_ok": parity_ok,
        "accounted": accounted, "kills": kills,
        "hosts": hosts, "devices_per_host": devices_per,
        "relaunches": len(resumes), "elastic_resumes": len(elastic),
        "survivor_exits": survivor_exits,
        "rollbacks": counts.get("rollback", 0),
        "nan_rollbacks": nan_rollbacks, "spike_rollbacks": spike_rollbacks,
        "step_retries": counts.get("step_retry", 0),
        "hang_retries": hang_retries,
        "coordinated_recoveries": coordinated,
        "ckpt_fallbacks": counts.get("ckpt_fallback", 0),
        "vote_timeouts": counts.get("vote_timeout", 0),
        "steps": done.get("steps"),
        "epochs": cfg["epochs"], "per_epoch": per, "batch": cfg["batch"],
        "device_kind": done.get("device_kind"),
        "measured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
    }


def run_soak(seed: int, workdir: str) -> dict:
    cfg = _cfg()
    rng = random.Random(seed * 7919 + 13)
    per, total_s = cfg["per_epoch"], 600.0
    ref_dir = os.path.join(workdir, f"ref_{seed}")
    chaos_dir = os.path.join(workdir, f"chaos_{seed}")
    os.makedirs(ref_dir, exist_ok=True)
    os.makedirs(chaos_dir, exist_ok=True)

    # Uninterrupted oracle.
    proc = _launch(ref_dir, {})
    proc.wait(timeout=total_s)
    if proc.returncode != 0:
        return {"seed": seed, "error": "reference run failed: "
                + _stderr_tail(ref_dir)}

    ckpt = os.path.join(chaos_dir, "ckpt")
    kills = 0
    launches = []
    want_kills = cfg["kills"]

    # Launch 1: loader fault + raising step in its first epoch; killed
    # after its first epoch checkpoint lands.  The raise is pinned at
    # least two calls past the loader draw: the loader fault travels
    # through the Prefetcher's queue and must SURFACE (at the consumer's
    # draw) before the step raise abandons the iteration, or the queued
    # fault dies with the abandoned worker and never gets its recovery.
    loader_at = rng.randrange(1, per - 3)
    launches.append({
        "TRAIN_SOAK_LOADER_AT": str(loader_at),
        "TRAIN_SOAK_RAISE_AT": str(loader_at + 2 + rng.randrange(0, 2)),
    })
    # Launch 2: NaN batch early in its first (resumed) epoch + a stalling
    # step; killed after its first epoch checkpoint.  The stall index is
    # pinned to per+1..per+2: the guaranteed NaN rollback replays the
    # whole epoch, so at least per+2 device calls dispatch BEFORE that
    # epoch's checkpoint — the stall always fires (and its hang recovery
    # completes) before the kill marker can arm.
    launches.append({
        "TRAIN_SOAK_NAN_AT": str(rng.randrange(1, per - 1)),
        "TRAIN_SOAK_STALL_AT": str(per + 1 + rng.randrange(0, 2)),
    })
    # Final launch: loss spike in its first resumed epoch; runs to
    # completion.
    final_faults = {"TRAIN_SOAK_SPIKE_AT": str(rng.randrange(2, per - 1))}

    corrupted = 0
    for i, faults in enumerate(launches[:want_kills]):
        len0 = _marker_len(chaos_dir)
        proc = _launch(chaos_dir, faults)
        if _kill_after_first_epoch(proc, chaos_dir, len0, total_s):
            kills += 1
        elif proc.returncode not in (0, -signal.SIGKILL):
            return {"seed": seed, "error":
                    f"chaos launch {i + 1} died rc={proc.returncode}: "
                    + _stderr_tail(chaos_dir)}
        if i == 0:
            # Corrupt the newest VERIFIED checkpoint before the relaunch:
            # the next resume must fall back to the previous intact step
            # dir.  Never corrupt the only verified checkpoint — the
            # fallback contract (refuse to silently restart from scratch)
            # would correctly abort the whole soak.
            from tpudp.utils.checkpoint import step_dirs_newest_first

            verified = [d for d in step_dirs_newest_first(ckpt)
                        if os.path.exists(d + ".manifest.json")]
            if len(verified) >= 2:
                from tpudp.training_faults import corrupt_checkpoint

                corrupt_checkpoint(verified[0], mode="flip")
                corrupted += 1
    # Relaunch until done (the final launch carries the spike fault; any
    # further relaunches — e.g. the spike landed before a kill — are
    # fault-free).
    relaunches = 0
    while not os.path.exists(os.path.join(chaos_dir, "done.json")):
        relaunches += 1
        if relaunches > 6:
            return {"seed": seed, "error": "soak did not converge in 6 "
                    "relaunches"}
        proc = _launch(chaos_dir, final_faults if relaunches == 1 else {})
        try:
            proc.wait(timeout=total_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            return {"seed": seed, "error": "final launch timed out"}
        if proc.returncode != 0:
            return {"seed": seed, "error":
                    f"final launch rc={proc.returncode}: "
                    + _stderr_tail(chaos_dir)}

    # Referee: bit-exact parity + typed-event accounting.
    ref_params = open(os.path.join(ref_dir, "params.npy"), "rb").read()
    chaos_params = open(os.path.join(chaos_dir, "params.npy"), "rb").read()
    parity_ok = ref_params == chaos_params
    events = _events(chaos_dir)
    counts = {}
    for e in events:
        counts[e["kind"]] = counts.get(e["kind"], 0) + 1
    hang_retries = sum(1 for e in events
                       if e["kind"] == "step_retry" and e.get("hang"))
    raise_retries = sum(1 for e in events
                        if e["kind"] == "step_retry" and not e.get("hang"))
    spike_rollbacks = sum(1 for e in events if e["kind"] == "loss_spike")
    nan_rollbacks = sum(1 for e in events if e["kind"] == "rollback"
                        and "FloatingPointError" in e.get("error", ""))
    resumes = counts.get("relaunch_resume", 0)
    # Accounting adapts to the PLANNED chaos: with TRAIN_SOAK_KILLS < 2
    # only the launches that ran injected their fault kinds (launch 2
    # carries NaN + stall), so only those owe a recovery.  The TPU stage
    # and the slow-tier test run the full 2-kill menu.
    ran = launches[:want_kills]
    planned_nan = any("TRAIN_SOAK_NAN_AT" in f for f in ran)
    planned_stall = any("TRAIN_SOAK_STALL_AT" in f for f in ran)
    planned_loader = any("TRAIN_SOAK_LOADER_AT" in f for f in ran)
    planned_raise = any("TRAIN_SOAK_RAISE_AT" in f for f in ran)
    accounted = (counts.get("loader_restart", 0) >= int(planned_loader)
                 and raise_retries >= int(planned_raise)
                 and hang_retries >= int(planned_stall)
                 and nan_rollbacks >= int(planned_nan)
                 and spike_rollbacks >= 1
                 and counts.get("ckpt_fallback", 0) >= corrupted
                 and (corrupted >= 1) == (want_kills >= 1)
                 and kills == want_kills
                 and resumes >= kills + 1)
    done = json.load(open(os.path.join(chaos_dir, "done.json")))
    recoveries = (counts.get("rollback", 0) + counts.get("step_retry", 0)
                  + counts.get("ckpt_fallback", 0)
                  + counts.get("loader_restart", 0) + kills)
    return {
        "metric": "train_soak", "seed": seed, "value": recoveries,
        "unit": "recoveries", "parity_ok": parity_ok,
        "accounted": accounted, "kills": kills, "relaunches": resumes,
        "corrupted_checkpoints": corrupted,
        "rollbacks": counts.get("rollback", 0),
        "nan_rollbacks": nan_rollbacks, "spike_rollbacks": spike_rollbacks,
        "step_retries": counts.get("step_retry", 0),
        "hang_retries": hang_retries,
        "ckpt_fallbacks": counts.get("ckpt_fallback", 0),
        "loader_restarts": counts.get("loader_restart", 0),
        "steps": done.get("steps"),
        "epochs": cfg["epochs"], "per_epoch": per, "batch": cfg["batch"],
        "device_kind": done.get("device_kind"),
        "measured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
    }


def run_sdc_soak(seed: int, workdir: str) -> dict:
    """Silent-corruption soak (metric ``sdc_soak``): three IN-PROCESS
    fits over the same data grid — the SDC response never kills the
    process, so no subprocess choreography is needed.

      1. clean: fingerprint checks on, NO injected faults — the
         false-positive gate (``clean_ok``: checks ran, zero
         detections);
      2. transient: a one-shot ``BitFlipParams`` flips one bit on one
         replica at a seed-chosen step — the vote must LOCALIZE that
         replica, grade it transient (the deterministic re-execution is
         clean), and the final params must be **bit-identical** to the
         clean run (``parity_ok``);
      3. persistent: ``BitFlipParams(persist_from=...)`` re-corrupts on
         every call — the supervisor must raise ``SdcPersistentError``
         and drop the quarantine marker (``quarantine_ok``).

    The flip site (step, replica, bit) is seed-jittered but always a
    low mantissa bit: the checksum is a bitcast sum, so ANY flipped bit
    trips it — the jitter varies WHERE, never WHETHER.
    """
    rng = random.Random(seed * 6007 + 11)
    if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ \
            and os.environ.get("JAX_PLATFORMS", "") == "cpu":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np

    if len(jax.devices()) < 2:
        return {"seed": seed, "error":
                "sdc soak needs >=2 devices for a replica vote (CPU "
                "smoke: JAX_PLATFORMS=cpu + "
                "XLA_FLAGS=--xla_force_host_platform_device_count=4)"}
    from tests.small_model import SmallConv
    from tpudp.data.cifar10 import _synthetic
    from tpudp.data.loader import DataLoader
    from tpudp.mesh import make_mesh
    from tpudp.resilience import ResiliencePolicy
    from tpudp.sdc import QUARANTINE_MARKER, BitFlipParams, SdcPersistentError
    from tpudp.train import Trainer

    def loader():
        ds = _synthetic(64, seed=3)
        return DataLoader(ds, 16, train=True, seed=2, backend="numpy")

    def trainer(hook=None):
        return Trainer(SmallConv(), make_mesh(), log_every=2,
                       log_fn=lambda s: None, track_sdc_fingerprint=True,
                       sdc_fault_hook=hook)

    def params_bytes(tr):
        return b"".join(np.asarray(x).tobytes()
                        for x in jax.tree_util.tree_leaves(tr.state.params))

    def run(subdir, hook=None):
        d = os.path.join(workdir, f"sdc_{seed}_{subdir}")
        os.makedirs(d, exist_ok=True)
        tr = trainer(hook=hook)
        tr.fit(loader(), epochs=2,
               resilience=ResiliencePolicy(checkpoint_dir=d,
                                           sdc_check_every=2))
        return tr, d

    # 1. clean — the false-positive gate.
    tr0, _ = run("clean")
    clean = params_bytes(tr0)
    clean_ok = (tr0.stats["sdc_checks"] > 0
                and tr0.stats["sdc_detections"] == 0)

    # 2. one-shot flip: detect, localize, repair bit-identical.
    flip = (rng.randrange(2, 6), rng.randrange(1, len(jax.devices())),
            rng.choice((3, 5, 7, 11)))
    inj = BitFlipParams([flip])
    tr1, _ = run("transient", hook=inj)
    det = [e for e in tr1.stats["events"] if e["kind"] == "sdc_detected"]
    localized = bool(det) and det[0].get("replicas") == [f"p0/d{flip[1]}"]
    detect_ok = (len(inj.fired) == 1
                 and tr1.stats["sdc_detections"] == 1
                 and tr1.stats["sdc_transients"] == 1 and localized)
    parity_ok = params_bytes(tr1) == clean

    # 3. persistent flip: graded response escalates to quarantine.
    inj2 = BitFlipParams(persist_from=rng.randrange(2, 5),
                         replica=rng.randrange(1, len(jax.devices())),
                         bit=rng.choice((3, 5, 7, 11)))
    quarantine_ok = False
    try:
        tr2, d3 = run("persistent", hook=inj2)
    except SdcPersistentError:
        d3 = os.path.join(workdir, f"sdc_{seed}_persistent")
        quarantine_ok = os.path.exists(os.path.join(d3, QUARANTINE_MARKER))
    detections = (tr0.stats["sdc_detections"] + tr1.stats["sdc_detections"]
                  + (1 if quarantine_ok else 0))
    return {
        "metric": "sdc_soak", "seed": seed, "value": detections,
        "unit": "detections", "clean_ok": clean_ok, "parity_ok": parity_ok,
        "quarantine_ok": quarantine_ok,
        "accounted": detect_ok and quarantine_ok,
        "sdc_checks": tr0.stats["sdc_checks"],
        "transients": tr1.stats["sdc_transients"],
        "flip": list(flip),
        "device_kind": jax.devices()[0].device_kind,
        "measured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one trainer process (env-config)")
    ap.add_argument("--soak", type=str, default=None,
                    help="comma-separated seeds (env: TRAIN_SOAK; default "
                         "the mode's seeds)")
    ap.add_argument("--multihost", action="store_true",
                    help="run the POD-SCALE soak instead: N worker "
                         "processes per launch, SIGKILL one of them "
                         "mid-epoch, byte-flip one host's shard, relaunch "
                         "at the same and at a reduced host geometry "
                         "(seeds via --soak / env TRAIN_SOAK_MULTIHOST)")
    ap.add_argument("--sdc", action="store_true",
                    help="run the silent-data-corruption soak instead: "
                         "clean / one-shot-flip / persistent-flip fits "
                         "in-process (seeds via --soak / env SDC_SOAK)")
    ap.add_argument("--workdir", type=str, default=None,
                    help="scratch root (default: a fresh temp dir)")
    args = ap.parse_args()
    if args.worker:
        raise SystemExit(_worker())
    registry = (SDC_SOAK_SEEDS if args.sdc
                else TRAIN_SOAK_MULTIHOST_SEEDS if args.multihost
                else TRAIN_SOAK_SEEDS)
    env_name = ("SDC_SOAK" if args.sdc
                else "TRAIN_SOAK_MULTIHOST" if args.multihost
                else "TRAIN_SOAK")
    soak_env = args.soak or os.environ.get(env_name)
    seeds = ([int(s) for s in soak_env.split(",") if s]
             if soak_env else list(registry))
    bad = [s for s in seeds if s not in registry]
    if bad:
        raise SystemExit(f"error: unregistered soak seeds {bad} "
                         f"(registry: {list(registry)})")
    workdir = args.workdir
    if workdir is None:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="tpudp_train_soak_")
    # One mode per invocation, and that is load-bearing on a chip: the
    # kill/resume soaks keep THIS process off JAX and launch workers that
    # take the device one at a time, while --sdc runs JAX in this process
    # and launches nothing — a parent that has touched JAX holds the chip
    # and a child that needs it would fail or hang.
    runner = (run_sdc_soak if args.sdc
              else run_soak_multihost if args.multihost else run_soak)
    metric = ("sdc_soak" if args.sdc
              else "train_soak_multihost" if args.multihost
              else "train_soak")
    failed = []
    for seed in seeds:
        try:
            row = runner(seed, workdir)
        except Exception as e:  # crash isolation: one seed, one row
            row = {"seed": seed, "error": f"{type(e).__name__}: {e}"}
        if "error" in row:
            row.setdefault("metric", metric)
            row.setdefault("value", 0)
            failed.append(seed)
        print(json.dumps(row), flush=True)
    if failed:  # every seed still got its row; the run did not succeed
        raise SystemExit(f"error: {metric} seeds failed: {failed}")


if __name__ == "__main__":
    main()
