"""Pipeline-parallel training rung (tpudp/parallel/schedule.py).

One row per PP x DP geometry (metric ``train_pipeline``; default sweep
``PIPELINE_CONFIGS`` below), each held to a merciless three-part
referee — the same bar the tier-1 tests hold, re-proven on the real
device at bench scale:

  * **throughput**: tokens/sec through the unrolled 1F1B MPMD step
    (ramp/steady/drain ticks in ONE jitted program, activations and
    grads moving between stages over ``lax.ppermute``, optimizer update
    reduce-scattered 1/DP per replica in-step), timed after the compile
    step, with the analytic bubble fraction
    (``tpudp.utils.flops.pipeline_bubble_fraction``) alongside so the
    measured gap to the PP=1 baseline can be attributed;
  * **parity** (``parity_ok``): the geometry's loss trajectory must
    track a single-stage (PP=1 DP=1) run of the same model at equal
    global batch within 1e-6 RELATIVE — about one float32 ulp, the
    slack the tpudp/parallel/schedule.py docstring documents as owned
    by XLA's fusion choices (at bench model dims the fusion contexts
    differ earlier than at the tier-1 dims, where
    tests/test_schedule.py pins the trajectory BIT-exact).  The row
    records ``loss_bitexact_steps`` (the leading bit-identical prefix)
    and ``loss_max_rel_diff`` so the drift stays visible, never
    silently absorbed;
  * **fault accounting** (``accounted``): a short Trainer soak at the
    same geometry with a fault raised INSIDE a pipeline step must take
    the supervisor's voted recovery path — exactly one ``step_retry`` in
    the typed event log — and land params bit-identical to an
    uninterrupted soak (per-stage checkpoint shards restored through the
    global-slice manifest).

A row that is fast but diverged, or recovered but unaccounted, is a
FAILURE — same philosophy as ``resilience_bench.py``.  A CPU smoke row
shows the referees at work, never a speed (every row names its
``device_kind``).

Env knobs: TRAIN_PIPELINE (comma geometry names; default the sweep),
TRAIN_PIPELINE_PLATFORM (e.g. ``cpu``), TRAIN_PIPELINE_DEVICES (virtual
CPU device count for smoke — also pins single-threaded Eigen so the
parity referee measures the schedule, not Eigen's reduction order),
TRAIN_PIPELINE_STEPS (8 timed steps), TRAIN_PIPELINE_BATCH (16),
TRAIN_PIPELINE_SEQ (64), TRAIN_PIPELINE_LAYERS (8),
TRAIN_PIPELINE_D_MODEL (128), TRAIN_PIPELINE_MICRO (4 microbatches).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# The default sweep, ``pp{P}dp{D}[v{V}]``: P stages x D replicas, V virtual
# stages a device.  All three need an 8-device slice (P*D = 8); the
# interleaved v2 geometry additionally proves the virtual-stage ring wrap
# at bench scale.
PIPELINE_CONFIGS = ("pp2dp4", "pp4dp2", "pp2dp4v2")


def _cfg() -> dict:
    return {
        "steps": int(os.environ.get("TRAIN_PIPELINE_STEPS", 8)),
        "batch": int(os.environ.get("TRAIN_PIPELINE_BATCH", 16)),
        "seq": int(os.environ.get("TRAIN_PIPELINE_SEQ", 64)),
        "layers": int(os.environ.get("TRAIN_PIPELINE_LAYERS", 8)),
        "d_model": int(os.environ.get("TRAIN_PIPELINE_D_MODEL", 128)),
        "micro": int(os.environ.get("TRAIN_PIPELINE_MICRO", 4)),
    }


def parse_config(name: str) -> tuple[int, int, int]:
    """``pp{P}dp{D}[v{V}]`` -> (stages, dp, interleave); ValueError on
    anything else (tests/test_hand_tools.py pins the format)."""
    m = re.fullmatch(r"pp(\d+)dp(\d+)(?:v(\d+))?", name)
    if not m:
        raise ValueError(f"bad pipeline config {name!r} "
                         "(expected pp{{P}}dp{{D}}[v{{V}}])")
    return int(m.group(1)), int(m.group(2)), int(m.group(3) or 1)


def _model_and_data(cfg):
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.gpt2 import gpt2_small

    model = gpt2_small(vocab_size=256, max_seq_len=cfg["seq"],
                      num_layers=cfg["layers"], num_heads=4,
                      d_model=cfg["d_model"])
    rng = np.random.default_rng(11)
    toks = rng.integers(0, 256, size=(cfg["steps"], cfg["batch"],
                                      cfg["seq"])).astype(np.int32)
    data = [(jnp.asarray(x), jnp.roll(jnp.asarray(x), -1, axis=1))
            for x in toks]
    return model, data


def _drive(pp: int, dp: int, v: int, cfg: dict):
    """One geometry through the MPMD step builder; returns the loss
    trajectory and the post-compile sec/step (None at PP=1 DP=1 where
    only the trajectory matters)."""
    import jax
    import numpy as np

    from tpudp.mesh import make_mesh_nd
    from tpudp.parallel.schedule import make_pipeline_train_step
    from tpudp.train import init_state, make_optimizer

    mesh = make_mesh_nd({"data": dp, "pipe": pp},
                        devices=jax.devices()[: dp * pp])
    model, data = _model_and_data(cfg)
    tx = make_optimizer(learning_rate=0.01)
    state, step = make_pipeline_train_step(
        model, tx, mesh, init_state(model, tx, input_shape=(1, 8), seed=0),
        n_microbatches=cfg["micro"], interleave=v)
    losses, timed = [], []
    for i, (x, y) in enumerate(data):
        t0 = time.perf_counter()
        state, loss = step(state, x, y)
        loss.block_until_ready()
        if i > 0:  # step 0 pays the compile
            timed.append(time.perf_counter() - t0)
        losses.append(np.asarray(loss))
    sec = sum(timed) / len(timed) if timed else None
    return np.array(losses), sec


def _fault_soak(pp: int, dp: int, v: int, cfg: dict,
                workdir: str, tag: str) -> dict:
    """The accounting leg: clean vs faulted Trainer soak at this
    geometry; a raise inside step 5 must cost exactly one accounted
    ``step_retry`` and zero bits of the final parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.mesh import make_mesh_nd
    from tpudp.models.gpt2 import gpt2_small
    from tpudp.resilience import ResiliencePolicy
    from tpudp.train import Trainer
    from tpudp.training_faults import RaisingStep

    model_kw = dict(vocab_size=256, max_seq_len=cfg["seq"],
                    num_layers=cfg["layers"], num_heads=4,
                    d_model=cfg["d_model"])

    class Loader:
        def __init__(self):
            rng = np.random.default_rng(7)
            toks = rng.integers(0, 256, size=(4, cfg["batch"],
                                              cfg["seq"])).astype(np.int32)
            self.batches = [
                (jnp.asarray(x), jnp.roll(jnp.asarray(x), -1, axis=1),
                 jnp.ones((cfg["batch"],), jnp.float32))
                for x in toks]

        def set_epoch(self, epoch):
            pass

        def __iter__(self):
            return iter(self.batches)

        def __len__(self):
            return len(self.batches)

    def fit(name, hook):
        mesh = make_mesh_nd({"data": dp, "pipe": pp},
                            devices=jax.devices()[: dp * pp])
        trainer = Trainer(
            gpt2_small(**model_kw), mesh, strategy="pp",
            strategy_options={"n_microbatches": cfg["micro"],
                              "schedule": "1f1b_mpmd", "interleave": v},
            input_shape=(1, cfg["seq"]), learning_rate=0.01, log_every=100,
            log_fn=lambda s: None, seed=0, step_fault_hook=hook)
        pol = ResiliencePolicy(
            checkpoint_dir=os.path.join(workdir, f"{tag}_{name}"))
        trainer.fit(Loader(), epochs=2, resilience=pol)
        return trainer

    clean = fit("clean", None)
    faulted = fit("fault", RaisingStep(fail_at={5}))
    retries = faulted.stats.get("step_retries", 0)
    retry_logged = any(e.get("kind") == "step_retry"
                       for e in faulted.stats.get("events", []))
    bits_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(jax.device_get(clean.state.params)),
                        jax.tree.leaves(jax.device_get(
                            faulted.state.params))))
    return {
        "accounted": bool(retries == 1 and retry_logged and bits_equal),
        "step_retries": int(retries),
        "fault_params_bitexact": bool(bits_equal),
    }


def run_config(name: str, cfg: dict, baseline, workdir: str) -> dict:
    import jax
    import numpy as np

    from tpudp.utils.flops import pipeline_bubble_fraction

    pp, dp, v = parse_config(name)
    need = pp * dp
    if len(jax.devices()) < need:
        return {"config": name, "error":
                f"needs {need} devices, have {len(jax.devices())}"}

    losses, sec = _drive(pp, dp, v, cfg)
    parity_ok = bool(np.allclose(losses, baseline, rtol=1e-6, atol=0))
    bitexact_steps = 0
    for a, b in zip(losses, baseline):
        if not np.array_equal(a, b):
            break
        bitexact_steps += 1
    max_rel = float(np.max(np.abs(losses - baseline) / np.abs(baseline)))
    acct = _fault_soak(pp, dp, v, cfg, workdir, name)
    tokens = cfg["batch"] * cfg["seq"]
    return {
        "metric": "train_pipeline", "config": name,
        "value": round(tokens / sec, 1), "unit": "tokens/sec",
        "sec_per_step": round(sec, 6),
        "stages": pp, "dp": dp, "interleave": v,
        "n_microbatches": cfg["micro"],
        "bubble_fraction": round(
            pipeline_bubble_fraction(pp, cfg["micro"], v), 4),
        "global_batch": cfg["batch"], "seq": cfg["seq"],
        "layers": cfg["layers"], "d_model": cfg["d_model"],
        "steps": cfg["steps"],
        "parity_ok": parity_ok,
        "loss_bitexact_steps": bitexact_steps,
        "loss_max_rel_diff": round(max_rel, 12),
        "devices": need,
        "device_kind": jax.devices()[0].device_kind,
        "measured_at_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        **acct,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--configs", type=str, default=None,
                    help="comma-separated geometry names (env: "
                         "TRAIN_PIPELINE; default PIPELINE_CONFIGS)")
    ap.add_argument("--workdir", type=str, default=None,
                    help="checkpoint scratch root (default: a temp dir)")
    args = ap.parse_args()
    conf_env = args.configs or os.environ.get("TRAIN_PIPELINE")
    names = ([c for c in conf_env.split(",") if c] if conf_env
             else list(PIPELINE_CONFIGS))
    for name in names:
        parse_config(name)  # a malformed name fails before any device work

    # Geometry env must land before the first backend touch (jax imports
    # happen inside the run functions, after this block).
    devices = int(os.environ.get("TRAIN_PIPELINE_DEVICES", 0))
    if devices:
        # Single-threaded Eigen pins the CPU reduction order (see
        # resilience_bench.py) so the smoke parity referee exercises the
        # schedule, not Eigen's partitioning; a real TPU run never sets
        # TRAIN_PIPELINE_DEVICES.
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices} "
            "--xla_cpu_multi_thread_eigen=false")
    if os.environ.get("TRAIN_PIPELINE_PLATFORM"):
        import jax

        jax.config.update("jax_platforms",
                          os.environ["TRAIN_PIPELINE_PLATFORM"])
    workdir = args.workdir
    if workdir is None:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="tpudp_train_pipeline_")

    cfg = _cfg()
    # One PP=1 DP=1 oracle run shared by every geometry: same model, same
    # data, same global batch — the trajectory every row must bit-match.
    try:
        baseline, _ = _drive(1, 1, 1, cfg)
    except Exception as e:
        for name in names:
            print(json.dumps({"metric": "train_pipeline", "config": name,
                              "value": 0,
                              "error": f"baseline: {type(e).__name__}: {e}"}),
                  flush=True)
        return
    for name in names:
        try:
            row = run_config(name, cfg, baseline, workdir)
        except Exception as e:  # crash isolation: one config, one row
            row = {"config": name, "error": f"{type(e).__name__}: {e}"}
        if "error" in row:
            row.setdefault("metric", "train_pipeline")
            row.setdefault("value", 0)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
