"""chip_smoke.py — the quickest proof that tpudp still starts on the chip.

    python3 chip_smoke.py          # on a TPU machine; ~minutes, one process

Drives the repo's two runtimes once, through the entry points a user calls,
at the full width of models the repo supports (depth uncut, random weights
from a seed, data generated from a seed — no network, no files read):

  train.vgg       ``tpudp.cli.run_part`` exactly as ``src/Part 2b/main.py``
                  calls it: VGG-11, bf16, global batch 256, two log windows
                  plus the eval loop, over ALL local chips.
  train.ladder    one step each of coordinator / allreduce / ring / auto
                  from one state via ``make_train_step``; updated params
                  must agree with the psum rung within 1e-5
                  (``__graft_entry__.py``'s bound) and ``verify_replicas``
                  must pass; every device must hold a batch shard and a
                  state replica.
  train.gpt2      one GPT-2-small (12 x 768, vocab 50,257, t=1024) train
                  step with ``attn_impl='dense'`` and one with ``'flash'``;
                  the flash program must contain Mosaic custom calls, and
                  the two losses / gradient norms must agree.
  train.lfm2      one LFM2-MoE train step at the published widths and a
                  depth of two (a conv block with the dense SwiGLU, an
                  attention block holding experts 0-7 of 32; t=1024) with
                  ``moe_impl='dense'`` and one with ``'gmm'``; the gmm
                  program must contain a Mosaic custom call under each of
                  the expert layer's kernel names (attention is dense in
                  both, so all of them are the layer's),
                  the two losses / gradient norms must agree, and the
                  ``obs_moe`` counters must have counted every assignment.
  kernels         each Pallas paged-attention family the engine dispatches
                  (decode, window: prefill + verify, tree), called directly
                  with ``interpret=False`` at the engine's own geometry,
                  against the ``impl='einsum'`` / dense-masked reference.
  serve           ``serve.Engine(gpt2_small(bf16), params, kv_pages=...)``
                  with the DEFAULT paged-attention dispatch: plain decode,
                  ``decode_fuse=8`` and ``speculate_k=4``; requests must
                  complete, every greedy token must be the argmax (to bf16
                  resolution) of a float32 teacher-forced forward of the
                  model, and ``metrics()["paged_attn"]`` must name what
                  ran.  The engine is single-device by design today (it
                  runs on device 0).

Nothing here is a measurement: no rate, no utilization.  Each phase prints
PASS/FAIL with its compile seconds (set-up) and run seconds.  Exit code 0 and
a last stdout line ``{"ok": true, "device": {...}}`` only if JAX found a TPU
and every phase passed; otherwise non-zero and no result line.

``tests/test_chip_smoke.py`` rehearses the same phase functions on the CPU at
tiny sizes with interpret mode requested explicitly (``rehearse=True``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at.  FULL is the chip's; the CPU rehearsal
    passes its own tiny instance."""

    vgg_batch: int = 256          # the reference's global batch
    vgg_windows: int = 2          # log windows of 20 steps each
    vgg_test: int = 1024
    lm: dict = dataclasses.field(default_factory=dict)  # gpt2_small overrides
    lm_seq: int = 1024
    lm_batch_per_device: int = 2
    # Lfm2Config overrides: the published widths at a depth of two, one
    # block of each kind (conv + dense SwiGLU, attention + 8 of 32 experts)
    lfm2: dict = dataclasses.field(default_factory=lambda: dict(
        vocab_size=16384, hidden_size=2048, intermediate_size=7168,
        moe_intermediate_size=1792, num_hidden_layers=2, num_dense_layers=1,
        layer_types=("conv", "full_attention"), num_attention_heads=32,
        num_key_value_heads=8, num_experts=8, num_experts_routed=32,
        num_experts_per_tok=4))
    serve_slots: int = 4
    serve_max_len: int = 1024
    serve_chunk: int = 16         # Engine's default prefill_chunk
    prompt_lens: tuple = (5, 16, 40, 100)
    max_new: int = 12


FULL = Sizes()

# Agreement bounds.  The CPU tests' own bounds are for XLA:CPU float32
# (tests/test_paged_kernel.py: 2e-6; tests/test_flash_attention.py: 2e-5
# fwd, 5e-4 grads, 0.05 bf16).  On the chip float32 matmuls are bf16-pass
# emulations, so the float32 comparison runs at precision 'highest' and is
# held to what that emulation resolves; the bf16 comparison (the engine's
# own dtype, default precision) is held to the flash test's bf16 bound.
# (Measured on the v5e: 2.0e-6 float32, 7.8e-3 = one bf16 ulp.)
KERNEL_ATOL_F32 = 1e-5
KERNEL_ATOL_BF16 = 0.05
FLASH_RTOL_BF16 = 0.05
LADDER_TOL = 1e-5
# A greedy token is right when no other token's float32 reference logit
# beats it by more than bf16 can resolve.  A random-weights GPT-2 has unit-
# variance logits whose top two sit 0.005-0.3 apart, while the bf16 engine
# resolves a logit of ~4 to 0.016-0.03 per rounding; a token from a wrong
# context would trail the maximum by ~4.  (Measured on the v5e: 0.016.)
SERVE_LOGIT_GAP = 0.1


class CompileMeter:
    """Sums JAX's own compile-path durations (trace + lowering + backend
    compile or persistent-cache retrieval) and counts persistent-cache
    hits/misses, so each phase can report compile time as set-up."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.seconds, self.hits, self.misses)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _brief(values: dict) -> dict:
    """``{name: value}`` rounded to two significant digits for a detail
    line."""
    return {k: float(f"{v:.2g}") for k, v in values.items()}


def _max_abs(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


# ------------------------------------------------------------------ train


def phase_train_vgg(sizes: Sizes, workdir: str, rehearse: bool) -> str:
    """The paper's path: ``run_part`` as ``src/Part 2b/main.py`` calls it."""
    import jax
    import numpy as np

    from tpudp import native
    from tpudp.cli import run_part

    os.environ["TPUDP_NO_DOWNLOAD"] = "1"  # sealed machine: never try the net
    n_dev = len(jax.devices())
    metrics = os.path.join(workdir, "smoke_vgg_metrics.jsonl")
    if os.path.exists(metrics):
        os.remove(metrics)
    steps = 20 * sizes.vgg_windows
    argv = ["--dtype", "bfloat16", "--batch-size", str(sizes.vgg_batch),
            "--epochs", "1", "--data-root",
            os.path.join(workdir, "no-cifar-here"),
            "--synthetic-train-size", str(sizes.vgg_batch * steps),
            "--synthetic-test-size", str(sizes.vgg_test),
            "--metrics-jsonl", metrics]
    if rehearse:
        argv += ["--platform", "cpu"]
    trainer = run_part("allreduce", "Part 2b: DP with all-reduce grad sync",
                       argv=argv)
    _check(trainer.mesh.size == n_dev,
           f"mesh has {trainer.mesh.size} devices, jax sees {n_dev}")
    with open(metrics) as f:
        rows = [json.loads(line) for line in f]
    windows = [r["loss"] for r in rows if r["kind"] == "train_window"]
    evals = [r for r in rows if r["kind"] == "eval"]
    _check(len(windows) == sizes.vgg_windows,
           f"expected {sizes.vgg_windows} log windows, got {windows}")
    _check(all(np.isfinite(windows)), f"non-finite window loss {windows}")
    _check(windows[-1] < windows[0], f"window loss not falling: {windows}")
    _check(len(evals) == 1 and np.isfinite(evals[0]["avg_loss"])
           and evals[0]["count"] == sizes.vgg_test,
           f"eval loop: {evals}")
    _check(int(trainer.state.step) == steps,
           f"optimizer step {int(trainer.state.step)} != {steps}")
    backend = "native" if native.available() else "numpy"
    return (f"mesh={trainer.mesh.size} per_device_batch="
            f"{sizes.vgg_batch // n_dev} steps={steps} window_loss="
            f"{[round(w, 4) for w in windows]} eval_loss="
            f"{evals[0]['avg_loss']:.4f} data_backend={backend}")


def phase_train_ladder(sizes: Sizes, workdir: str, rehearse: bool) -> str:
    """One step of each sync rung from one state; placement checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.mesh import batch_sharding, make_mesh, replicated_sharding
    from tpudp.models.vgg import VGG11
    from tpudp.train import init_state, make_optimizer, make_train_step
    from tpudp.utils.consistency import verify_replicas

    mesh = make_mesh()
    n_dev = mesh.size
    _check(n_dev == len(jax.devices()), "mesh does not span every device")
    model = VGG11(dtype=jnp.bfloat16)
    tx = make_optimizer()
    state = jax.device_put(init_state(model, tx), replicated_sharding(mesh))
    rng = np.random.default_rng(0)
    batch = sizes.vgg_batch
    images = jax.device_put(
        rng.normal(size=(batch, 32, 32, 3)).astype(np.float32),
        batch_sharding(mesh))
    labels = jax.device_put(
        rng.integers(0, 10, size=batch).astype(np.int32),
        batch_sharding(mesh))

    # Code that has only run on one chip may have put everything on the
    # first: every device must hold its batch shard and a state replica.
    shard_devs = {s.device for s in images.addressable_shards}
    _check(shard_devs == set(mesh.devices.flat),
           f"batch shards on {len(shard_devs)} of {n_dev} devices")
    _check({s.data.shape[0] for s in images.addressable_shards}
           == {batch // n_dev}, "uneven batch shards")
    leaf = jax.tree.leaves(state.params)[0]
    _check({s.device for s in leaf.addressable_shards}
           == set(mesh.devices.flat), "state is not replicated everywhere")

    updated, losses = {}, {}
    for rung in ("allreduce", "coordinator", "ring", "auto"):
        step = make_train_step(model, tx, mesh, sync=rung, donate=False)
        new_state, loss = step(state, images, labels)
        jax.block_until_ready(new_state)
        losses[rung] = float(loss)
        _check(np.isfinite(losses[rung]), f"{rung}: loss {losses[rung]}")
        updated[rung] = new_state
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in
              mesh.devices.flat]
    if all(b is not None for b in in_use):  # XLA:CPU reports no stats
        _check(min(in_use) > 0 and min(in_use) >= 0.5 * max(in_use),
               f"per-device bytes_in_use uneven: {in_use}")

    ref = updated["allreduce"].params
    deltas = {}
    for rung, st in updated.items():
        deltas[rung] = max(jax.tree.leaves(jax.tree.map(_max_abs, st.params,
                                                        ref)))
        _check(deltas[rung] < LADDER_TOL,
               f"{rung} params differ from psum by {deltas[rung]}")
        n_checked = verify_replicas({"params": st.params,
                                     "batch_stats": st.batch_stats})
        _check(n_checked > 0 or n_dev == 1,
               f"{rung}: no replicated leaves compared on {n_dev} devices")
    return (f"mesh={n_dev} batch_shard={batch // n_dev} "
            f"max|params-psum|={_brief(deltas)} "
            f"verify_replicas=ok bytes_in_use={in_use}")


def _plain_vs_kernel_step(sizes: Sizes, rehearse: bool, vocab: int,
                          make_model, plain: str, kernel: str,
                          kernels: tuple = (),
                          **state_kw) -> tuple[str, dict]:
    """One train step of ``make_model(impl)`` for the plain-XLA ``impl``
    and for the Pallas one, from the same state and batch: the plain
    program holds no Mosaic call, the kernel's does, one under each name
    of ``kernels`` (asserted from the program that runs, not inferred from
    the backend's name), and loss and gradient norm agree to the bf16
    bound.  Returns the phase's detail line and each impl's final state."""
    import jax
    import numpy as np

    from tpudp.mesh import batch_sharding, make_mesh, replicated_sharding
    from tpudp.train import init_state, make_optimizer, make_train_step

    mesh = make_mesh()
    batch = sizes.lm_batch_per_device * mesh.size
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, vocab, size=(batch, sizes.lm_seq)).astype(
        np.int32)
    x = jax.device_put(tokens, batch_sharding(mesh))
    y = jax.device_put(np.roll(tokens, -1, axis=1), batch_sharding(mesh))
    tx = make_optimizer(learning_rate=0.01)

    out, states = {}, {}
    for impl in (plain, kernel):
        model = make_model(impl)
        state = jax.device_put(
            init_state(model, tx, input_shape=(1, 8), track_grad_norm=True,
                       **state_kw),
            replicated_sharding(mesh))
        step = make_train_step(model, tx, mesh, donate=False)
        lowered = step.lower(state, x, y)
        text = lowered.as_text()
        mosaic = "tpu_custom_call" in text
        if impl == plain:
            _check(not mosaic,
                   f"{plain} step unexpectedly holds a Mosaic call")
        elif not rehearse:
            _check(mosaic, f"{kernel} step holds no Mosaic custom call — "
                           "the kernel was interpreted or replaced")
            missing = [k for k in kernels
                       if f'kernel_name = "{k}"' not in text]
            _check(not missing, f"{kernel} step: no Mosaic call named "
                                f"{missing}")
        states[impl], loss = lowered.compile()(state, x, y)
        jax.block_until_ready(states[impl])
        # obs_norms = [sum|g|, sum|g|^2] accumulated inside the step
        gnorm = float(np.sqrt(np.asarray(states[impl].obs_norms)[1]))
        out[impl] = (float(loss), gnorm)
        _check(np.isfinite(out[impl]).all(), f"{impl}: {out[impl]}")
        del state, lowered, text
    (lp, gp), (lk, gk) = out[plain], out[kernel]
    _check(abs(lk - lp) <= FLASH_RTOL_BF16 * abs(lp),
           f"{kernel} loss {lk} vs {plain} {lp}")
    _check(abs(gk - gp) <= FLASH_RTOL_BF16 * abs(gp),
           f"{kernel} grad norm {gk} vs {plain} {gp}")
    return (f"mesh={mesh.size} batch={batch} t={sizes.lm_seq} "
            f"loss {plain}={lp:.5f} {kernel}={lk:.5f} "
            f"grad_norm {plain}={gp:.5f} {kernel}={gk:.5f} "
            f"{kernel}_mosaic={'asserted' if not rehearse else 'interpreted'}",
            states)


def phase_train_gpt2(sizes: Sizes, workdir: str, rehearse: bool) -> str:
    """A transformer at published width: dense vs flash train step."""
    import jax.numpy as jnp

    from tpudp.models.gpt2 import gpt2_small

    detail, _ = _plain_vs_kernel_step(
        sizes, rehearse, gpt2_small(**sizes.lm).config.vocab_size,
        lambda impl: gpt2_small(dtype=jnp.bfloat16, attn_impl=impl,
                                **sizes.lm), "dense", "flash")
    return detail


# every Pallas kernel of the expert layer, forward and backward
MOE_KERNELS = ("moe_gmm", "moe_tgmm", "moe_swiglu", "moe_swiglu_bwd",
               "moe_combine")


def phase_train_lfm2(sizes: Sizes, workdir: str, rehearse: bool) -> str:
    """Two kinds of block and a share of a routed expert layer: the
    train step with the expert layer as a plain loop vs as grouped-matmul
    kernels (attention dense in both, so the only Mosaic calls are theirs)."""
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.lfm2 import Lfm2, Lfm2Config

    detail, states = _plain_vs_kernel_step(
        sizes, rehearse, sizes.lfm2["vocab_size"],
        lambda impl: Lfm2(Lfm2Config(dtype=jnp.bfloat16, moe_impl=impl,
                                     **sizes.lfm2)),
        "dense", "gmm", kernels=MOE_KERNELS, track_moe=True)
    for impl, state in states.items():
        total, held = (float(v) for v in np.asarray(state.obs_moe)[:2])
        _check(total == state.loss_sum.sharding.mesh.size
               * sizes.lm_batch_per_device * sizes.lm_seq
               * sizes.lfm2["num_experts_per_tok"] and 0 < held < total,
               f"{impl}: obs_moe {np.asarray(state.obs_moe)}")
    return f"{detail} held_share={held / total:.4f}"


# ---------------------------------------------------------------- kernels


def phase_kernels(sizes: Sizes, workdir: str, rehearse: bool) -> str:
    """Each paged kernel family, called directly at the engine's geometry,
    against the einsum / dense-masked reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.gpt2 import gpt2_small
    from tpudp.ops.paged_attention import (paged_attention,
                                           tree_paged_attention)
    from tpudp.serve.engine import _ancestor_matrix

    cfg = gpt2_small(**sizes.lm).config
    h, dh = cfg.num_heads, cfg.d_model // cfg.num_heads
    T, S = sizes.serve_chunk, sizes.serve_slots
    M = sizes.serve_max_len // T
    n_pages = S * M
    rng = np.random.default_rng(2)
    # a fragmented table: random page ids, a shared prefix, -1 tails
    depth = [sizes.serve_max_len - T, sizes.serve_max_len // 2 + 5, T + 1,
             T - 1][:S]
    depth += [T + 3] * (S - len(depth))
    perm = rng.permutation(n_pages)
    table = np.full((S, M), -1, np.int32)
    used = 0
    for s, d in enumerate(depth):
        n = -(-(d + 8) // T)
        table[s, :n] = perm[used:used + n]
        used += n
    if S > 1:
        table[1, :2] = table[0, :2]
    pos = np.asarray(depth, np.int32)
    anc_t = _ancestor_matrix((-1, 0, 1, 0, 3))  # the engine's fork2x2 tree
    anc = np.asarray(anc_t)
    t1 = len(anc_t)

    def tree_reference(q, k, v, wk, wv, dtype):
        """Dense masked reference (tests/test_paged_kernel.py's): strict
        ``< pos`` cache visibility + ancestor-or-self window mask."""
        tbl = jnp.where(table >= 0, table, n_pages)
        kc = k[tbl].reshape(S, M * T, h, dh)
        vc = v[tbl].reshape(S, M * T, h, dh)
        kk = jnp.concatenate([kc, wk], axis=1)
        vv = jnp.concatenate([vc, wv], axis=1)
        lg = jnp.einsum("bjhd,bthd->bjht", q, kk) * dh ** -0.5
        vis = jnp.concatenate(
            [jnp.broadcast_to((jnp.arange(M * T)[None, :]
                               < pos[:, None])[:, None], (S, t1, M * T)),
             jnp.broadcast_to(anc[None], (S, t1, t1))], axis=2)
        lg = jnp.where(vis[:, :, None], lg, -1e30)
        pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1).astype(dtype)
        return jnp.einsum("bjht,bthd->bjhd", pr, vv)

    worst = {}
    for dtype, precision, atol in ((jnp.float32, "highest", KERNEL_ATOL_F32),
                                   (jnp.bfloat16, None, KERNEL_ATOL_BF16)):
        name = jnp.dtype(dtype).name
        # the pool's stored form: a token's heads side by side in one row
        k = jnp.asarray(rng.standard_normal((n_pages + 1, T, h * dh)), dtype)
        v = jnp.asarray(rng.standard_normal((n_pages + 1, T, h * dh)), dtype)

        def attend(impl, q, tbl, p):
            fn = jax.jit(lambda q, k, v: paged_attention(
                q, (k, v), tbl, p, dtype=dtype, impl=impl,
                **({"interpret": rehearse} if impl == "kernel" else {})))
            with jax.default_matmul_precision(precision or "default"):
                return fn(q, k, v)

        cases = (("decode", 1, table, pos),
                 ("verify", 5, table, pos),
                 ("prefill", T, table[:1], np.int32(depth[0] // T * T)))
        for fam, cur, tbl, p in cases:
            q = jnp.asarray(rng.standard_normal((tbl.shape[0], cur, h, dh)),
                            dtype)
            got = attend("kernel", q, tbl, p)
            ref = attend("einsum", q, tbl, p)
            _check(bool(jnp.isfinite(got.astype(jnp.float32)).all()),
                   f"{fam}/{name}: non-finite kernel output")
            worst[f"{fam}/{name}"] = err = _max_abs(got, ref)
            _check(err <= atol, f"{fam}/{name}: kernel vs einsum {err}")
        q = jnp.asarray(rng.standard_normal((S, t1, h, dh)), dtype)
        wk = jnp.asarray(rng.standard_normal((S, t1, h, dh)), dtype)
        wv = jnp.asarray(rng.standard_normal((S, t1, h, dh)), dtype)
        with jax.default_matmul_precision(precision or "default"):
            got = jax.jit(lambda q, k, v, wk, wv: tree_paged_attention(
                q, (k, v), table, pos, wk, wv, anc_t, dtype=dtype,
                interpret=rehearse))(q, k, v, wk, wv)
            ref = jax.jit(lambda q, k, v, wk, wv: tree_reference(
                q, k, v, wk, wv, dtype))(q, k, v, wk, wv)
        worst[f"tree/{name}"] = err = _max_abs(got, ref)
        _check(err <= atol, f"tree/{name}: kernel vs dense-masked {err}")
    return (f"h={h} dh={dh} page_tokens={T} pages/slot={M} interpret="
            f"{rehearse} max|kernel-ref|={_brief(worst)}")


# ------------------------------------------------------------------ serve


def phase_serve(sizes: Sizes, workdir: str, rehearse: bool) -> str:
    """The serving engine at published width, default dispatch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.generate import generate
    from tpudp.models.gpt2 import gpt2_small
    from tpudp.serve import TRACE_COUNTS, Engine

    model = gpt2_small(dtype=jnp.bfloat16, **sizes.lm)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    # serve bf16 weights, as a deployment would: one 2-byte copy on device
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in sizes.prompt_lens]
    new = sizes.max_new
    # The repo's decode oracle, reported not asserted: with random weights
    # near-tied logits make exact token equality a property of rounding.
    want = [list(np.asarray(generate(model, params, jnp.asarray(p[None]),
                                     new))[0, p.size:]) for p in prompts[:2]]
    # The reference that IS asserted: the flax training forward in float32
    # at precision 'highest' over prompt + emitted tokens (one padded shape;
    # the model is causal, so the padding cannot reach the rows read).
    ref_model = gpt2_small(dtype=jnp.float32, **sizes.lm)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    pad_to = -(-(max(sizes.prompt_lens) + new) // 8) * 8
    ref_logits = jax.jit(lambda p, x: ref_model.apply({"params": p}, x,
                                                      train=False))

    def logit_gap(prompt, tokens) -> float:
        seq = np.zeros((1, pad_to), np.int32)
        seq[0, :prompt.size + len(tokens)] = [*prompt, *tokens]
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref_logits(params32, seq))[0]
        rows = lg[prompt.size - 1:prompt.size - 1 + len(tokens)]
        return float(np.max(rows.max(-1)
                            - rows[np.arange(len(tokens)), tokens]))

    dev0 = jax.devices()[0]
    traces = dict(TRACE_COUNTS)

    def engine(**kw):
        # On the chip paged_attn is left UNSET: the default dispatch is the
        # thing under test.  The CPU rehearsal has to ask for the kernels
        # (there the default is einsum) and gets them in interpret mode.
        if rehearse:
            kw["paged_attn"] = "kernel"
        return Engine(model, params, num_slots=sizes.serve_slots,
                      max_len=sizes.serve_max_len,
                      prefill_chunk=sizes.serve_chunk,
                      kv_pages=sizes.serve_slots
                      * (sizes.serve_max_len // sizes.serve_chunk), **kw)

    gaps, exact = {}, {}

    def drive(eng, label, sampled=True):
        handles = [eng.submit(p, new) for p in prompts]
        if sampled:
            handles.append(eng.submit(prompts[0], new, temperature=0.8,
                                      top_k=40, seed=7))
        eng.run_until_complete()
        for hd in handles:
            _check(hd.ok and len(hd.tokens) == new, f"{label}: request "
                   f"{hd.id} finished {hd.finish_reason} with "
                   f"{len(hd.tokens)}/{new} tokens ({hd.error})")
            _check(all(0 <= t < cfg.vocab_size for t in hd.tokens),
                   f"{label}: token out of vocab")
        gaps[label] = max(logit_gap(p, hd.tokens)
                          for p, hd in zip(prompts, handles))
        _check(gaps[label] <= SERVE_LOGIT_GAP, f"{label}: a greedy token "
               f"trails the reference argmax by {gaps[label]:.3f} logits")
        exact[label] = sum(w == hd.tokens for w, hd in zip(want, handles))
        m = eng.metrics()
        pa = m["paged_attn"]
        _check(eng.last_step_error is None
               and not m["stats"].get("step_failures"),
               f"{label}: a device step failed: {eng.last_step_error!r}")
        if not rehearse:
            _check(pa["requested"] is None and pa["resolved"] == "kernel",
                   f"{label}: default dispatch resolved {pa}")
        _check(set(pa["dispatch"].values()) == {"kernel"}
               and pa["fallbacks"] == [], f"{label}: dispatch {pa}")
        eng.check_paged()
        # every slot retired: the only pages still held are the prefix
        # tree's published ones
        _check(m["page_pools"][0]["used_pages"]
               == len(eng.page_index.tree_refs()), f"{label}: pages leaked")
        return m

    plain = drive(engine(), "decode")
    fused = drive(engine(decode_fuse=8), "decode_fuse=8")
    _check(fused["stats"].get("fused_windows", 0) > 0,
           f"fused decode never engaged: {fused['stats']}")
    spec = drive(engine(speculate_k=4), "speculate_k=4", sampled=False)
    _check(spec["stats"].get("verify_steps", 0) > 0,
           f"speculation never verified a window: {spec['stats']}")
    ran = {k: TRACE_COUNTS[k] - traces.get(k, 0) for k in TRACE_COUNTS
           if k.endswith("_paged_kernel") and TRACE_COUNTS[k] > traces.get(k, 0)}
    for prog in ("decode_paged_kernel", "prefill_paged_kernel",
                 "fused_decode_paged_kernel", "verify_paged_kernel"):
        _check(prog in ran, f"kernel program {prog} never traced: {ran}")
    n_chunks = sum(-(-n // sizes.serve_chunk) for n in sizes.prompt_lens)
    _check(plain["stats"]["prefill_chunks"] >= n_chunks - 1,
           f"prefill chunks {plain['stats']['prefill_chunks']}")
    in_use = (dev0.memory_stats() or {}).get("bytes_in_use")
    return (f"device={dev0.id} (single-device engine; other chips idle) "
            f"dispatch={plain['paged_attn']['dispatch']['decode_paged']} "
            f"fallbacks={plain['paged_attn']['fallbacks']} "
            f"programs={sorted(ran)} requests={len(prompts) * 3 + 2} "
            f"draft_acceptance={spec.get('acceptance_rate')} "
            f"max_logit_gap={_brief(gaps)} "
            f"equal_generate={exact} of {len(want)} "
            f"weight_bytes={weight_bytes} bytes_in_use={in_use}")


PHASES = (("train.vgg", phase_train_vgg),
          ("train.ladder", phase_train_ladder),
          ("train.gpt2", phase_train_gpt2),
          ("train.lfm2", phase_train_lfm2),
          ("kernels", phase_kernels),
          ("serve", phase_serve))


def run_phases(sizes: Sizes, workdir: str, *,
               rehearse: bool = False) -> list[dict]:
    """Run the phases in order; a failed phase is recorded, never raised.
    ``rehearse=True`` is the CPU rehearsal: kernels run in interpret mode
    because it is asked for here, explicitly."""
    import gc

    meter = CompileMeter()
    os.makedirs(workdir, exist_ok=True)
    results = []
    for name, fn in PHASES:
        c0, h0, m0 = meter.mark()
        t0 = time.perf_counter()
        try:
            detail, ok = fn(sizes, workdir, rehearse), True
        except Exception:  # noqa: BLE001 — a failed phase fails the smoke
            detail, ok = traceback.format_exc(), False
        wall = time.perf_counter() - t0
        compile_s = meter.seconds - c0
        results.append({"phase": name, "ok": ok, "detail": detail,
                        "compile_s": compile_s,
                        "run_s": max(wall - compile_s, 0.0),
                        "cache_hits": meter.hits - h0,
                        "cache_misses": meter.misses - m0})
        print(f"[chip_smoke] {name}: {'PASS' if ok else 'FAIL'} "
              f"(set-up: compile {compile_s:.1f}s, persistent-cache hits "
              f"{meter.hits - h0} misses {meter.misses - m0}; run "
              f"{max(wall - compile_s, 0.0):.1f}s)\n"
              f"[chip_smoke]   {detail}", flush=True)
        gc.collect()
    return results


def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[chip_smoke] jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} devices={device['count']}",
          flush=True)
    if dev.platform != "tpu":
        print("[chip_smoke] no TPU: this script proves the chip path and "
              "has nothing to say without one", file=sys.stderr)
        return 2
    from tpudp.utils.compile_cache import enable_persistent_cache

    print(f"[chip_smoke] persistent compile cache: "
          f"{enable_persistent_cache()}", flush=True)
    results = run_phases(FULL, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out"))
    failed = [r["phase"] for r in results if not r["ok"]]
    print(f"[chip_smoke] compile seconds (set-up) total "
          f"{sum(r['compile_s'] for r in results):.1f}; persistent-cache "
          f"hits {sum(r['cache_hits'] for r in results)} misses "
          f"{sum(r['cache_misses'] for r in results)}", flush=True)
    if failed:
        print(f"[chip_smoke] FAILED: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
