"""Microbench: Pallas flash attention vs XLA dense attention (grad step).

Source of the BASELINE.md flash-attention rows. Run on the TPU chip:

    python benchmarks/flash_attention_bench.py [t ...]     # default 4096 8192 16384

Times a full gradient step (fwd+bwd) at GPT-2 head geometry with
``jax.block_until_ready`` at the timing edges.  At long sequences the dense
baseline materializes the (t, t) score matrix and runs out of HBM — the
bench then halves the dense batch until it fits and normalizes times to
per-sample, so the ratio stays an equal-work comparison (flash's memory is
O(t·d), so its batch never shrinks).  Prints one JSON line per sequence
length: flash/dense ms, the speedup ratio, and the flash kernel's MFU from
the analytic attention FLOPs (7 blocked matmuls per grad step, halved by
causality).
"""

import json
import os
import sys
import time

import jax

if os.environ.get("FLASH_PLATFORM"):  # cpu smoke mode
    jax.config.update("jax_platforms", os.environ["FLASH_PLATFORM"])
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from tpudp.utils.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()  # no-op on the CPU backend (smoke mode)

from tpudp.ops.flash_attention import flash_attention  # noqa: E402
from tpudp.utils.flops import chip_peak_flops  # noqa: E402


def _time_grad(loss_fn, q, k, v, reps=10):
    f = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))
    for _ in range(3):
        np.asarray(f(q, k, v)[0]).ravel()  # warmup + fence
    t0 = time.perf_counter()
    for _ in range(reps):
        r = f(q, k, v)
    np.asarray(r[0]).ravel()  # fence
    return (time.perf_counter() - t0) / reps


def attention_grad_flops(b, t, h, dh, causal=True):
    """fwd: QK^T + PV (2 matmuls); bwd: S recompute, dP, dQ, dK, dV (5) —
    7 passes of 2*b*h*t^2*dh each, halved by the causal triangle."""
    full = 7 * 2 * b * h * t * t * dh
    return full // 2 if causal else full


def main(*ts: int) -> None:
    ts = ts or (4096, 8192, 16384)
    b = int(os.environ.get("FLASH_B", 4))
    h = int(os.environ.get("FLASH_H", 12))
    dh = 64
    kind = jax.devices()[0].device_kind
    peak = chip_peak_flops(kind)

    for t in ts:
      try:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, t, h, dh), jnp.bfloat16)
                   for kk in ks)

        def make_loss_flash(bq, bk):
            def loss_flash(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal=True, block_q=bq,
                                    block_k=bk).astype(jnp.float32))
            return loss_flash

        def make_loss_dense(tt):
            def loss_dense(q, k, v):
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                    jnp.float32) * dh ** -0.5
                mask = jnp.tril(jnp.ones((tt, tt), bool))
                logits = jnp.where(mask[None, None], logits, -1e30)
                probs = jax.nn.softmax(logits, -1).astype(jnp.bfloat16)
                return jnp.sum(
                    jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(
                        jnp.float32))
            return loss_dense

        # Block-size sweep: the best (block_q, block_k) depends on the
        # chip's VMEM/MXU balance, so the one TPU window should find it
        # rather than trusting the 128x128 default.  FLASH_SWEEP=0 pins
        # the default for quick runs.
        if os.environ.get("FLASH_SWEEP", "1") != "0":
            candidates = [(128, 128), (128, 256), (256, 128), (256, 256),
                          (512, 512)]
        else:
            candidates = [(128, 128)]
        # Clamp to t (flash_attention's own clamping rule), dedupe, then
        # keep only divisible configs — short t degrades to one candidate
        # instead of none.
        candidates = sorted({(min(bq, t), min(bk, t))
                             for bq, bk in candidates
                             if t % min(bq, t) == 0 and t % min(bk, t) == 0})
        if not candidates:
            raise ValueError(
                f"t={t} is not divisible by any candidate block size "
                "(lengths must be multiples of 128, or < 512 for the "
                "clamped fallback)")
        flash_ms, best_blocks, last_exc = None, None, None
        for bq, bk in candidates:
            try:
                ms = _time_grad(make_loss_flash(bq, bk), q, k, v) * 1e3
            except Exception as e:  # noqa: BLE001 - e.g. VMEM overflow at 512
                last_exc = e
                continue
            if flash_ms is None or ms < flash_ms:
                flash_ms, best_blocks = ms, (bq, bk)
        if flash_ms is None:
            # Preserve the real failure for the unattended-run postmortem.
            raise RuntimeError(
                f"no flash block config ran at t={t}: "
                f"{type(last_exc).__name__}: {last_exc}") from last_exc

        dense_ms = None
        dense_b = b
        while dense_b >= 1:
            try:
                per = _time_grad(make_loss_dense(t),
                                 q[:dense_b], k[:dense_b], v[:dense_b])
                dense_ms = per * 1e3 * (b / dense_b)  # normalize to b samples
                break
            except Exception as e:  # RESOURCE_EXHAUSTED at long t
                if "RESOURCE_EXHAUSTED" not in repr(e) and \
                        "Out of memory" not in repr(e):
                    raise
                dense_b //= 2

        flops = attention_grad_flops(b, t, h, dh)
        row = {
            "t": t, "b": b, "h": h, "dh": dh, "dtype": "bfloat16",
            "block_q": best_blocks[0], "block_k": best_blocks[1],
            "flash_ms": round(flash_ms, 2),
            "dense_ms": round(dense_ms, 2) if dense_ms else None,
            "dense_batch": dense_b if dense_ms else 0,
            "ratio_dense_over_flash": (round(dense_ms / flash_ms, 2)
                                       if dense_ms else None),
            "flash_mfu": (round(flops / (flash_ms / 1e3) / peak, 4)
                          if peak else None),
            "device_kind": kind,
        }
        print(json.dumps(row), flush=True)
      except Exception as exc:  # noqa: BLE001 - one t must not cost the rest
        print(json.dumps({"t": t,
                          "error": f"{type(exc).__name__}: {exc}"[:500]}),
              flush=True)


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
