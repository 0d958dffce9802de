"""Analytic FLOPs accounting and MFU (model FLOPs utilization).

The reference defines only wall-clock metrics (``src/Part 2a/main.py:
87-98,106-109``); this module converts them into the single-chip perf
criterion a TPU build is judged on: achieved model FLOPs/s divided by the
chip's peak.  Counts follow the standard convention — matmul/conv FLOPs
only (2 x MACs), elementwise/norm/pool ignored, backward = 2 x forward so
a train step is 3 x forward.

Peak numbers are the published per-chip bf16 figures (the "How to Scale
Your Model" hardware table); MFU is reported against bf16 peak regardless
of compute dtype, which is conservative for fp32 runs.
"""

from __future__ import annotations

# Published per-chip dense bf16 peak FLOPs/s, keyed by the exact
# (lower-cased) ``jax.Device.device_kind`` string each chip reports.
# Exact keys on purpose: a substring table priced any unknown "v5 ..."
# kind as a v5p.
_PEAK_BF16: dict[str, float] = {
    "tpu v6 lite": 918e12,  # Trillium
    "tpu v6e": 918e12,
    "tpu v5 lite": 197e12,  # v5e
    "tpu v5e": 197e12,
    "tpu v5": 459e12,       # v5p reports the bare generation
    "tpu v5p": 459e12,
    "tpu v4": 275e12,
    "tpu v3": 123e12,
    "tpu v2": 45e12,
}


def chip_peak_flops(device_kind: str) -> float | None:
    """Per-chip bf16 peak for a ``jax.Device.device_kind`` string.  The
    CPU platform (``device_kind == "cpu"``) has no peak and returns None;
    any other kind missing from the table is an error — an MFU computed
    against a guessed peak is worse than none."""
    kind = device_kind.strip().lower()
    if kind == "cpu":
        return None
    try:
        return _PEAK_BF16[kind]
    except KeyError:
        raise ValueError(
            f"unknown accelerator device_kind {device_kind!r}: add its "
            f"published bf16 peak to tpudp.utils.flops._PEAK_BF16 "
            f"(known: {sorted(_PEAK_BF16)})") from None


def mfu(flops_per_step: float, sec_per_step: float,
        device_kind: str, n_devices: int = 1) -> float | None:
    """Achieved fraction of peak: ``flops / (time * n * peak)``.  None on
    the CPU platform; raises on an unknown accelerator kind."""
    peak = chip_peak_flops(device_kind)
    if peak is None or sec_per_step <= 0:
        return None
    return flops_per_step / (sec_per_step * n_devices * peak)


# --- per-model analytic counts (forward, per batch) ----------------------

def conv2d_flops(batch: int, h_out: int, w_out: int, c_in: int, c_out: int,
                 kh: int, kw: int) -> int:
    return 2 * batch * h_out * w_out * c_in * c_out * kh * kw


def dense_flops(batch: int, d_in: int, d_out: int) -> int:
    return 2 * batch * d_in * d_out


def vgg_fwd_flops(batch: int, variant: str = "VGG11", image_size: int = 32,
                  num_classes: int = 10) -> int:
    """Walk the variant's config table (tpudp.models.vgg.CONFIGS — the
    reference's ``_cfg``, ``src/Part 1/model.py:3-8``)."""
    from tpudp.models.vgg import CONFIGS

    h = image_size
    c_in = 3
    total = 0
    for v in CONFIGS[variant]:
        if v == "M":
            h //= 2
        else:
            total += conv2d_flops(batch, h, h, c_in, int(v), 3, 3)
            c_in = int(v)
    total += dense_flops(batch, c_in * h * h, num_classes)
    return total


def resnet_fwd_flops(batch: int, stage_sizes=(3, 4, 6, 3),
                     image_size: int = 224, num_classes: int = 1000,
                     width: int = 64) -> int:
    """Bottleneck-ResNet walk matching tpudp.models.resnet.ResNet: 7x7/2
    stem, 3x3/2 maxpool, stages of (1x1 -> 3x3 -> 1x1 x4) bottlenecks with
    a projection on each stage's first block."""
    h = image_size // 2  # stem conv stride 2
    total = conv2d_flops(batch, h, h, 3, width, 7, 7)
    h = (h + 1) // 2  # maxpool stride 2
    c_in = width
    for stage, num_blocks in enumerate(stage_sizes):
        w = width * (2 ** stage)
        for block in range(num_blocks):
            stride = 2 if (stage > 0 and block == 0) else 1
            h_out = h // stride
            total += conv2d_flops(batch, h, h, c_in, w, 1, 1)
            total += conv2d_flops(batch, h_out, h_out, w, w, 3, 3)
            total += conv2d_flops(batch, h_out, h_out, w, 4 * w, 1, 1)
            if block == 0:  # projection shortcut
                total += conv2d_flops(batch, h_out, h_out, c_in, 4 * w, 1, 1)
            c_in, h = 4 * w, h_out
    total += dense_flops(batch, c_in, num_classes)
    return total


def gpt2_fwd_flops(batch: int, seq_len: int, *, num_layers: int = 12,
                   d_model: int = 768, vocab_size: int = 50_257,
                   mlp_ratio: int = 4) -> int:
    """Per-layer matmuls (QKV 3d^2 + proj d^2 + MLP 2*ratio*d^2 per token)
    plus the quadratic attention score/value matmuls and the LM head."""
    tokens = batch * seq_len
    per_layer = dense_flops(tokens, d_model, 3 * d_model)      # qkv
    per_layer += dense_flops(tokens, d_model, d_model)         # out proj
    per_layer += 2 * dense_flops(tokens, d_model, mlp_ratio * d_model)
    per_layer += 2 * 2 * batch * seq_len * seq_len * d_model   # QK^T + AV
    return num_layers * per_layer + dense_flops(tokens, d_model, vocab_size)


def llama_fwd_flops(batch: int, seq_len: int, *, num_layers: int,
                    d_model: int, vocab_size: int, hidden: int,
                    num_heads: int, kv_heads: int) -> int:
    """LLaMA-family analytic MACs: q/wo at d^2, k/v shrunk by the GQA
    ratio, SwiGLU's three d*hidden matmuls, quadratic attention, and the
    untied LM head (tpudp/models/llama.py)."""
    tokens = batch * seq_len
    kv_dim = d_model * kv_heads // num_heads
    per_layer = dense_flops(tokens, d_model, d_model)       # wq
    per_layer += 2 * dense_flops(tokens, d_model, kv_dim)   # wk, wv
    per_layer += dense_flops(tokens, d_model, d_model)      # wo
    per_layer += 3 * dense_flops(tokens, d_model, hidden)   # gate, up, down
    per_layer += 2 * 2 * batch * seq_len * seq_len * d_model  # QK^T + AV
    return num_layers * per_layer + dense_flops(tokens, d_model, vocab_size)


def train_step_flops(fwd_flops: int) -> int:
    """Backward is ~2x forward (grad wrt activations + grad wrt weights)."""
    return 3 * fwd_flops


def pipeline_bubble_fraction(stages: int, n_microbatches: int,
                             interleave: int = 1) -> float:
    """Idle fraction of a 1F1B pipeline schedule: ``(P-1)/(M+P-1)`` for
    ``P`` stages and ``M`` microbatches — the fill/drain slots no
    microbatch occupies.  ``interleave=V`` virtual stages per device cut
    each ramp slot to ``1/V`` of a stage's work (Megatron's interleaved
    schedule): ``(P-1)/(V*M + P-1)``.

    Reported alongside MFU for pipeline bench rows so they are
    comparable to DP rows: a PP row's achievable MFU ceiling is
    ``(1 - bubble) * dp_mfu``, making a bubble-bound row distinguishable
    from a kernel-bound one.  Degenerates to 0.0 at a single stage.
    """
    if stages < 1 or n_microbatches < 1 or interleave < 1:
        raise ValueError(
            f"stages ({stages}), n_microbatches ({n_microbatches}) and "
            f"interleave ({interleave}) must all be >= 1")
    if stages == 1:
        return 0.0
    return (stages - 1) / (interleave * n_microbatches + stages - 1)


def xla_cost_flops(jitted_fn, *args) -> float | None:
    """XLA's own FLOPs estimate for a jitted function at these args — an
    independent cross-check of the analytic counts above (the two differ
    by design: XLA counts every op post-fusion, the analytic count only
    matmul/conv MACs).  Returns None when the backend reports no flops."""
    analysis = jitted_fn.lower(*args).compile().cost_analysis()
    flops = analysis.get("flops") if analysis else None
    return float(flops) if flops and flops > 0 else None
