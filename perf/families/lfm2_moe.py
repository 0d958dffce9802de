"""LFM2-MoE family: how a configuration file becomes the system's model, the
plain reference it is held to, and the counts its metrics divide by.

The reference is the architecture as its ``config.json`` describes it
(LiquidAI LFM2-8B-A1B, ``model_type`` ``lfm2_moe``), in straightforward
``jax.numpy`` and float32.  With ``h`` the residual stream and ``RMS`` an
RMSNorm with a learned scale, no projection with a bias:

  block i:  h = h + op_i(RMS(h));  h = h + ffn_i(RMS(h));  RMS and the tied
            embedding after the last block.
  conv:     B, C, x = split3(W_in u); z = B * x;
            c_t = sum_j w[:, j] * z_{t-2+j} (depthwise, causal, zero before
            the sequence); W_out (C * c).
  attention: q, k, v = W_q u, W_k u, W_v u; q = rope(RMS_q(q)),
            k = rope(RMS_k(k)) (RMSNorm over each head, rotate-half RoPE);
            causal softmax at 1/sqrt(head), each KV head serving
            heads / kv_heads query heads; W_o o.
  dense ffn (the first num_dense_layers): W_2 (silu(W_1 u) * W_3 u).
  experts:  s = sigmoid(W_g u); sel = top_k(s + b); w = s[sel] /
            (sum s[sel] + 1e-6) * routed_scaling_factor;
            sum over the chosen experts HELD here of w_j * W2_e (silu(W1_e u)
            * W3_e u).  Held: experts first_expert .. first_expert +
            num_experts - 1 of num_experts_routed.  What the others would
            add is left out, as in the system (the chip's share).

No kernel, no sort, no cache: the expert layer is a loop over the held
experts with a mask, attention runs a block of queries at a time so that a
``(heads, T, T)`` score tensor never exists whole.  It reads the parameter
tree of ``tpudp/models/lfm2.py`` and shares no code with ``tpudp/models``.
Call it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import re

from perf.harness import flops

_QUERY_BLOCK = 1024  # reference attention: queries at a time


def build_model(config: dict, *, attn_impl: str = "dense"):
    """The system's model at the file's sizes."""
    import jax.numpy as jnp

    from tpudp.models.lfm2 import Lfm2, Lfm2Config

    train = config.get("train", {})
    return Lfm2(Lfm2Config.from_dict(
        config, attn_impl=attn_impl, moe_impl=train.get("moe_impl", "gmm"),
        remat=bool(train.get("remat", False)),
        dtype=jnp.dtype(config["compute_dtype"])))


def init_input_shape(config: dict) -> tuple:
    return (1, 16)


# ------------------------------------------------------------------ counts


def held_share(config: dict) -> float:
    """Expected assignments a token to experts held here, of its top-k."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_experts_routed"])


def fwd_flops_per_token(config: dict, seq_len: int) -> float:
    """Matmul operations one token's forward pass requires: causal
    attention at the half a causal model needs, the experts at the
    EXPECTED ``held_share`` assignments a token, nothing recomputed."""
    d = config["hidden_size"]
    dh = d // config["num_attention_heads"]
    kv_width = config["num_key_value_heads"] * dh
    total = 0.0
    for i, kind in enumerate(config["layer_types"]):
        if kind == "conv":
            total += flops.dense_flops(1, d, 3 * d) + flops.dense_flops(1, d, d)
        else:
            total += 2 * flops.dense_flops(1, d, d)  # q, o
            total += 2 * flops.dense_flops(1, d, kv_width)  # k, v
            total += 2 * 2 * (seq_len + 1) / 2 * d  # QK^T and AV, causal
        if i < config["num_dense_layers"]:
            total += 3 * flops.dense_flops(1, d, config["intermediate_size"])
        else:
            total += flops.dense_flops(1, d, config["num_experts_routed"])
            total += held_share(config) * 3 * flops.dense_flops(
                1, d, config["moe_intermediate_size"])
    return total + flops.dense_flops(1, d, config["vocab_size"])


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward + backward operations for one sample (one token) at the
    cell's sequence length."""
    return flops.train_step_flops(fwd_flops_per_token(config,
                                                      traffic["seq_len"]))


def grouped_product_counts(config: dict, traffic: dict) -> tuple[float, float]:
    """``(operations, bytes)`` one grouped product of the expert layer
    needs at the cell's sizes: ``M`` = the EXPECTED rows of the held
    experts (tokens a step x ``held_share``), times ``hidden_size`` x
    ``moe_intermediate_size``.  All nine products of a layer (three
    forward, three data gradients, three weight gradients) have these
    operations; the bytes are each operand once in bf16 (the weight
    gradient's float32 output is counted at bf16 too: the lower bound)."""
    m = traffic["per_chip_batch"] * traffic["seq_len"] * held_share(config)
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return (2.0 * m * d * f,
            2.0 * (m * (d + f) + config["num_experts"] * d * f))


def kernel_roofline_share(run, kernel: str) -> float | None:
    """Percent of its roofline a grouped-product kernel reaches in the
    traced window: calls seen x the least time a call can take (the
    larger of operations / peak FLOP/s and bytes / peak bytes/s,
    :func:`grouped_product_counts`, perf/harness/peaks.json) over the
    calls' device seconds.  The calls are the Mosaic operations whose HLO
    instruction is ``kernel`` or ``kernel.<n>``, as
    perf/harness/layers.py::kernel_ms finds them.  None where there is no
    trace or no such call."""
    from perf.harness.peaks import peaks
    from perf.harness.trace import WINDOW_SPAN

    form = run.trace_form
    if not form or not form.get("devices"):
        return None
    windows = [h for h in form.get("host", []) if h[0] == WINDOW_SPAN]
    if not windows:
        return None
    w0 = windows[-1][1]
    w1 = w0 + windows[-1][2]
    named = re.compile(re.escape(kernel) + r"(\.\d+)? ")
    calls, seconds = 0, 0.0
    for ops in form["devices"].values():
        for label, start, dur, is_kernel, _op in ops:
            if (is_kernel and start < w1 and start + dur > w0
                    and named.match(label)):
                calls += 1
                seconds += dur
    if not calls or seconds <= 0:
        return None
    ops_needed, bytes_needed = grouped_product_counts(run.cell.config,
                                                      run.cell.traffic)
    peak = peaks(run.device_kind)
    least = max(ops_needed / peak["bf16_flops_per_s"],
                bytes_needed / peak["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds


# ------------------------------------------------------------- reference


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """Rotate-half RoPE on ``(B, T, H, Dh)``: pair ``i`` of the two halves
    turns by ``t / theta**(2i/Dh)``."""
    import jax.numpy as jnp

    t, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq  # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _conv_op(p, u, config):
    import jax.numpy as jnp

    taps = config["conv_L_cache"]
    gate_b, gate_c, x = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    z = gate_b * x
    t = z.shape[1]
    c = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads z this many steps back
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :t - back]], axis=1)
        c = c + p["conv_w"][:, j] * shifted
    return (gate_c * c) @ p["out_proj"]["kernel"]


def _attention_op(p, u, config):
    import jax
    import jax.numpy as jnp

    b, t, d = u.shape
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = d // h
    eps, theta = config["norm_eps"], float(config["rope_theta"])
    q = (u @ p["wq"]["kernel"]).reshape(b, t, h, dh)
    k = (u @ p["wk"]["kernel"]).reshape(b, t, kv, dh)
    v = (u @ p["wv"]["kernel"]).reshape(b, t, kv, dh)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), theta)
    q = q.reshape(b, t, kv, h // kv, dh)  # query head j reads KV head j // g
    bq = min(_QUERY_BLOCK, t)
    assert t % bq == 0, (t, bq)
    key_pos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("bqkgd,bmkd->bkgqm", qb, k) / jnp.sqrt(float(dh))
        seen = key_pos[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bkgqm,bmkd->bqkgd", jax.nn.softmax(s, axis=-1), v)

    # checkpoint: a gradient recomputes a block's scores and never holds
    # all the blocks' at once (the same numbers)
    o = jax.lax.map(jax.checkpoint(block),
                    jnp.arange(t // bq))  # (nb, B, bq, kv, g, dh)
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, d)
    return o @ p["wo"]["kernel"]


def _expert_ffn(p, u, config, chosen):
    """The held experts' part of the layer on ``u`` ``(N, d)`` and the
    biased scores ``(N, routed)``.  ``chosen`` ``(N, k)`` replaces the
    reference's own top-k when given."""
    import jax
    import jax.numpy as jnp

    k, first = config["num_experts_per_tok"], config["first_expert"]
    s = jax.nn.sigmoid(u @ p["gate"])
    biased = s + p["expert_bias"] if config["use_expert_bias"] else s
    if chosen is None:
        _, chosen = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * config["routed_scaling_factor"]
    y = jnp.zeros_like(u)
    for j in range(config["num_experts"]):
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), axis=-1)
        hidden = _silu(u @ p["w1"][j]) * (u @ p["w3"][j])
        y = y + w_j[:, None] * (hidden @ p["w2"][j])
    return y, biased


def _block(blk, h, config: dict, kind: str, dense: bool, forced):
    """One block on the residual stream ``h``: ``(h, biased scores or
    None)``."""
    eps = config["norm_eps"]
    u = _rms(h, blk["rms_op"]["scale"], eps)
    h = h + (_conv_op(blk["conv"], u, config) if kind == "conv"
             else _attention_op(blk["attn"], u, config))
    u = _rms(h, blk["rms_ffn"]["scale"], eps)
    if dense:
        return h + (_silu(u @ blk["w1"]["kernel"])
                    * (u @ blk["w3"]["kernel"])) @ blk["w2"]["kernel"], None
    y, biased = _expert_ffn(blk["moe"], u.reshape(-1, u.shape[-1]), config,
                            forced)
    return h + y.reshape(h.shape), biased


def reference_logits(params, tokens, config: dict, routing=None, *,
                     remat: bool = False):
    """``(B, T)`` tokens -> ``((B, T, vocab)`` float32 logits, the biased
    router scores ``(B*T, routed)`` of each expert layer in order``)``.
    ``routing``, one ``(B*T, k)`` array of routed-expert ids per expert
    layer, replaces the reference's own top-k.  ``remat`` keeps only each
    block's input for a gradient (the same numbers: a float32 backward
    pass over 8,192 tokens otherwise holds every block's activations)."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    h = p["wte"]["embedding"][tokens]
    scores = []
    for i, kind in enumerate(config["layer_types"]):
        dense = i < config["num_dense_layers"]
        forced = None if dense or routing is None else routing[len(scores)]

        def block(blk, h, forced, kind=kind, dense=dense):
            return _block(blk, h, config, kind, dense, forced)

        h, biased = (jax.checkpoint(block) if remat else block)(
            p[f"h_{i}"], h, forced)
        if not dense:
            scores.append(biased)
    h = _rms(h, p["rms_out"]["scale"], config["norm_eps"])
    return h @ p["wte"]["embedding"].T, scores


def _token_losses(logits, targets):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def reference_token_losses(params, tokens, targets, config: dict):
    """``(B, T)`` next-token cross entropies of the reference forward, the
    reference routing itself."""
    return _token_losses(reference_logits(params, tokens, config)[0], targets)


def reference_token_losses_routed(params, tokens, targets, config: dict,
                                  routing):
    """The same with the expert choices given, and the reference's own
    biased scores on the inputs those choices lead to."""
    logits, scores = reference_logits(params, tokens, config, routing)
    return _token_losses(logits, targets), scores


def system_token_losses(model, params, tokens, targets):
    """The same ``(B, T)`` cross entropies through the system's model, as
    ``tpudp/train.py``'s ``loss_fn`` takes them before its mean."""
    import optax

    logits = model.apply({"params": params}, tokens, train=True)
    return optax.softmax_cross_entropy_with_integer_labels(logits, targets)


def system_routed(model, params, tokens, targets):
    """Through the system's model as ``loss_fn`` differentiates it (the
    grouped kernels' and flash attention's custom VJPs, the gather
    pair's, remat), from ONE program: the ``(B, T)`` cross entropies, the
    experts it chose, and the gradient of the cross entropies' mean with
    respect to every parameter.  One program, because two compiled
    programs round differently and break near ties differently: choices
    read from a forward-only program are not the ones the gradient's
    program made (on the chip that alone read 0.2 at the last router)."""
    import jax
    import jax.numpy as jnp
    import optax

    def mean_loss(p):
        logits, sown = model.apply({"params": p}, tokens, train=True,
                                   mutable=["intermediates"])
        losses = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                                 targets)
        # one (B*T, k) array per expert layer, in layer order, from what
        # the expert layer sows
        layers = sown["intermediates"]
        chosen = [layers[name]["moe"]["moe_chosen"][0]
                  for name in sorted(layers, key=lambda n: int(n.split("_")[1]))
                  if "moe" in layers[name]]
        return jnp.mean(losses), (losses, chosen)

    (_, (losses, chosen)), grads = jax.value_and_grad(
        mean_loss, has_aux=True)(params)
    return losses, chosen, grads


def reference_grad_gaps(params, tokens, targets, config: dict, routing, got):
    """The reference's gradient of the same mean with the expert choices
    given, against ``got`` (:func:`system_routed`'s): the parameter tree with
    ``|got - want| / |want|`` (2-norms) for each leaf; a leaf the
    reference gives no gradient (``expert_bias``) must have none.  The
    reference's gradients live only inside this function."""
    import jax
    import jax.numpy as jnp

    want = jax.grad(lambda p: jnp.mean(_token_losses(reference_logits(
        p, tokens, config, routing, remat=True)[0], targets)))(params)
    return jax.tree.map(
        lambda g, w: jnp.linalg.norm((g - w).ravel())
        / jnp.maximum(jnp.linalg.norm(w.ravel()), 1e-30), got, want)


def choice_gap(scores, chosen):
    """How far the system's choice is from a valid top-k of the
    reference's scores: over every token and every expert the system
    chose, the largest ``(reference's k-th best score) - (reference's
    score of the chosen expert)``.  Zero or less when the choice IS the
    reference's top-k; a tie broken the other way reads the tie's width."""
    import jax
    import jax.numpy as jnp

    kth = jax.lax.top_k(scores, chosen.shape[-1])[0][:, -1:]
    return jnp.max(kth - jnp.take_along_axis(scores, chosen, axis=-1))
