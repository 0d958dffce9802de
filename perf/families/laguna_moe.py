"""Laguna family: how a configuration file becomes the system's model, the
plain reference it is held to, and the counts its metrics divide by.

The reference is the architecture as its ``config.json`` describes it
(poolside/Laguna-XS.2, ``model_type`` ``laguna``), in straightforward
``jax.numpy`` and float32.  With ``h`` the residual stream, ``u =
RMSNorm(h)`` (learned scale, eps ``rms_norm_eps``), no projection with a
bias, ``H_l`` the query heads of layer ``l``, 8 KV heads of 128:

  block:     h = h + attn(RMSNorm_in(h)); h = h + mlp(RMSNorm_mlp(h));
             RMSNorm_out and an untied head after the last block.
  attention: q = u W_q (H_l x 128), k = u W_k, v = u W_v (8 x 128); K and
             V repeated to the query heads (query head j reads KV head
             j // (H_l / 8)); softmax(q k^T / sqrt(128) + mask), the mask a
             dense (T, T) one: causal, and on a sliding_attention layer
             also q_pos - k_pos < sliding_window; o_j = o_j *
             sigmoid(u W_g)_j (one gate a head); W_o concat_j(o_j).
  RoPE:      rotate-half on the first rot = partial_rotary_factor x 128
             dimensions of every query and key head, the rest pass.
             sliding layers: angle t x theta^(-2i/rot).  full layers
             (YaRN): inv_extra_i = theta^(-2i/rot), inv_inter_i =
             inv_extra_i / factor, corr(n) = rot ln(orig / (2 pi n)) /
             (2 ln theta), low = floor(corr(beta_fast)), high =
             ceil(corr(beta_slow)) clipped to [0, rot - 1], ramp_i =
             clip((i - low) / (high - low), 0, 1), inv_freq_i = inv_inter_i
             ramp_i + inv_extra_i (1 - ramp_i); cos and sin times
             attention_factor.
  dense mlp (mlp_layer_types "dense"): W_2 (silu(W_1 u) * W_3 u).
  experts:   s = sigmoid(W_r u) in float32 over all num_experts; sel =
             top_k(s); w = 2.5 * s[sel] / (sum s[sel] + 1e-6), applied to
             the experts' OUTPUT; sum_j w_j E_j(u) + E_shared(u), E(u) =
             W_2 (silu(W_1 u) * W_3 u).  Every expert is held.

Departures from the published description: (1) the file's five layers are
published layers 0-4, one pipeline stage of eight, with the head applied
after them so that the stage emits tokens; (2) what the configuration file
lists under ``assumed``: the gate's form (``gating: true`` read as one
sigmoid gate a head), the router's (sigmoid scores, normalised, no groups,
no bias), rotate-half.

No kernel, no sort, no cache, no page: the expert layer is a masked loop
over the experts, attention runs at most 1,024 queries at a time so that a
``(heads, T, T)`` score tensor never exists whole, the head a block of
vocabulary columns at a time, and every weight matrix is upcast to float32
where it is used (the served tree is bfloat16 and 7.7 GB and runs beside
5.1 GB of pages).  It reads the parameter tree of ``tpudp/models/laguna.py``
and shares no code with ``tpudp/models``.  Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

_QUERY_BLOCK = 1024  # reference attention: queries at a time
_HEAD_COLUMNS = 12544  # the head: vocabulary columns at a time


def build_model(config: dict):
    """The system's model at the file's sizes, its parameters made in the
    type they are served in."""
    import jax.numpy as jnp

    from tpudp.models.laguna import Laguna, LagunaConfig

    return Laguna(LagunaConfig.from_dict(
        config, dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["serve"]["weight_dtype"])))


def init_input_shape(config: dict) -> tuple:
    return (1, 16)


# ------------------------------------------------------------------ counts


def _attention_params(c: dict, layer: int) -> int:
    """Matmul parameters of one attention layer, gate included."""
    d, dh = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads_per_layer"][layer], c["num_key_value_heads"]
    return d * h * dh * 2 + d * kv * dh * 2 + d * h


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _layers(c: dict, key: str, value: str) -> list[int]:
    return [i for i, kind in enumerate(c[key]) if kind == value]


def _window_layers(c: dict) -> list[int]:
    return _layers(c, "layer_types", "sliding_attention")


def parameters_held(config: dict) -> int:
    """Every parameter of the file's model, norms included."""
    c, d = config, config["hidden_size"]
    total = 2 * c["vocab_size"] * d + d  # embedding, head, rms_out
    for i in range(c["num_hidden_layers"]):
        total += _attention_params(c, i) + 2 * d
        if c["mlp_layer_types"][i] == "dense":
            total += 3 * d * c["intermediate_size"]
        else:
            total += (d * c["num_experts"]
                      + c["num_experts"] * _expert_params(c)
                      + 3 * d * c["shared_expert_intermediate_size"])
    return total


def serve_costs(config: dict) -> dict:
    """What serving one unit of the window's work (the serving driver's
    ``WORK``) costs at the file's sizes; ``perf/metrics/serve_mfu.py``
    prices a window with it.  Every entry is the LEAST a correct engine
    can do, so the share cannot pass 100%:

    * ``flops_per_token``: 2 x the matmul parameters a token row meets:
      every attention layer's projections and gate, the dense SwiGLU, and
      in an expert layer the router, the shared expert and the top-8
      experts (every expert is held, so a row costs all 8).
    * ``flops_per_logit``: the untied head.
    * ``flops_per_attended`` and ``bytes_per_cache_token`` price the
      FULL-attention layers only: ``2 x 2 x heads x 128`` a query-key
      pair a layer and ``2 x 8 x 128`` values a cached token a layer.
      The driver's ``cache_tokens`` and ``attended`` count a request's
      whole depth, which a sliding layer does not read: its reads are
      bounded by the window (at most 512 + a page's slack of a slot's
      depth, under 6% of a decode run's bytes at this cell's depths) and
      are LEFT OUT, so the share stays a lower bound;
      ``paged_window_roofline`` prices them from the engine's own
      counters.
    * ``bytes_per_run``: every weight once in the served type but the
      embedding table (gathered by row), the routed experts at the share
      32 active rows touch, ``1 - (1 - 8/256)**32`` = 63.8%: a decode run
      of the cell's closed loop has 40-55 active rows and touches more, a
      prefill chunk of 512 rows touches all 256."""
    import jax.numpy as jnp

    c, d = config, config["hidden_size"]
    wsize = jnp.dtype(c["serve"]["weight_dtype"]).itemsize
    csize = jnp.dtype(c["compute_dtype"]).itemsize
    layers = range(c["num_hidden_layers"])
    dense = len(_layers(c, "mlp_layer_types", "dense"))
    sparse = len(_layers(c, "mlp_layer_types", "sparse"))
    full = _layers(c, "layer_types", "full_attention")
    attn = sum(_attention_params(c, i) for i in layers)
    around = d * c["num_experts"] + 3 * d * c["shared_expert_intermediate_size"]
    expert = _expert_params(c)
    norms = (2 * c["num_hidden_layers"] + 1) * d
    touched = 1.0 - (1.0 - c["num_experts_per_tok"] / c["num_experts"]) ** 32
    return {
        "flops_per_token": 2 * (
            attn + dense * 3 * d * c["intermediate_size"]
            + sparse * (around + c["num_experts_per_tok"] * expert)),
        "flops_per_logit": 2 * c["vocab_size"] * d,
        "flops_per_attended": 2 * 2 * c["head_dim"] * sum(
            c["num_attention_heads_per_layer"][i] for i in full),
        "bytes_per_run": wsize * (
            attn + dense * 3 * d * c["intermediate_size"]
            + sparse * (around + touched * c["num_experts"] * expert)
            + norms + c["vocab_size"] * d),
        "bytes_per_cache_token": csize * len(full) * 2 * (
            c["num_key_value_heads"] * c["head_dim"]),
    }


def serve_gmm_least_ms(config: dict, experts_touched: float,
                       rows_held: float, peak: dict) -> float:
    """Least milliseconds the ``moe_gmm`` calls of one engine step can
    take on a chip with these peaks (perf/harness/peaks.json): the larger
    of the bytes of the ``experts_touched`` experts' three matrices, each
    read once in the served type, over the peak bytes/s, and the
    ``rows_held`` rows' three products over the peak FLOP/s.  Both counts
    are a step's, summed over its expert layers and its two programs."""
    import jax.numpy as jnp

    each = _expert_params(config)
    wsize = jnp.dtype(config["serve"]["weight_dtype"]).itemsize
    return 1e3 * max(experts_touched * each * wsize / peak["hbm_bytes_per_s"],
                     rows_held * 2 * each / peak["bf16_flops_per_s"])


def window_attn_least_ms(config: dict, rows_read: float, pairs: float,
                         peak: dict) -> float:
    """Least milliseconds the sliding-window layers' attention calls can
    take: ``rows_read`` cached token rows (the engine's
    ``window_rows_read``: the keys inside the window of every call, summed
    over the window layers), K and V of ``8 x 128`` values each in the
    compute type, over the peak bytes/s; or ``pairs`` query-key pairs
    (``window_pairs``, summed over the window layers likewise) at ``2 x 2
    x 128`` operations a head over the peak FLOP/s; whichever is larger.
    The window layers all have the same head count in a published
    pattern; the mean is taken where they do not."""
    import jax.numpy as jnp

    c = config
    csize = jnp.dtype(c["compute_dtype"]).itemsize
    row = 2 * c["num_key_value_heads"] * c["head_dim"] * csize
    heads = [c["num_attention_heads_per_layer"][i] for i in _window_layers(c)]
    flops = pairs * 2 * 2 * c["head_dim"] * sum(heads) / max(len(heads), 1)
    return 1e3 * max(rows_read * row / peak["hbm_bytes_per_s"],
                     flops / peak["bf16_flops_per_s"])


def window_pool_pages(config: dict, engine: dict) -> int:
    """Pages of the engine's window pool: ``num_slots x (ceil(window /
    page) + 1)`` (``tpudp/serve/engine.py::_build_page_pools``)."""
    page = engine["prefill_chunk"]
    return engine["num_slots"] * (-(-config["sliding_window"] // page) + 1)


# ------------------------------------------------------------- reference


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(scale)


def _silu(x):
    import jax.numpy as jnp

    return x / (1.0 + jnp.exp(-x))


def inv_freq(rope: dict, head_dim: int):
    """The inverse frequencies of one ``rope_parameters`` entry and the
    factor its ``cos`` and ``sin`` are multiplied by, from the formula in
    the module docstring."""
    import jax.numpy as jnp

    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    i = jnp.arange(rot // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / rot)
    if rope.get("rope_type", "default") == "default":
        return extra, 1.0
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def corr(n):
        return rot * math.log(orig / (2.0 * math.pi * n)) / (
            2.0 * math.log(theta))

    low = max(math.floor(corr(rope.get("beta_fast", 32.0))), 0)
    high = min(math.ceil(corr(rope.get("beta_slow", 1.0))), rot - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp),
            float(rope.get("attention_factor", 0.1 * math.log(factor) + 1.0)))


def _rope(x, rope: dict):
    """Rotate-half RoPE on ``(B, T, H, dh)`` at positions ``0..T-1``."""
    import jax.numpy as jnp

    freq, factor = inv_freq(rope, x.shape[-1])
    half = freq.shape[0]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


def _swiglu(p, u):
    return (_silu(u @ _f32(p["w1"]["kernel"]))
            * (u @ _f32(p["w3"]["kernel"]))) @ _f32(p["w2"]["kernel"])


def _attention(p, u, c, layer: int):
    """One attention layer on ``u`` ``(B, T, d)``."""
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    h = c["num_attention_heads_per_layer"][layer]
    kv, dh = c["num_key_value_heads"], c["head_dim"]
    kind = c["layer_types"][layer]
    rope = c["rope_parameters"][kind]
    q = _rope((u @ _f32(p["wq"]["kernel"])).reshape(b, t, h, dh), rope)
    k = _rope((u @ _f32(p["wk"]["kernel"])).reshape(b, t, kv, dh), rope)
    v = (u @ _f32(p["wv"]["kernel"])).reshape(b, t, kv, dh)
    k = jnp.repeat(k, h // kv, axis=2)  # query head j reads KV head j // g
    v = jnp.repeat(v, h // kv, axis=2)
    bq = max(n for n in range(1, min(_QUERY_BLOCK, t) + 1) if t % n == 0)
    key_pos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(float(dh))
        ahead = (i * bq + jnp.arange(bq))[:, None] - key_pos[None, :]
        seen = ahead >= 0
        if kind == "sliding_attention":
            seen = seen & (ahead < c["sliding_window"])
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(t // bq))  # (t/bq, b, bq, h, dh)
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h, dh)
    o = o * jax.nn.sigmoid(u @ _f32(p["wg"]["kernel"]))[..., None]
    return o.reshape(b, t, h * dh) @ _f32(p["wo"]["kernel"])


def _expert_ffn(p, u, c, chosen):
    """The routed experts' part of the layer on ``u`` ``(N, d)`` and the
    scores ``(N, experts)``.  ``chosen`` ``(N, k)`` replaces the
    reference's own top-k when given."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ _f32(p["gate"]))
    if chosen is None:
        _, chosen = jax.lax.top_k(s, c["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * c["moe_routed_scaling_factor"]

    def expert(j, y):
        w_j = jnp.sum(jnp.where(chosen == j, w, 0.0), axis=-1)
        w1, w3, w2 = (_f32(jax.lax.dynamic_index_in_dim(
            p[name], j, keepdims=False)) for name in ("w1", "w3", "w2"))
        return y + w_j[:, None] * ((_silu(u @ w1) * (u @ w3)) @ w2)

    return jax.lax.fori_loop(0, c["num_experts"], expert,
                             jnp.zeros_like(u)), s


def _head(h, kernel):
    """``h @ kernel`` a block of vocabulary columns at a time, so that the
    float32 copy of the head's matrix never exists whole."""
    import jax
    import jax.numpy as jnp

    vocab = kernel.shape[1]
    if vocab % _HEAD_COLUMNS:
        return h @ _f32(kernel)
    out = jax.lax.map(
        lambda j: h @ _f32(jax.lax.dynamic_slice_in_dim(
            kernel, j * _HEAD_COLUMNS, _HEAD_COLUMNS, axis=1)),
        jnp.arange(vocab // _HEAD_COLUMNS))  # (blocks, ..., columns)
    return jnp.moveaxis(out, 0, -2).reshape(*h.shape[:-1], vocab)


def reference_forward(params, tokens, config: dict, routing=None):
    """``(B, T)`` tokens -> ``((B, T, vocab)`` float32 logits, the router
    scores ``(B*T, experts)`` of each expert layer in order``)``.
    ``routing``, one ``(B*T, k)`` array of expert ids per expert layer,
    replaces the reference's own top-k (the system's choices: a bf16
    stream flips a near tie, and a flip swaps an eighth of a layer's
    routed output)."""
    c, eps = config, config["rms_norm_eps"]
    h = _f32(params["wte"]["embedding"][tokens])
    scores = []
    for i in range(c["num_hidden_layers"]):
        blk = params[f"h_{i}"]
        h = h + _attention(blk["attn"], _rms(h, blk["rms_in"]["scale"], eps),
                           c, i)
        u = _rms(h, blk["rms_mlp"]["scale"], eps)
        if c["mlp_layer_types"][i] == "dense":
            h = h + _swiglu(blk["mlp"], u)
            continue
        forced = None if routing is None else routing[len(scores)]
        y, s = _expert_ffn(blk["moe"], u.reshape(-1, u.shape[-1]), c, forced)
        h = h + y.reshape(u.shape) + _swiglu(blk["shared"], u)
        scores.append(s)
    h = _rms(h, params["rms_out"]["scale"], eps)
    return _head(h, params["lm_head"]["kernel"]), scores


def reference_logits(params, tokens, config: dict):
    """The logits alone, the reference routing itself (what the serving
    driver's free-routing oracle reads)."""
    return reference_forward(params, tokens, config)[0]


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
