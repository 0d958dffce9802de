"""openPangu-Ultra-MoE family: how a configuration file becomes the system's
model, the plain reference it is held to, and the counts its metrics divide
by.

The reference is the architecture as its ``config.json`` describes it
(FreedomIntelligence/openPangu-Ultra-MoE-718B, ``model_type``
``pangu_ultra_moe``), in straightforward ``jax.numpy`` and float32.  With
``h`` the residual stream, ``RMS`` an RMSNorm with a learned scale, no
projection with a bias, ``H`` heads:

  block:     h = h + RMS_post_attn(attn(RMS_in(h)));
             h = h + RMS_post_mlp(mlp(RMS_pre_mlp(h))); RMS_out and an
             untied head after the last block.
  attention: c_q = RMS(u W_qa); [q_nope | q_rope]_h = c_q W_qb;
             [c_kv | k_rope] = u W_kva; c_kv = RMS(c_kv);
             [k_nope | v]_h = c_kv W_kvb (the EXPANDED form: keys and
             values of every head exist; the system attends in the latent
             space instead and never forms them);
             causal softmax(([q_nope | rope(q_rope)] . [k_nope |
             rope(k_rope)]) / sqrt(192)), k_rope shared by the heads;
             W_o concat_h(p v).
  dense mlp (the first first_k_dense_replace layers):
             W_2 (silu(W_1 u) * W_3 u).
  experts:   s = sigmoid(W_g u) in float32; sel = top_k(s);
             w = 2.5 * s[sel] / (sum s[sel] + 1e-6);
             sum over the chosen experts HELD here of w_j E_j(u), plus
             E_shared(u), E(u) = W_2 (silu(W_1 u) * W_3 u).  Held: experts
             first_expert .. first_expert + n_routed_experts - 1 of
             num_experts_routed.

Departures from the published description: (1) what the absent experts
would add to a token is left out, as in the system (the chip's share of a
16-chip expert-parallel layer); (2) the vocabulary is the 19,200 rows held;
(3) the multi-token-prediction module is absent; (4) the rotary part is
rotate-half, the score scale 1/sqrt(192) with no YaRN factor, the router
has no groups and no bias (the configuration file's ``assumed``).

No kernel, no sort, no cache, no page: the expert layer is a loop over the
held experts with a mask, attention runs at most 1,024 queries and 16 heads
at a time so that a ``(heads, T, T)`` score tensor never exists whole, and every
weight matrix is upcast to float32 where it is used (the served tree is
bfloat16 and 9.8 GB; a float32 copy of it would be 19.7).  It reads the
parameter tree of ``tpudp/models/pangu.py`` and shares no code with
``tpudp/models``.  Call it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

_QUERY_BLOCK = 1024  # reference attention: queries at a time
_HEAD_BLOCK = 16  # and heads at a time


def build_model(config: dict, *, attn_impl: str = "dense"):
    """The system's model at the file's sizes, its parameters made in the
    type they are served in."""
    import jax.numpy as jnp

    from tpudp.models.pangu import Pangu, PanguConfig

    return Pangu(PanguConfig.from_dict(
        config, attn_impl=attn_impl,
        dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(config["serve"]["weight_dtype"])))


def init_input_shape(config: dict) -> tuple:
    return (1, 16)


# ------------------------------------------------------------------ counts


def _attention_params(c: dict) -> int:
    h, d = c["num_attention_heads"], c["hidden_size"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def held_share(c: dict) -> float:
    """Expected assignments a token to experts held here, of its top-k."""
    return (c["num_experts_per_tok"] * c["n_routed_experts"]
            / c["num_experts_routed"])


def serve_costs(config: dict) -> dict:
    """What serving one unit of the window's work (the serving driver's
    ``WORK``) costs at the file's sizes; ``perf/metrics/serve_mfu.py``
    prices a window with it.  Every entry is the LEAST a correct engine
    can do, so the share cannot pass 100%:

    * ``flops_per_token``: 2 x the matmul parameters a token row meets: in
      the dense layer attention and the dense SwiGLU (621.3 M), in an
      expert layer attention, router and shared expert (245.8 M) and the
      EXPECTED ``8 x 16 / 256`` held experts of 47.2 M (a row sent to an
      absent expert costs this chip nothing).  ``W_kvb`` counts once: the
      expanded form applies it to the token's latent, the absorbed form
      half to its query and half to its output.
    * ``flops_per_logit``: the untied head over the held rows.
    * ``flops_per_attended``: a query-key pair in the EXPANDED form,
      ``2 x 128 x (192 + 128)`` a layer; the absorbed form the engine
      runs pays ``2 x 128 x (576 + 512)``, and the cheaper one is the
      least.
    * ``bytes_per_run``: every weight once in the served type but the
      embedding table (gathered by row), the routed experts at the share
      32 active rows touch, ``1 - (1 - 8/256)**32`` = 63.8%: a decode run
      of the cell's closed loop has 40-55 active rows and touches more, a
      prefill chunk of 512 rows touches all 16.
    * ``bytes_per_cache_token``: ``kv_lora_rank + qk_rope_head_dim`` = 576
      values a layer in the compute type (1,152 B; the engine's pages pad
      the 64 rotary values to 128: 1,280)."""
    import jax.numpy as jnp

    c = config
    d, layers = c["hidden_size"], c["num_hidden_layers"]
    dense, sparse = c["first_k_dense_replace"], layers - c["first_k_dense_replace"]
    h = c["num_attention_heads"]
    wsize = jnp.dtype(c["serve"]["weight_dtype"]).itemsize
    csize = jnp.dtype(c["compute_dtype"]).itemsize
    attn, expert = _attention_params(c), _expert_params(c)
    around = attn + d * c["num_experts_routed"] \
        + c["n_shared_experts"] * expert  # of an expert layer, every token
    norms = layers * (4 * d + c["q_lora_rank"] + c["kv_lora_rank"]) + d
    touched = 1.0 - (1.0 - c["num_experts_per_tok"]
                     / c["num_experts_routed"]) ** 32
    return {
        "flops_per_token": 2 * (
            dense * (attn + 3 * d * c["intermediate_size"])
            + sparse * (around + held_share(c) * expert)),
        "flops_per_logit": 2 * c["vocab_size"] * d,
        "flops_per_attended": 2 * layers * h * (
            c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]),
        "bytes_per_run": wsize * (
            dense * (attn + 3 * d * c["intermediate_size"])
            + sparse * (around + touched * c["n_routed_experts"] * expert)
            + norms + c["vocab_size"] * d),
        "bytes_per_cache_token": csize * layers * (
            c["kv_lora_rank"] + c["qk_rope_head_dim"]),
    }


def serve_gmm_least_ms(config: dict, experts_touched: float,
                       rows_held: float, peak: dict) -> float:
    """Least milliseconds the ``moe_gmm`` calls of one engine step can
    take on a chip with these peaks (perf/harness/peaks.json): the larger
    of the bytes of the ``experts_touched`` held experts' three matrices,
    each read once in the served type, over the peak bytes/s, and the
    ``rows_held`` rows' three products over the peak FLOP/s.  Both counts
    are a step's, summed over its expert layers and its two programs."""
    import jax.numpy as jnp

    each = _expert_params(config)
    wsize = jnp.dtype(config["serve"]["weight_dtype"]).itemsize
    return 1e3 * max(experts_touched * each * wsize / peak["hbm_bytes_per_s"],
                     rows_held * 2 * each / peak["bf16_flops_per_s"])


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


def _rope(x, theta):
    """Rotate-half RoPE on ``(B, T, H, Dr)``: pair ``i`` of the two halves
    turns by ``t / theta**(2i/Dr)``."""
    import jax.numpy as jnp

    t, dr = x.shape[1], x.shape[-1]
    half = dr // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dr)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq  # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(p, u):
    return (_silu(u @ _f32(p["w1"]["kernel"]))
            * (u @ _f32(p["w3"]["kernel"]))) @ _f32(p["w2"]["kernel"])


def _attention(p, u, c):
    """Expanded MLA on ``u`` ``(B, T, d)``, causal."""
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    h, lat = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    c_q = _rms(u @ _f32(p["wq_a"]["kernel"]), p["q_norm"]["scale"], eps)
    q = (c_q @ _f32(p["wq_b"]["kernel"])).reshape(b, t, h, dn + dr)
    kv = u @ _f32(p["wkv_a"]["kernel"])
    c_kv = _rms(kv[..., :lat], p["kv_norm"]["scale"], eps)
    k_rope = _rope(kv[..., None, lat:], theta)  # (B, T, 1, dr)
    kvb = (c_kv @ _f32(p["wkv_b"]["kernel"])).reshape(b, t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_rope, (b, t, h, dr))], axis=-1)
    v = kvb[..., dn:]
    # the largest blocks within the two bounds that divide the extents
    bq = max(n for n in range(1, min(_QUERY_BLOCK, t) + 1) if t % n == 0)
    bh = max(n for n in range(1, min(_HEAD_BLOCK, h) + 1) if h % n == 0)
    key_pos = jnp.arange(t)

    def block(ij):
        i, j = ij // (h // bh), ij % (h // bh)
        qb = jax.lax.dynamic_slice(q, (0, i * bq, j * bh, 0),
                                   (b, bq, bh, dn + dr))
        kb = jax.lax.dynamic_slice_in_dim(k, j * bh, bh, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, j * bh, bh, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) / jnp.sqrt(float(dn + dr))
        seen = key_pos[None, :] <= (i * bq + jnp.arange(bq))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vb)

    o = jax.lax.map(block, jnp.arange((t // bq) * (h // bh)))
    o = o.reshape(t // bq, h // bh, b, bq, bh, dv)  # -> (b, t, h, dv)
    o = jnp.transpose(o, (2, 0, 3, 1, 4, 5)).reshape(b, t, h * dv)
    return o @ _f32(p["wo"]["kernel"])


def _expert_ffn(p, u, c, chosen):
    """The held experts' part of the layer on ``u`` ``(N, d)`` and the
    scores ``(N, routed)``.  ``chosen`` ``(N, k)`` replaces the
    reference's own top-k when given."""
    import jax
    import jax.numpy as jnp

    k, first = c["num_experts_per_tok"], c["first_expert"]
    s = jax.nn.sigmoid(u @ _f32(p["gate"]))
    if chosen is None:
        _, chosen = jax.lax.top_k(s, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * c["routed_scaling_factor"]
    y = jnp.zeros_like(u)
    for j in range(c["n_routed_experts"]):
        w_j = jnp.sum(jnp.where(chosen == first + j, w, 0.0), axis=-1)
        hidden = _silu(u @ _f32(p["w1"][j])) * (u @ _f32(p["w3"][j]))
        y = y + w_j[:, None] * (hidden @ _f32(p["w2"][j]))
    return y, s


def reference_forward(params, tokens, config: dict, routing=None):
    """``(B, T)`` tokens -> ``((B, T, vocab)`` float32 logits, the router
    scores ``(B*T, routed)`` of each expert layer in order``)``.
    ``routing``, one ``(B*T, k)`` array of routed-expert ids per expert
    layer, replaces the reference's own top-k (the system's choices: a
    bf16 stream flips a near tie, and a flip swaps an eighth of a layer's
    routed output)."""
    c, eps = config, config["rms_norm_eps"]
    h = _f32(params["wte"]["embedding"][tokens])
    scores = []
    for i in range(c["num_hidden_layers"]):
        blk = params[f"h_{i}"]
        a = _attention(blk["attn"], _rms(h, blk["rms_in"]["scale"], eps), c)
        h = h + _rms(a, blk["rms_post_attn"]["scale"], eps)
        u = _rms(h, blk["rms_pre_mlp"]["scale"], eps)
        if i < c["first_k_dense_replace"]:
            m = _swiglu(blk["mlp"], u)
        else:
            forced = None if routing is None else routing[len(scores)]
            y, s = _expert_ffn(blk["moe"], u.reshape(-1, u.shape[-1]), c,
                               forced)
            m = y.reshape(u.shape) + _swiglu(blk["shared"], u)
            scores.append(s)
        h = h + _rms(m, blk["rms_post_mlp"]["scale"], eps)
    h = _rms(h, params["rms_out"]["scale"], eps)
    return h @ _f32(params["lm_head"]["kernel"]), scores


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
