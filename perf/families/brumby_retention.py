"""Brumby family: how a configuration file becomes the system's model, the
plain reference it is held to, and the counts its metrics divide by.

The reference is the architecture as its configuration file describes it
(manifestai/Brumby-14B-Base, ``model_type`` ``brumby``: power-retention
layers of degree 2 in place of attention, arXiv:2507.04239), in
straightforward ``jax.numpy`` and float32.  With ``h`` the residual stream,
``u = RMSNorm(h)`` (learned scale, eps ``rms_norm_eps``), no projection
with a bias, 40 query heads over 8 KV heads of 128:

  block:     h = h + W_o concat_i(y_i); h = h + W_2 (silu(W_1 u) * W_3 u)
             with u = RMSNorm_mlp(h); RMSNorm_out and an untied head after
             the last block.
  retention: q = u W_q, k = u W_k, v = u W_v; q and k each through a
             per-head RMSNorm (learned scale) and rotate-half RoPE at
             rope_theta (pair i turns by t / theta^(2i/128)); the gate
             log g_t = logsigmoid(u_t W_g + b_h) a KV head, b_h = log(tau_h
             - 1), tau_h = gate_timescale x 2^h; G_t = sum_{r<=t} log g_r;
             for query head i of KV head h = i // 5:
               A[t, s] = (q_t . k_s / sqrt(128))^2 exp(G_t - G_s), s <= t
               y_t = sum_s A[t, s] v_s / (sum_s A[t, s] + retention_eps)

Departures from the published description: (1) the file's four layers are
published layers 0-3, one pipeline stage of ten, with the head applied after
them so that the stage emits tokens; (2) what the configuration file lists
under ``assumed``: the normaliser and its eps, the gate's form, QK norm,
RoPE on q and k.

No kernel, no state, no cache: the quadratic form, a block of queries at a
time against every key (a ``(heads, T, T)`` weight tensor never exists
whole), the head a block of vocabulary columns at a time, and every weight
matrix upcast to float32 where it is used (the served tree is bfloat16 and
5.75 GB and runs beside 6.6 GB of state).  It reads the parameter tree of
``tpudp/models/brumby.py`` and shares no code with ``tpudp/models``.  Call
it under ``jax.default_matmul_precision("highest")``.

Beside it, :func:`reference_fold`: one fold of the recurrent form, in
float64 on the host, from a state and the rows folded into it; the check
holds a served slot's float32 state to it.
"""

from __future__ import annotations

import math
import os

from perf.harness.cells import CellError

# the system under test beside this checkout's benchmark files
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.isfile(os.path.join(_ROOT, "tpudp", "models", "brumby.py")):
    raise CellError("the system under test has no power-retention family "
                    "(tpudp/models/brumby.py)")

_QUERY_BLOCK = 512  # reference retention: queries at a time
_HEAD_COLUMNS = 18992  # the head: vocabulary columns at a time (151,936 / 8)


def build_model(config: dict):
    """The system's model at the file's sizes, its parameters made in the
    type they are served in."""
    import jax.numpy as jnp

    from tpudp.models.brumby import Brumby, BrumbyConfig

    serve = config["serve"]
    return Brumby(BrumbyConfig.from_dict(
        config, dtype=jnp.dtype(config["compute_dtype"]),
        param_dtype=jnp.dtype(serve["weight_dtype"]),
        fold_tokens=serve["fold_tokens"]))


def init_input_shape(config: dict) -> tuple:
    return (1, 16)


# ------------------------------------------------------------------ counts


def features(config: dict) -> int:
    """``D``: entries of the symmetric degree-2 feature map a KV head."""
    dh = config["head_dim"]
    return dh * (dh + 1) // 2


def layer_parameters(config: dict) -> int:
    """Every parameter of one block: W_q, W_k, W_v, W_o, the two head
    norms, W_g, the SwiGLU's three matrices, the two norms."""
    c, d, dh = config, config["hidden_size"], config["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return (2 * d * h * dh + 2 * d * kv * dh + 2 * dh + d * kv
            + 3 * d * c["intermediate_size"] + 2 * d)


def parameters_held(config: dict) -> int:
    """Every parameter of the file's model, norms included."""
    c = config
    return (c["num_hidden_layers"] * layer_parameters(c)
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def _matmul_parameters(c: dict) -> int:
    d, dh = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return (2 * d * h * dh + 2 * d * kv * dh + d * kv
            + 3 * d * c["intermediate_size"])


def serve_costs(config: dict) -> dict:
    """What serving one unit of the window's work (the serving driver's
    ``WORK``) costs at the file's sizes; ``perf/metrics/serve_mfu.py``
    prices a window with it.  Every entry is the LEAST a correct engine can
    do, so the share cannot pass 100%:

    * ``flops_per_token``: 2 x the matmul parameters a token row meets in
      the blocks, plus retention's own: every query head reads its KV
      head's state and normaliser (``2 x D x (128 + 1)``) and every KV head
      folds the token's ``phi(k)`` into them once (the same again), in
      every layer.  The tail's quadratic part is left out.
    * ``flops_per_logit``: the untied head.
    * ``flops_per_attended`` 0 and ``bytes_per_cache_token`` 0: retention
      keeps no K/V by token, so the driver's ``attended`` and
      ``cache_tokens`` (which count a request's depth) price nothing.
      **So ``serve_mfu`` does not price the state's bytes**: the 6.54 GB
      of float32 state a decode run reads at 48 slots, more than the
      weights, are outside it; ``retention_decode_roofline`` prices them
      from the engine's own counters.
    * ``bytes_per_run``: every weight once in the served type but the
      embedding table (gathered by row)."""
    import jax.numpy as jnp

    c, d = config, config["hidden_size"]
    wsize = jnp.dtype(c["serve"]["weight_dtype"]).itemsize
    layers = c["num_hidden_layers"]
    D, dh = features(c), c["head_dim"]
    heads = c["num_attention_heads"] + c["num_key_value_heads"]
    return {
        "flops_per_token": layers * (2 * _matmul_parameters(c)
                                     + heads * 2 * D * (dh + 1)),
        "flops_per_logit": 2 * c["vocab_size"] * d,
        "flops_per_attended": 0,
        "bytes_per_run": wsize * (
            layers * layer_parameters(c) + c["vocab_size"] * d + d),
        "bytes_per_cache_token": 0,
    }


def retention_decode_least_ms(config: dict, state_reads: float,
                              tail_rows: float, peak: dict) -> float:
    """Least milliseconds the ``retention_decode`` calls of one engine
    step can take on a chip with these peaks (perf/harness/peaks.json):
    the larger of the bytes read once, over the peak bytes/s, and the
    operations over the peak FLOP/s.  ``state_reads`` slot-layers whose
    state a step read: each KV head's ``S`` ``(D, 128)`` and ``z`` ``(D,)``
    in the state type; ``tail_rows`` tail rows attended (summed over slots
    and layers): K and V of every KV head in the compute type and a
    float32 gate.  Operations: every query head's ``phi(q)`` against ``S``
    and ``z``, and ``(q . k)^2`` and its product with ``v`` a tail row."""
    import jax.numpy as jnp

    c = config
    D, dh = features(c), c["head_dim"]
    kv, h = c["num_key_value_heads"], c["num_attention_heads"]
    ssize = jnp.dtype(c["serve"]["state_dtype"]).itemsize
    csize = jnp.dtype(c["compute_dtype"]).itemsize
    nbytes = (state_reads * kv * D * (dh + 1) * ssize
              + tail_rows * kv * (2 * dh * csize + 4))
    flops = state_reads * h * 2 * D * (dh + 1) + tail_rows * h * 4 * dh
    return 1e3 * max(nbytes / peak["hbm_bytes_per_s"],
                     flops / peak["bf16_flops_per_s"])


# ------------------------------------------------------------- reference


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * _f32(scale)


def _rope(x, theta: float):
    """Rotate-half RoPE on ``(B, T, H, dh)`` at positions ``0..T-1``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _retention(p, u, c):
    """One retention sublayer on ``u`` ``(B, T, d)``."""
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c["head_dim"]
    eps = c["rms_norm_eps"]
    q = (u @ _f32(p["wq"]["kernel"])).reshape(b, t, h, dh)
    k = (u @ _f32(p["wk"]["kernel"])).reshape(b, t, kv, dh)
    v = (u @ _f32(p["wv"]["kernel"])).reshape(b, t, kv, dh)
    q = _rope(_rms(q, p["q_norm"]["scale"], eps), c["rope_theta"])
    k = _rope(_rms(k, p["k_norm"]["scale"], eps), c["rope_theta"])
    tau = c["gate_timescale"] * 2.0 ** jnp.arange(kv, dtype=jnp.float32)
    log_g = jax.nn.log_sigmoid(u @ _f32(p["wg"]["kernel"])
                               + jnp.log(tau - 1.0))
    G = jnp.cumsum(log_g, axis=1)  # (b, t, kv)
    qg = q.reshape(b, t, kv, h // kv, dh)
    bq = max(n for n in range(1, min(_QUERY_BLOCK, t) + 1) if t % n == 0)
    key_pos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * bq, bq, axis=1)
        gb = jax.lax.dynamic_slice_in_dim(G, i * bq, bq, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k) / math.sqrt(dh)
        seen = (i * bq + jnp.arange(bq))[:, None] >= key_pos[None, :]
        diff = jnp.transpose(gb, (0, 2, 1))[..., :, None] \
            - jnp.transpose(G, (0, 2, 1))[..., None, :]  # (b, kv, bq, t)
        a = jnp.where(seen, s ** c["retention_degree"]
                      * jnp.exp(jnp.where(seen, diff, 0.0))[:, :, None], 0.0)
        num = jnp.einsum("bkgqs,bskd->bqkgd", a, v)
        den = jnp.transpose(jnp.sum(a, axis=-1), (0, 3, 1, 2))[..., None]
        return num / (den + c["retention_eps"])

    y = jax.lax.map(block, jnp.arange(t // bq))  # (t/bq, b, bq, kv, g, dh)
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, h * dh)
    return y @ _f32(p["wo"]["kernel"])


def _swiglu(p, u):
    import jax.numpy as jnp

    g = u @ _f32(p["w1"]["kernel"])
    return (g / (1.0 + jnp.exp(-g))
            * (u @ _f32(p["w3"]["kernel"]))) @ _f32(p["w2"]["kernel"])


def head_columns(vocab: int) -> int:
    """The vocabulary columns the reference's head takes at a time."""
    return _HEAD_COLUMNS if vocab % _HEAD_COLUMNS == 0 else vocab


def head_block(h, kernel, j):
    """Columns ``[j x head_columns, (j + 1) x head_columns)`` of ``h @
    kernel``, the matrix upcast a block at a time."""
    import jax

    cols = head_columns(kernel.shape[1])
    return h @ _f32(jax.lax.dynamic_slice_in_dim(kernel, j * cols, cols,
                                                 axis=1))


def _head(h, kernel):
    """``h @ kernel`` a block of vocabulary columns at a time, each written
    in place into the logits, so that the float32 copy of the head's matrix
    never exists whole."""
    import jax
    import jax.numpy as jnp

    vocab = kernel.shape[1]
    cols = head_columns(vocab)
    out = jnp.zeros((*h.shape[:-1], vocab), jnp.float32)
    return jax.lax.fori_loop(
        0, vocab // cols, lambda j, out: jax.lax.dynamic_update_slice_in_dim(
            out, head_block(h, kernel, j), j * cols, axis=-1), out)


def reference_hidden(params, tokens, config: dict):
    """``(B, T)`` tokens -> the final norm's output ``(B, T, d)`` float32:
    what the head multiplies (the check compares logits a block of columns
    at a time from it, so that ``(T, vocab)`` float32 never exists whole
    beside the engine's state)."""
    c, eps = config, config["rms_norm_eps"]
    h = _f32(params["wte"]["embedding"][tokens])
    for i in range(c["num_hidden_layers"]):
        blk = params[f"h_{i}"]
        h = h + _retention(blk["attn"], _rms(h, blk["rms_in"]["scale"], eps),
                           c)
        h = h + _swiglu(blk["mlp"], _rms(h, blk["rms_mlp"]["scale"], eps))
    return _rms(h, params["rms_out"]["scale"], eps)


def _features(x):
    """``phi(x)`` in float64: ``x / dh^(1/4)``'s products of every
    unordered pair once, ``sqrt 2`` on the pairs of two different entries,
    in the order the system stores them (its state's rows):
    ``tpudp/ops/retention.py``'s module docstring, cyclic offsets ``o = 0
    .. dh/2 - 1`` of ``dh`` entries ``x_a x_{(a + o) mod dh}``, then
    ``x_a x_{a + dh/2}`` for ``a < dh/2``.  Index arrays, no rotation."""
    import numpy as np

    dh = x.shape[-1]
    a = np.arange(dh)
    first = np.concatenate([a] * (dh // 2) + [a[:dh // 2]])
    second = np.concatenate([(a + o) % dh for o in range(dh // 2)]
                            + [a[:dh // 2] + dh // 2])
    coef = np.where(first == second, 1.0, math.sqrt(2.0))
    x = np.asarray(x, np.float64) * dh ** -0.25
    return x[..., first] * x[..., second] * coef


def reference_fold(S, z, k, v, g):
    """The state a fold of ``C`` rows leaves, in float64 on the host::

        S' = exp(g_C) S + sum_s exp(g_C - g_s) phi(k_s) v_s^T
        z' = exp(g_C) z + sum_s exp(g_C - g_s) phi(k_s)

    ``S`` ``(..., D, dh)``, ``z`` ``(..., D)``: the state before the fold;
    ``k``, ``v`` ``(..., C, dh)``, ``g`` ``(..., C)``: the folded rows and
    their cumulative log-gates from the state's last fold (``g_C`` the
    last).  The check holds the system's state after the fold to this,
    from the state before it and the rows the system folded."""
    import numpy as np

    g = np.asarray(g, np.float64)
    w = np.exp(g[..., -1:] - g)[..., None] * _features(k)  # (..., C, D)
    keep = np.exp(g[..., -1])
    S = (keep[..., None, None] * np.asarray(S, np.float64)
         + np.swapaxes(w, -1, -2) @ np.asarray(v, np.float64))
    z = keep[..., None] * np.asarray(z, np.float64) + w.sum(-2)
    return S, z


def reference_logits(params, tokens, config: dict):
    """``(B, T)`` tokens -> ``(B, T, vocab)`` float32 logits."""
    return _head(reference_hidden(params, tokens, config),
                 params["lm_head"]["kernel"])
