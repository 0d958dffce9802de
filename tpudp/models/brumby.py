"""Brumby decoder (``model_type`` ``brumby``): every layer a power-retention
layer (``tpudp.ops.retention``, degree 2) in place of attention, then a
SwiGLU; untied head.

With ``h`` the residual stream and ``u = RMSNorm(h)`` (a learned scale,
float32), no projection with a bias:

  * block: ``h += W_o concat(y)``, ``y`` the retention of ``u``'s
    projections; ``h += SwiGLU(RMSNorm(h))``; after the last block
    ``RMSNorm_out`` and the head.
  * retention: ``q = u W_q`` ``(H x dh)``, ``k = u W_k``, ``v = u W_v``
    ``(KV x dh)``; a per-head RMSNorm on ``q`` and ``k`` (learned scale,
    the Qwen3 lineage) and rotate-half RoPE at ``rope_theta``, in float32
    with one rounding to the compute type; a gate a KV head, ``log g =
    logsigmoid(u W_g + b_h)`` in float32, ``b_h`` a fixed offset a head
    (:data:`GATE_TIMESCALE`); query head ``i`` reads KV head
    ``i // (H / KV)``; ``y = retention(q, k, v, log g)`` of degree 2
    with normaliser ``+ retention_eps``.

Parameters are made in ``param_dtype``.

Serving: the raw-param twins at the end (:func:`forward_state`) are what
``generate._forward_state`` dispatches to.  The cache is
``generate.RetentionState``, indexed by slot and not by token: a layer's
state ``S`` ``(KV, D, dh)`` and normaliser ``z`` ``(KV, D)`` in float32
(``D = dh (dh + 1) / 2``, the symmetric form), and a tail of the K/V rows
since the last fold.  The fold boundary is every ``fold_tokens`` (``C``)
absolute positions, so a slot's tail holds ``pos mod C`` rows and nothing
else records its length.  A prefill chunk starts from the slot's state
(zero on the chunk at position 0: a request admitted to a used slot
starts fresh without a dispatch of its own), folds its whole ``C``-blocks
and leaves the rest as the tail; a decode step appends its token to the
tail, attends over state and tail, and folds the tail into the state on
the step that fills it.  There is no dense or paged KV twin, so
``generate()`` / ``beam_search()`` refuse the config.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpudp.models.llama import apply_rope
from tpudp.models.moe import scaled_init, swiglu
from tpudp.models.pangu import _mm, _rms
from tpudp.ops import retention as ret

#: The gate's fixed offset a KV head (an assumed reading: the config names
#: no gate; RetNet fixes a decay a head the same way): ``b_h = log(tau_h -
#: 1)``, ``tau_h = gate_timescale x 2^h`` tokens (this the config's
#: default), so that ``g ~ 1 -
#: 1/tau_h`` and the heads remember over 16 to 2,048 tokens at the
#: published 8 KV heads; the learned ``W_g`` moves a token's gate around
#: it.  Without an offset ``g ~ 0.5`` and a head forgets in two tokens:
#: no state would outlive a fold.  ``W_g`` starts at a tenth of
#: lecun_normal (chosen, not published).
GATE_TIMESCALE = 16.0
GATE_INIT_SCALE = 0.1
#: The untrained embedding at unit variance (as ``laguna.EMBED_STD``): a
#: token's own row dominates the first norm's input.
EMBED_STD = 1.0


def gate_offsets(cfg: "BrumbyConfig") -> jnp.ndarray:
    """``b_h`` of the :data:`GATE_TIMESCALE` note, float32 ``(KV,)``
    (``gate_timescale`` infinite: every gate 1)."""
    tau = cfg.gate_timescale * 2.0 ** jnp.arange(cfg.num_key_value_heads,
                                                 dtype=jnp.float32)
    return jnp.log(tau - 1.0)


@dataclass(frozen=True)
class BrumbyConfig:
    """The published ``config.json``'s keys under their own names, then
    what this repo adds.  Defaults are a small model, not the release."""

    vocab_size: int = 256
    hidden_size: int = 128
    intermediate_size: int = 256
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32_768
    tie_word_embeddings: bool = False
    # --- this repo's (the degree, 2, and the float32 state are the
    # family's constants)
    retention_eps: float = 1e-6  # the normaliser's
    gate_timescale: float = GATE_TIMESCALE
    fold_tokens: int = 128  # C: the tail folds into the state every C
    attn_impl: str = "dense"  # the module's retention is XLA's
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    #: which cache the serve engine keeps this family in: a state arena
    page_layout = "state"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads not divisible by "
                f"{self.num_key_value_heads} KV heads")
        if self.head_dim % 2:
            raise ValueError(f"head_dim {self.head_dim} must be even")
        if self.tie_word_embeddings:
            raise ValueError("tie_word_embeddings=True is not implemented")
        if self.attn_impl != "dense":
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}: the "
                             "module's retention is XLA's ('dense')")
        if self.fold_tokens < 1:
            raise ValueError(f"fold_tokens {self.fold_tokens}")

    @classmethod
    def from_dict(cls, config: dict, **overrides) -> "BrumbyConfig":
        """From a ``config.json``-style mapping: the keys this class has
        are taken, every other key ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in names}
        return cls(**{**kw, **overrides})

    @property
    def state_features(self) -> int:
        """``D``: entries of ``phi`` a KV head."""
        return ret.features(self.head_dim)

    # the names the serve engine reads of every family
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def d_model(self) -> int:
        return self.hidden_size


def _rms_mod(cfg: BrumbyConfig, name: str) -> nn.RMSNorm:
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                      param_dtype=cfg.param_dtype, name=name)


def _dense_mod(cfg: BrumbyConfig, features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


class _SwiGLU(nn.Module):
    config: BrumbyConfig

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        gate = _dense_mod(cfg, cfg.intermediate_size, "w1")(u)
        up = _dense_mod(cfg, cfg.intermediate_size, "w3")(u)
        return _dense_mod(cfg, cfg.hidden_size, "w2")(nn.silu(gate) * up)


def _gate(cfg: BrumbyConfig, p: dict, u: jnp.ndarray) -> jnp.ndarray:
    """``logsigmoid(u W_g + b_h)`` in float32, ``(..., KV)``."""
    f32 = jnp.float32
    return jax.nn.log_sigmoid(u.astype(f32) @ p["kernel"].astype(f32)
                              + gate_offsets(cfg))


class Retention(nn.Module):
    """The retention sublayer, uncached, in the quadratic form:
    ``(B, T, d) -> (B, T, d)``."""

    config: BrumbyConfig

    @nn.compact
    def __call__(self, u: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        b, t, _ = u.shape
        h, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        q = _dense_mod(cfg, h * dh, "wq")(u).reshape(b, t, h, dh)
        k = _dense_mod(cfg, kv * dh, "wk")(u).reshape(b, t, kv, dh)
        v = _dense_mod(cfg, kv * dh, "wv")(u).reshape(b, t, kv, dh)
        # norm and rotation in float32, one rounding on the way out
        q = apply_rope(_rms_mod(cfg, "q_norm")(q), positions,
                       cfg.rope_theta).astype(cfg.dtype)
        k = apply_rope(_rms_mod(cfg, "k_norm")(k), positions,
                       cfg.rope_theta).astype(cfg.dtype)
        gate = nn.Dense(kv, use_bias=False, dtype=jnp.float32,
                        param_dtype=cfg.param_dtype, name="wg",
                        kernel_init=scaled_init(
                            nn.initializers.lecun_normal(), GATE_INIT_SCALE))
        log_g = jax.nn.log_sigmoid(gate(u.astype(jnp.float32))
                                   + gate_offsets(cfg))
        y = ret.retention_reference(q, k, v, log_g, eps=cfg.retention_eps)
        return _dense_mod(cfg, cfg.hidden_size, "wo")(
            y.astype(cfg.dtype).reshape(b, t, h * dh))


class BrumbyBlock(nn.Module):
    config: BrumbyConfig

    @nn.compact
    def __call__(self, h: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        u = _rms_mod(cfg, "rms_in")(h).astype(cfg.dtype)
        h = h + Retention(cfg, name="attn")(u, positions)
        u = _rms_mod(cfg, "rms_mlp")(h).astype(cfg.dtype)
        return h + _SwiGLU(cfg, name="mlp")(u)


class Brumby(nn.Module):
    """Decoder-only LM: ``(B, T) int tokens -> (B, T, vocab) float32
    logits`` (untied head).  ``train`` is accepted for Trainer
    compatibility."""

    config: BrumbyConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray,
                 train: bool = False) -> jnp.ndarray:
        del train
        cfg = self.config
        positions = jnp.arange(tokens.shape[1])
        h = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wte",
                     embedding_init=nn.initializers.normal(EMBED_STD))(
                         tokens)
        for i in range(cfg.num_hidden_layers):
            h = BrumbyBlock(cfg, name=f"h_{i}")(h, positions)
        h = _rms_mod(cfg, "rms_out")(h).astype(cfg.dtype)
        return _dense_mod(cfg, cfg.vocab_size, "lm_head")(h).astype(
            jnp.float32)


# --------------------------------------------------- raw-param serving twins


def projections(cfg: BrumbyConfig, p: dict, u: jnp.ndarray,
                positions: jnp.ndarray):
    """``q`` ``(b, cur, H, dh)``, ``k``, ``v`` ``(b, cur, KV, dh)`` in the
    compute type and ``log g`` ``(b, cur, KV)`` float32 of ``u`` at
    ``positions`` ``(b, cur)``: :class:`Retention`'s, on raw parameters."""
    b, cur, _ = u.shape
    h, kv, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    q = _mm(p["wq"], u, cfg.dtype).reshape(b, cur, h, dh)
    k = _mm(p["wk"], u, cfg.dtype).reshape(b, cur, kv, dh)
    v = _mm(p["wv"], u, cfg.dtype).reshape(b, cur, kv, dh)
    q = apply_rope(_rms(p["q_norm"], q, eps), positions,
                   cfg.rope_theta).astype(cfg.dtype)
    k = apply_rope(_rms(p["k_norm"], k, eps), positions,
                   cfg.rope_theta).astype(cfg.dtype)
    return q, k, v, _gate(cfg, p["wg"], u)


def _mlp(cfg: BrumbyConfig, p: dict, x: jnp.ndarray) -> jnp.ndarray:
    u = _rms(p["rms_mlp"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    return x + swiglu(p["mlp"], u, cfg.dtype)


def _out(cfg: BrumbyConfig, p: dict, y: jnp.ndarray) -> jnp.ndarray:
    b, cur = y.shape[:2]
    return _mm(p["wo"], y.astype(cfg.dtype).reshape(b, cur, -1), cfg.dtype)


def fold_tails(cfg: BrumbyConfig, state, need: jnp.ndarray):
    """Fold every layer's full tail of the slots in ``need`` ``(b,)`` into
    their state: a loop over those slots alone (a decode step folds the
    ~1 in C slots whose tail it filled), each an in-place update of the
    slot's rows of the donated arena."""
    S, z, tk, tv, tg = state
    layers, b, kv, c, dh = tk.shape
    D = S.shape[3]
    order = jnp.nonzero(need, size=b, fill_value=0)[0]

    def body(j, carry):
        S, z = carry
        s = order[j]

        def take(a):  # slot s of every layer, (layers x KV, ...)
            a = lax.dynamic_index_in_dim(a, s, axis=1, keepdims=False)
            return a.reshape(layers * kv, *a.shape[2:])

        s_new, z_new = ret.chunk_fold(
            take(tk), take(tv), take(tg), take(S), take(z), c)
        return (lax.dynamic_update_index_in_dim(
                    S, s_new.reshape(layers, kv, D, dh), s, axis=1),
                lax.dynamic_update_index_in_dim(
                    z, z_new.reshape(layers, kv, D), s, axis=1))

    S, z = lax.fori_loop(0, jnp.sum(need, dtype=jnp.int32), body, (S, z))
    return state._replace(S=S, z=z)


def _decode(cfg: BrumbyConfig, params: dict, x, state, pos, active, impl):
    """One token a slot (``x`` ``(b, 1, d)``) at depths ``pos``."""
    b = x.shape[0]
    kv, c = cfg.num_key_value_heads, cfg.fold_tokens
    grp = cfg.num_attention_heads // kv
    at = pos % c  # the tail row this token takes
    n = jnp.where(active, at + 1, 0)
    rows = jnp.arange(b)
    S, z, tk, tv, tg = state
    for i in range(cfg.num_hidden_layers):
        p = params[f"h_{i}"]
        u = _rms(p["rms_in"], x, cfg.rms_norm_eps).astype(cfg.dtype)
        q, k, v, log_g = projections(cfg, p["attn"], u, pos[:, None])

        def put(buf, new):  # row ``at`` of layer i's tail, decoding slots
            old = buf[i, rows, :, at]
            live = active.reshape((b,) + (1,) * (new.ndim - 1))
            return buf.at[i, rows, :, at].set(jnp.where(live, new, old))

        tk = put(tk, k[:, 0])
        tv = put(tv, v[:, 0])
        before = jnp.where((at > 0)[:, None],
                           tg[i, rows, :, jnp.maximum(at - 1, 0)], 0.0)
        tg = put(tg, before + log_g[:, 0])
        y = ret.retention_decode(
            q[:, 0].reshape(b, kv, grp, cfg.head_dim), S, z, tk, tv, tg, n,
            eps=cfg.retention_eps, impl=impl, layer=i)
        x = _mlp(cfg, p, x + _out(cfg, p["attn"], y.reshape(b, 1, -1)))
    state = fold_tails(cfg, type(state)(S, z, tk, tv, tg),
                       active & (at == c - 1))
    return x, state


def _prefill(cfg: BrumbyConfig, params: dict, x, state, start, slot, last,
             every_row: bool):
    """One chunk of one slot (``x`` ``(1, P, d)``) from position
    ``start``; rows past ``last`` are padding: their ``k``, ``v`` and
    ``log g`` are zeroed, so they reach neither the state nor the tail."""
    cur = x.shape[1]
    c, eps = cfg.fold_tokens, cfg.retention_eps
    row = jnp.arange(cur)
    live = row <= last
    upto = (last + 1) // c * c  # the rows of whole C-blocks: folded
    fresh = start == 0
    positions = (start + row)[None]
    S, z, tk, tv, tg = state
    for i in range(cfg.num_hidden_layers):
        p = params[f"h_{i}"]
        u = _rms(p["rms_in"], x, cfg.rms_norm_eps).astype(cfg.dtype)
        q, k, v, log_g = projections(cfg, p["attn"], u, positions)
        k = jnp.where(live[None, :, None, None], k, 0)
        v = jnp.where(live[None, :, None, None], v, 0)
        log_g = jnp.where(live[None, :, None], log_g, 0.0)
        # the slot's rows of layer i, read as they lie in the arena
        s0 = lax.dynamic_slice(S, (i, slot, 0, 0, 0),
                               (1, 1) + S.shape[2:])[0, 0]
        z0 = lax.dynamic_slice(z, (i, slot, 0, 0), (1, 1) + z.shape[2:])[0, 0]
        s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
        z0 = jnp.where(fresh, jnp.zeros_like(z0), z0)
        y, G = ret.chunk_attend(q, k, v, log_g, s0[None], z0[None], eps=eps)
        x = _mlp(cfg, p, x + _out(cfg, p["attn"], y))
        kk, vv = (jnp.swapaxes(a[0], 0, 1) for a in (k, v))  # (KV, P, dh)
        gg = G[0].T  # (KV, P)
        s1, z1 = ret.chunk_fold(kk, vv, gg, s0, z0, upto)
        S = lax.dynamic_update_slice(S, s1[None, None], (i, slot, 0, 0, 0))
        z = lax.dynamic_update_slice(z, z1[None, None], (i, slot, 0, 0))
        # the rows after the last whole block become the tail
        end = jnp.sum(jnp.where(row == upto - 1, gg, 0.0), axis=1)
        tail = [lax.dynamic_slice_in_dim(
                    jnp.concatenate([a, jnp.zeros_like(a[:, :c])], axis=1),
                    upto, c, axis=1) for a in (kk, vv)]
        gpad = jnp.concatenate([gg, jnp.repeat(gg[:, -1:], c, axis=1)], 1)
        gtail = lax.dynamic_slice_in_dim(gpad, upto, c, axis=1) - end[:, None]
        tk = lax.dynamic_update_slice(tk, tail[0][None, None],
                                      (i, slot, 0, 0, 0))
        tv = lax.dynamic_update_slice(tv, tail[1][None, None],
                                      (i, slot, 0, 0, 0))
        tg = lax.dynamic_update_slice(tg, gtail[None, None], (i, slot, 0, 0))
    if not every_row:
        x = lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    return x, type(state)(S, z, tk, tv, tg)


def forward_state(cfg: BrumbyConfig, params: dict, tokens: jnp.ndarray,
                  state, pos, active: jnp.ndarray, impl: str | None = None,
                  *, slot=None, last=None, every_row: bool = False):
    """Tokens through ``generate.RetentionState``: ``(logits, state)``.

    Decode (``slot`` None): ``tokens`` ``(b, 1)``, one a slot, at depths
    ``pos`` ``(b,)``; ``active`` ``(b,)`` the slots that decode (the others'
    tails and states are left as they were).  Prefill: ``tokens`` ``(1,
    P)`` for slot ``slot`` from position ``pos`` (a multiple of
    ``fold_tokens``; 0 starts the slot fresh), valid through row
    ``last``; only row ``last`` goes through the head (``logits`` ``(1, 1,
    vocab)``), or with ``every_row`` every row (``(1, P, vocab)``: the
    benchmark's check reads each prompt position).

    ``impl`` is the decode read: ``'einsum'`` or ``'kernel'``
    (``ops.retention.retention_decode``); ``None`` the kernel on an
    accelerator and einsum on the CPU.  A prefill chunk runs the chunked
    form (XLA) either way."""
    if impl is None:
        impl = "einsum" if jax.default_backend() == "cpu" else "kernel"
    x = params["wte"]["embedding"].astype(cfg.dtype)[tokens]
    pos = jnp.asarray(pos)
    if slot is None:
        x, state = _decode(cfg, params, x, state, pos, active, impl)
    else:
        x, state = _prefill(cfg, params, x, state, pos, slot, last,
                            every_row)
    x = _rms(params["rms_out"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    return _mm(params["lm_head"], x, cfg.dtype).astype(jnp.float32), state


def parameters_per_layer(cfg: BrumbyConfig) -> int:
    """Every parameter of one block: retention's four projections, its
    two head norms and gate matrix, the SwiGLU, the two norms."""
    d, dh = cfg.hidden_size, cfg.head_dim
    h, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    return (d * h * dh * 2 + d * kv * dh * 2 + 2 * dh + d * kv
            + 3 * d * cfg.intermediate_size + 2 * d)


def state_bytes(cfg: BrumbyConfig, num_slots: int) -> int:
    """Bytes a ``RetentionState`` of ``num_slots`` holds (its state and
    normaliser in float32)."""
    D, dh, c = cfg.state_features, cfg.head_dim, cfg.fold_tokens
    per = (cfg.num_hidden_layers * num_slots * cfg.num_key_value_heads)
    return per * ((D * dh + D) * 4
                  + 2 * c * dh * jnp.dtype(cfg.dtype).itemsize + c * 4)

