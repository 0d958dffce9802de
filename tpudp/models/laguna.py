"""Laguna decoder (``model_type`` ``laguna``): grouped-query attention
whose layers are of two kinds, a leading dense SwiGLU layer, then expert
layers of routed + one shared SwiGLU expert, every routed expert held.

With ``h`` the residual stream, ``u = RMSNorm(h)`` (a learned scale,
float32), no projection with a bias, ``H_l`` the query heads of layer
``l`` (``num_attention_heads_per_layer``), ``kv`` KV heads of ``dh``:

  * block: ``h += attn(RMSNorm_in(h))``; ``h += mlp(RMSNorm_mlp(h))``
    (pre-norm only); after the last block ``RMSNorm_out`` and an untied
    head.
  * attention: ``q = u W_q`` ``(H_l x dh)``, ``k = u W_k``, ``v = u W_v``
    ``(kv x dh)``; query head ``j`` reads KV head ``j // (H_l / kv)``;
    softmax of ``q k^T / sqrt(dh)`` in float32 under a causal mask, and
    on a ``sliding_attention`` layer also ``q_pos - k_pos <
    sliding_window``; each head's output times ``sigmoid(u W_g)`` of that
    head (``gating``: one gate a head, ``W_g`` ``(d, H_l)``); ``W_o``.
  * RoPE, rotate-half, by layer kind (:class:`RopeKind`): the first
    ``rotary_factor x dh`` dimensions of a head turn, the rest pass;
    ``rope_type`` ``yarn`` blends interpolated and extrapolated
    frequencies (:func:`rope_inv_freq`) and multiplies ``cos`` and
    ``sin`` by ``attention_factor``.
  * mlp of a ``dense`` layer: SwiGLU of ``intermediate_size``; of a
    ``sparse`` layer ``moe.DroplessMoe`` over all ``num_experts`` experts
    (sigmoid scores, no groups, no selection bias, top-``k``, normalised,
    times ``moe_routed_scaling_factor``, applied to the experts' output)
    plus one shared SwiGLU expert.

Parameters are made in ``param_dtype`` (no float32 copy of a served tree
ever exists).

Serving: the raw-param twins at the end (``forward_paged``) are what
``generate._forward_paged`` dispatches to for this family.  The cache is
``generate.WindowedPages``: one page pool for the ``full_attention``
layers and one for the ``sliding_attention`` layers, each behind a block
table of its own, so that the engine can free a window layer's pages once
the window has passed them.  There is no dense cache twin, so
``generate()`` / ``beam_search()`` refuse the config.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpudp.models.moe import (DroplessMoe, routed_and_shared, scaled_init,
                              swiglu)
from tpudp.models.pangu import _mm, _rms

FULL, SLIDING = "full_attention", "sliding_attention"

#: Where the untrained embedding starts (what a checkpoint would replace):
#: unit variance, so that a token's residual stream is mostly the token's
#: own and an untrained router is near even, as a trained one is.  With
#: flax's 1/sqrt(d) embedding the first attention layer's output, nearly
#: the same vector for every token at random weights (it averages its
#: context), is as large as the embedding, every router sees it, and a few
#: experts take most of the choices (PERF.md section 6, PR 34's lesson).
EMBED_STD = 1.0
#: Where the untrained projections start, as multiples of flax's
#: lecun_normal (chosen, not published; a checkpoint replaces them).  At
#: lecun_normal throughout the model is a poor stand-in for a trained one
#: in two ways that the comparison with the reference feels (PERF.md
#: section 6, PR 36).  A head's scores have unit variance, so it averages
#: ~190 of a window's 512 keys, what attention adds is 3% of the stream,
#: and a wrong window or a wrong RoPE cannot be told from bf16 rounding:
#: ``W_q`` and ``W_k`` start at sqrt(2) x, scores of variance 4, a head
#: that attends a handful of keys as a trained one does.  And an MLP
#: sublayer adds as much as the stream holds, so ONE expert choice that a
#: bf16 stream flips (the 8th and 9th of 256 scores lie ~0.01 apart)
#: moves the logits by 0.3-0.5: the matrices that close an MLP sublayer
#: (``w2`` of the dense SwiGLU, of every expert, of the shared expert)
#: start at a quarter, the order of a depth-scaled start for 40 layers
#: (1 / sqrt(2 x 40) = 0.11 of GPT-2's and Megatron's recipes).
QK_INIT_SCALE = 2.0 ** 0.5
MLP_OUT_INIT_SCALE = 0.25


@dataclass(frozen=True)
class RopeKind:
    """One entry of the published ``rope_parameters``."""

    theta: float = 10_000.0
    rotary_factor: float = 1.0  # partial_rotary_factor
    yarn_factor: float | None = None  # None: rope_type "default"
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    @classmethod
    def from_dict(cls, d: dict) -> "RopeKind":
        kind = d.get("rope_type", "default")
        if kind not in ("default", "yarn"):
            raise ValueError(f"rope_type {kind!r}: 'default' and 'yarn' "
                             "are implemented")
        out = {"theta": float(d["rope_theta"]),
               "rotary_factor": float(d.get("partial_rotary_factor", 1.0))}
        if kind == "yarn":
            factor = float(d["factor"])
            out.update(
                yarn_factor=factor,
                original_max_position_embeddings=int(
                    d["original_max_position_embeddings"]),
                beta_fast=float(d.get("beta_fast", 32.0)),
                beta_slow=float(d.get("beta_slow", 1.0)),
                attention_factor=float(d.get(
                    "attention_factor", 0.1 * math.log(factor) + 1.0)))
        return cls(**out)


def rope_inv_freq(rope: RopeKind, head_dim: int) -> np.ndarray:
    """The ``rot / 2`` inverse frequencies of one layer kind, ``rot`` =
    ``rotary_factor x head_dim``, float32.  Plain: ``theta^(-2i/rot)``.
    YaRN: with ``corr(n) = rot ln(orig / (2 pi n)) / (2 ln theta)`` the
    pair index at which a wave turns ``n`` times over the original
    context, ``low = floor(corr(beta_fast))``, ``high =
    ceil(corr(beta_slow))`` clipped to ``[0, rot - 1]``, ``ramp_i =
    clip((i - low) / (high - low), 0, 1)``: pair ``i`` turns at
    ``theta^(-2i/rot) x ((1 - ramp_i) + ramp_i / factor)``, fast pairs as
    trained, slow pairs interpolated."""
    rot = int(head_dim * rope.rotary_factor)
    i = np.arange(rot // 2, dtype=np.float64)
    extra = rope.theta ** (-2.0 * i / rot)
    if rope.yarn_factor is None:
        return extra.astype(np.float32)

    def corr(turns: float) -> float:
        return (rot * math.log(rope.original_max_position_embeddings
                               / (turns * 2.0 * math.pi))
                / (2.0 * math.log(rope.theta)))

    low = max(math.floor(corr(rope.beta_fast)), 0)
    high = min(math.ceil(corr(rope.beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / rope.yarn_factor * ramp
            + extra * (1.0 - ramp)).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               rope: RopeKind) -> jnp.ndarray:
    """Rotate ``x`` ``(B, T, H, dh)`` at ``positions`` ``(T,)`` or ``(B,
    T)``: rotate-half over the first ``rotary_factor x dh`` dimensions,
    float32 angles, ``cos`` and ``sin`` times ``attention_factor``."""
    inv_freq = rope_inv_freq(rope, x.shape[-1])
    half = inv_freq.size
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = (jnp.cos(angles) * rope.attention_factor)[..., None, :]
    sin = (jnp.sin(angles) * rope.attention_factor)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., 2 * half:]], axis=-1)


@dataclass(frozen=True)
class LagunaConfig:
    """The published ``config.json``'s keys under their own names (lists
    as tuples, ``rope_parameters`` as two :class:`RopeKind`), then what
    this repo adds.  Defaults are a small model, not the release."""

    vocab_size: int = 512
    hidden_size: int = 128
    intermediate_size: int = 256  # dense SwiGLU width
    moe_intermediate_size: int = 64  # one expert's SwiGLU width
    shared_expert_intermediate_size: int = 64
    num_hidden_layers: int = 5
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 4
    num_attention_heads_per_layer: tuple = (6, 8, 8, 8, 6)
    num_key_value_heads: int = 2
    head_dim: int = 16
    sliding_window: int = 16
    rope_full: RopeKind = RopeKind(
        theta=500_000.0, rotary_factor=0.5, yarn_factor=64.0,
        beta_fast=64.0, attention_factor=1.4158883083359672)
    rope_sliding: RopeKind = RopeKind()
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_routed_scaling_factor: float = 2.5
    gating: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262_144
    # --- this repo's
    attn_impl: str = "dense"  # the module's attention is XLA's
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    #: which page type the serve engine keeps this family's cache in
    page_layout = "windowed"

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {n} layers")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {self.layer_types}: "
                             f"{FULL!r} and {SLIDING!r} are implemented")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"mlp_layer_types {self.mlp_layer_types}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError(
                f"query heads {self.num_attention_heads_per_layer} not "
                f"divisible by {self.num_key_value_heads} KV heads")
        if self.attn_impl != "dense":
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}: the "
                             "module's attention is XLA's ('dense')")
        if not self.gating:
            raise ValueError("gating=False is not implemented")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window}")

    @classmethod
    def from_dict(cls, config: dict, **overrides) -> "LagunaConfig":
        """From a ``config.json``-style mapping: the keys this class has
        are taken (lists as tuples, ``rope_parameters`` by layer kind),
        every other key ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config.items() if k in names}
        ropes = config.get("rope_parameters")
        if ropes is not None:
            kw["rope_full"] = RopeKind.from_dict(ropes[FULL])
            kw["rope_sliding"] = RopeKind.from_dict(ropes[SLIDING])
        return cls(**{**kw, **overrides})

    def rope(self, layer: int) -> RopeKind:
        return (self.rope_sliding if self.layer_types[layer] == SLIDING
                else self.rope_full)

    def window(self, layer: int) -> int | None:
        """The window of a sliding layer; None on a full layer."""
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    def pool_layer(self, layer: int) -> tuple[str, int]:
        """Where layer ``layer``'s K/V live: the field of
        ``generate.WindowedPages`` and the stratum inside it."""
        kind = self.layer_types[layer]
        return ("window" if kind == SLIDING else "full",
                self.layer_types[:layer].count(kind))

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


def _rms_mod(cfg: LagunaConfig, name: str) -> nn.RMSNorm:
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                      param_dtype=cfg.param_dtype, name=name)


def _dense_mod(cfg: LagunaConfig, features: int, name: str,
               init_scale: float = 1.0) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name,
                    kernel_init=scaled_init(nn.initializers.lecun_normal(),
                                            init_scale))


class _SwiGLU(nn.Module):
    config: LagunaConfig
    width: int

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        gate = _dense_mod(cfg, self.width, "w1")(u)
        up = _dense_mod(cfg, self.width, "w3")(u)
        return _dense_mod(cfg, cfg.hidden_size, "w2",
                          MLP_OUT_INIT_SCALE)(nn.silu(gate) * up)


def _gated(o: jnp.ndarray, gate_logits: jnp.ndarray) -> jnp.ndarray:
    """``o`` ``(..., H, dh)`` times each head's sigmoid gate ``(..., H)``,
    the gate in float32."""
    g = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)


class LagunaAttention(nn.Module):
    """Causal (and, on a sliding layer, windowed) GQA with a per-head
    output gate, uncached: ``(B, T, d) -> (B, T, d)``."""

    config: LagunaConfig
    index: int

    @nn.compact
    def __call__(self, u: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg, i = self.config, self.index
        b, t, _ = u.shape
        h, kv = cfg.num_attention_heads_per_layer[i], cfg.num_key_value_heads
        dh, rope = cfg.head_dim, cfg.rope(i)
        q = _dense_mod(cfg, h * dh, "wq", QK_INIT_SCALE)(u).reshape(
            b, t, h, dh)
        k = _dense_mod(cfg, kv * dh, "wk", QK_INIT_SCALE)(u).reshape(
            b, t, kv, dh)
        v = _dense_mod(cfg, kv * dh, "wv")(u).reshape(b, t, kv, dh)
        q = apply_rope(q, positions, rope).reshape(b, t, kv, h // kv, dh)
        k = apply_rope(k, positions, rope)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        ahead = positions[:, None] - positions[None, :]  # q_pos - k_pos
        seen = ahead >= 0
        if cfg.window(i) is not None:
            seen &= ahead < cfg.window(i)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(cfg.dtype),
                       v).reshape(b, t, h, dh)
        o = _gated(o, _dense_mod(cfg, h, "wg")(u))
        return _dense_mod(cfg, cfg.hidden_size, "wo")(
            o.reshape(b, t, h * dh))


class LagunaBlock(nn.Module):
    config: LagunaConfig
    index: int

    @nn.compact
    def __call__(self, h: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        u = _rms_mod(cfg, "rms_in")(h).astype(cfg.dtype)
        h = h + LagunaAttention(cfg, self.index, name="attn")(u, positions)
        u = _rms_mod(cfg, "rms_mlp")(h).astype(cfg.dtype)
        if cfg.mlp_layer_types[self.index] == "dense":
            return h + _SwiGLU(cfg, cfg.intermediate_size, name="mlp")(u)
        m = DroplessMoe(
            num_experts=cfg.num_experts, hidden=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok, score_fn="sigmoid",
            selection_bias=False, normalize=True,
            scaling=cfg.moe_routed_scaling_factor, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            down_init_scale=MLP_OUT_INIT_SCALE, name="moe")(u)
        return h + m + _SwiGLU(cfg, cfg.shared_expert_intermediate_size,
                               name="shared")(u)


class Laguna(nn.Module):
    """Decoder-only LM: ``(B, T) int tokens -> (B, T, vocab) float32
    logits`` (untied head).  ``train`` is accepted for Trainer
    compatibility."""

    config: LagunaConfig

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
            h = LagunaBlock(cfg, i, name=f"h_{i}")(h, positions)
        h = _rms_mod(cfg, "rms_out")(h).astype(cfg.dtype)
        return _dense_mod(cfg, cfg.vocab_size, "lm_head")(h).astype(
            jnp.float32)


# --------------------------------------------------- raw-param serving twins


def block_paged(cfg: LagunaConfig, p: dict, x: jnp.ndarray, index: int,
                store, positions: jnp.ndarray, live: jnp.ndarray):
    """One block on ``(b, cur, d)`` new tokens at ``positions`` ``(b,
    cur)`` through a ``generate._PagedKV`` store over this layer's pool
    (the store knows the layer's window): the tokens' K/V are written
    into their pages first, then the queries attend through the block
    table.  Mirrors :class:`LagunaBlock`.  ``live`` ``(b, cur)``: the
    rows that are real tokens; the others reach no routed expert.
    Returns ``(x, (chosen, counts) or None)``, the latter
    ``moe.dropless_moe``'s."""
    b, cur, _ = x.shape
    h, kv = cfg.num_attention_heads_per_layer[index], cfg.num_key_value_heads
    dh, rope, attn = cfg.head_dim, cfg.rope(index), p["attn"]
    u = _rms(p["rms_in"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    q = _mm(attn["wq"], u, cfg.dtype).reshape(b, cur, h, dh)
    k = _mm(attn["wk"], u, cfg.dtype).reshape(b, cur, kv, dh)
    v = _mm(attn["wv"], u, cfg.dtype).reshape(b, cur, kv, dh)
    store.write(apply_rope(k, positions, rope), v)
    o = _gated(store.attend(apply_rope(q, positions, rope)),
               _mm(attn["wg"], u, cfg.dtype))
    x = x + _mm(attn["wo"], o.reshape(b, cur, h * dh), cfg.dtype)
    u = _rms(p["rms_mlp"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    if cfg.mlp_layer_types[index] == "dense":
        return x + swiglu(p["mlp"], u, cfg.dtype), None
    m, routed = routed_and_shared(
        p["moe"], p["shared"], u, top_k=cfg.num_experts_per_tok,
        normalize=True, scaling=cfg.moe_routed_scaling_factor,
        dtype=cfg.dtype, live=live.reshape(-1))
    return x + m, routed


def forward_paged(cfg: LagunaConfig, params: dict, tokens: jnp.ndarray, pool,
                  table, pos: jnp.ndarray, active: jnp.ndarray,
                  impl: str | None = None, *, last=None,
                  routed: list | None = None):
    """``(b, cur)`` tokens at per-slot depths ``pos`` (or one shared
    scalar depth: a prefill chunk) through ``generate.WindowedPages``:
    ``(logits, pool)``.

    ``table`` is the pair ``(full layers' table, window layers' table)``
    the engine keeps (the second has the entries behind the window set to
    ``-1``, their pages freed), or ONE ``(b, max_pages)`` array both layer
    kinds read through (nothing freed).  The window mask alone decides
    what a sliding layer sees, so the two give the same logits.

    ``impl``: ``'einsum'`` or ``'kernel'`` (``ops.paged_attention``);
    ``None`` is the kernels on an accelerator and einsum on the CPU (the
    engine's rule for an unset ``paged_attn``; the einsum path's ``(b,
    max_pages, page, ...)`` tiles are for small sizes).  Under ``'kernel'``
    every layer works on its WHOLE stacked pool (writes scatter at
    ``[stratum, page, ...]``, the kernels' block specs pick the stratum),
    so no slice of a pool is ever a value of its own.

    ``last`` (a traced scalar; prefill): rows past it are the chunk's
    padding, and only row ``last`` goes through the head (``logits``
    ``(b, 1, vocab)``).  Rows of inactive slots and of padding are live
    nowhere: they reach no routed expert.  ``routed``, when a list, takes
    each expert layer's ``(chosen, counts)`` in layer order (a trace-time
    out-parameter, like the page store)."""
    from tpudp.models.generate import _PagedKV, _stack_pages

    if impl is None:
        impl = "einsum" if jax.default_backend() == "cpu" else "kernel"
    b, cur = tokens.shape
    pos = jnp.asarray(pos)
    positions = jnp.broadcast_to(pos, (b,))[:, None] + jnp.arange(cur)
    live = jnp.broadcast_to(active[:, None], (b, cur))
    if last is not None:
        live = live & (jnp.arange(cur) <= last)
    tables = dict(zip(pool._fields, table if isinstance(table, (tuple, list))
                      else (table, table)))
    whole = impl == "kernel"
    bufs = {kind: tuple(getattr(pool, kind)) for kind in pool._fields}
    layers: dict = {kind: [] for kind in pool._fields}
    x = params["wte"]["embedding"].astype(cfg.dtype)[tokens]
    for i in range(cfg.num_hidden_layers):
        kind, j = cfg.pool_layer(i)
        store = _PagedKV(
            cfg, bufs[kind] if whole else tuple(buf[j] for buf in bufs[kind]),
            tables[kind], pos, active, grouped=True, impl=impl,
            layer=j if whole else None, window=cfg.window(i))
        x, out = block_paged(cfg, params[f"h_{i}"], x, i, store, positions,
                             live)
        if whole:
            bufs[kind] = store.pages
        else:
            layers[kind].append(store.pages)
        if out is not None and routed is not None:
            routed.append(out)
    if last is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    x = _rms(params["rms_out"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    new_pool = type(pool)(*(
        type(part)(*bufs[kind]) if whole else _stack_pages(part, layers[kind])
        for kind, part in zip(pool._fields, pool)))
    return (_mm(params["lm_head"], x, cfg.dtype).astype(jnp.float32),
            new_pool)
