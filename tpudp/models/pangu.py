"""openPangu-Ultra-MoE decoder (``model_type`` ``pangu_ultra_moe``): latent
attention (MLA), sandwich norms, leading dense SwiGLU layers, then expert
layers of routed + shared SwiGLU experts held by share.

With ``h`` the residual stream, ``RMS`` an RMSNorm with a learned scale,
no projection with a bias, ``H`` heads, ``dn`` / ``dr`` / ``dv`` the
no-position, rotary and value head sizes and ``c`` = ``kv_lora_rank``:

  * block: ``h += RMS_post_attn(attn(RMS_in(h)))``;
    ``h += RMS_post_mlp(mlp(RMS_pre_mlp(h)))`` (the sandwich norm); after
    the last block ``RMS_out`` and an untied head over the held
    vocabulary rows.
  * attention: ``c_q = RMS(u W_qa)``; ``[q_nope | q_rope]_h = c_q W_qb``;
    ``[c_kv | k_rope] = u W_kva``, ``c_kv = RMS(c_kv)``; rotate-half RoPE
    on ``q_rope`` of every head and on the one shared ``k_rope``.  A token
    leaves ``(c_kv, RoPE(k_rope))`` behind: ``c + dr`` values a layer.
    EXPANDED (the module's ``__call__``): ``[k_nope | v]_h = c_kv W_kvb``,
    causal softmax of ``([q_nope | q_rope] . [k_nope | k_rope]) /
    sqrt(dn + dr)``, ``W_o concat_h(p v)``.  ABSORBED (the serving twin,
    the same function in another association): ``q_lat_h = q_nope_h
    W_kvb,k,h^T`` attends ``c_kv`` itself, ``o_h = (sum_t p_t c_kv,t)
    W_kvb,v,h``, so nothing per head is ever cached or expanded.
  * mlp of the first ``num_dense_layers`` blocks: SwiGLU of width
    ``intermediate_size``; of the others ``DroplessMoe`` (sigmoid scores,
    no groups, no selection bias, top-``k``, normalised, times
    ``routed_scaling_factor``; this chip HOLDS ``num_experts`` of the
    ``num_experts_routed`` from ``first_expert`` on and leaves the others'
    part out) plus one unrouted shared SwiGLU expert every chip computes
    alike.

Parameters are made in ``param_dtype`` (no float32 copy of a served tree
ever exists).  The multi-token-prediction module is not implemented.

Serving: the raw-param twins at the end (``forward_paged``) are what
``generate._forward_paged`` dispatches to for this family.  They keep the
latent cache in ``generate.LatentPages`` and read it through the block
table (``ops.paged_attention.latent_paged_attention``); there is no dense
cache twin, so ``generate()`` / ``beam_search()`` refuse the config.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpudp.models.llama import apply_rope
from tpudp.models.moe import DroplessMoe, routed_and_shared, swiglu


@dataclass(frozen=True)
class PanguConfig:
    """The published ``config.json``'s keys under their own names where
    this repo has no name of its own, then what this repo adds.  Defaults
    are a small model, not the release."""

    vocab_size: int = 512  # rows HELD here
    hidden_size: int = 128
    intermediate_size: int = 256  # dense SwiGLU width
    moe_intermediate_size: int = 64  # one expert's SwiGLU width
    num_hidden_layers: int = 3
    first_k_dense_replace: int = 1  # leading dense layers
    num_attention_heads: int = 4
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    n_routed_experts: int = 4  # HELD here
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25_600_000.0
    max_position_embeddings: int = 131_072
    # --- this repo's
    num_experts_routed: int | None = None  # None: all routed are held
    first_expert: int = 0
    attn_impl: str = "dense"  # the module's expanded attention is XLA's
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    #: which page type the serve engine keeps this family's cache in
    page_layout = "latent"

    def __post_init__(self):
        if self.attn_impl != "dense":
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}: latent "
                             "attention runs as XLA contractions ('dense')")
        if self.qk_rope_head_dim % 2:
            raise ValueError("RoPE needs an even qk_rope_head_dim")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is implemented, got "
                             f"n_shared_experts={self.n_shared_experts}")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers")

    @classmethod
    def from_dict(cls, config: dict, **overrides) -> "PanguConfig":
        """From a ``config.json``-style mapping: the keys this class has
        are taken, every other key ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{**{k: v for k, v in config.items() if k in names},
                      **overrides})

    # the names the serve engine reads of every family
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_seq_len(self) -> int:
        return self.max_position_embeddings

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def d_model(self) -> int:
        return self.hidden_size

    @property
    def score_scale(self) -> float:
        return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


#: Where the untrained model starts (what a checkpoint would replace).  A
#: norm that closes a sublayer starts at a tenth and the embedding at unit
#: variance, so that a token's residual stream is mostly the token's own.
#: With ones and flax's 1/sqrt(d) embedding every sublayer adds a unit-RMS
#: vector that is nearly the same for all tokens (attention at random
#: weights averages its context), the router sees that common vector, and
#: 8 of 256 experts take a quarter of all assignments; a trained router is
#: near even (its balance loss), and so is this start: PERF.md section 6.
POST_NORM_SCALE = 0.1
EMBED_STD = 1.0


def _rms_mod(cfg: PanguConfig, name: str, scale: float = 1.0) -> nn.RMSNorm:
    return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=jnp.float32,
                      param_dtype=cfg.param_dtype, name=name,
                      scale_init=nn.initializers.constant(scale))


def _dense_mod(cfg: PanguConfig, features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)


class _SwiGLU(nn.Module):
    config: PanguConfig
    width: int

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        gate = _dense_mod(cfg, self.width, "w1")(u)
        up = _dense_mod(cfg, self.width, "w3")(u)
        return _dense_mod(cfg, cfg.hidden_size, "w2")(nn.silu(gate) * up)


class LatentAttention(nn.Module):
    """Causal MLA in its EXPANDED form, ``(B, T, d) -> (B, T, d)``."""

    config: PanguConfig

    @nn.compact
    def __call__(self, u: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        b, t, _ = u.shape
        h, c = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        c_q = _rms_mod(cfg, "q_norm")(_dense_mod(cfg, cfg.q_lora_rank,
                                                 "wq_a")(u))
        q = _dense_mod(cfg, h * (dn + dr), "wq_b")(
            c_q.astype(cfg.dtype)).reshape(b, t, h, dn + dr)
        kv = _dense_mod(cfg, c + dr, "wkv_a")(u)
        c_kv = _rms_mod(cfg, "kv_norm")(kv[..., :c]).astype(cfg.dtype)
        k_rope = apply_rope(kv[..., None, c:], positions, cfg.rope_theta)
        q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
        kvb = _dense_mod(cfg, h * (dn + dv), "wkv_b")(c_kv).reshape(
            b, t, h, dn + dv)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
        k = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))], axis=-1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * cfg.score_scale
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, kvb[..., dn:])
        return _dense_mod(cfg, cfg.hidden_size, "wo")(
            o.reshape(b, t, h * dv))


class PanguBlock(nn.Module):
    config: PanguConfig
    index: int

    @nn.compact
    def __call__(self, h: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        a = LatentAttention(cfg, name="attn")(_rms_mod(cfg, "rms_in")(h),
                                              positions)
        h = h + _rms_mod(cfg, "rms_post_attn",
                         POST_NORM_SCALE)(a).astype(cfg.dtype)
        u = _rms_mod(cfg, "rms_pre_mlp")(h).astype(cfg.dtype)
        if self.index < cfg.first_k_dense_replace:
            m = _SwiGLU(cfg, cfg.intermediate_size, name="mlp")(u)
        else:
            m = DroplessMoe(
                num_experts=cfg.n_routed_experts,
                hidden=cfg.moe_intermediate_size,
                top_k=cfg.num_experts_per_tok,
                num_experts_routed=cfg.num_experts_routed,
                first_expert=cfg.first_expert, score_fn="sigmoid",
                selection_bias=False, normalize=cfg.norm_topk_prob,
                scaling=cfg.routed_scaling_factor,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="moe")(u)
            m = m + _SwiGLU(cfg, cfg.moe_intermediate_size, name="shared")(u)
        return h + _rms_mod(cfg, "rms_post_mlp",
                            POST_NORM_SCALE)(m).astype(cfg.dtype)


class Pangu(nn.Module):
    """Decoder-only LM: ``(B, T) int tokens -> (B, T, vocab) float32
    logits`` over the held vocabulary rows (untied head).  ``train`` is
    accepted for Trainer compatibility."""

    config: PanguConfig

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
            h = PanguBlock(cfg, i, name=f"h_{i}")(h, positions)
        h = _rms_mod(cfg, "rms_out")(h).astype(cfg.dtype)
        return _dense_mod(cfg, cfg.vocab_size, "lm_head")(h).astype(
            jnp.float32)


# --------------------------------------------------- raw-param serving twins


def _rms(p: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Exactly the module's RMSNorm (flax apply on the raw subtree)."""
    return nn.RMSNorm(epsilon=eps, dtype=jnp.float32).apply(
        {"params": p}, x)


def _mm(p: dict, x: jnp.ndarray, dtype) -> jnp.ndarray:
    return x.astype(dtype) @ p["kernel"].astype(dtype)


def latent_pad(cfg: PanguConfig) -> int:
    """Width the rotary part is stored at: up to a multiple of the 128
    lanes, so that neither page buffer has a minor dimension XLA would
    pad (or relayout) behind our back."""
    return -(-cfg.qk_rope_head_dim // 128) * 128


def absorbed_queries(cfg: PanguConfig, p: dict, u: jnp.ndarray,
                     positions: jnp.ndarray):
    """``u`` ``(b, cur, d)`` normed block input at ``positions`` ``(b,
    cur)`` -> ``(q_lat (b, cur, H, c), q_rope (b, cur, H, pad), c_kv (b,
    cur, c), k_rope (b, cur, pad))``: the absorbed queries and what the
    tokens leave in the cache, rotary parts zero-padded to
    :func:`latent_pad`."""
    b, cur, _ = u.shape
    h, c = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c_q = _rms(p["q_norm"], _mm(p["wq_a"], u, cfg.dtype), cfg.rms_norm_eps)
    q = _mm(p["wq_b"], c_q, cfg.dtype).reshape(b, cur, h, dn + dr)
    kv = _mm(p["wkv_a"], u, cfg.dtype)
    c_kv = _rms(p["kv_norm"], kv[..., :c], cfg.rms_norm_eps).astype(cfg.dtype)
    k_rope = apply_rope(kv[..., None, c:], positions, cfg.rope_theta)[:, :, 0]
    q_rope = apply_rope(q[..., dn:], positions, cfg.rope_theta)
    w_k = p["wkv_b"]["kernel"].astype(cfg.dtype).reshape(
        c, h, dn + cfg.v_head_dim)[..., :dn]
    q_lat = jnp.einsum("bqhn,chn->bqhc", q[..., :dn], w_k)
    grow = latent_pad(cfg) - dr
    return (q_lat, jnp.pad(q_rope, ((0, 0),) * 3 + ((0, grow),)), c_kv,
            jnp.pad(k_rope, ((0, 0),) * 2 + ((0, grow),)))


def block_paged(cfg: PanguConfig, p: dict, x: jnp.ndarray, index: int,
                store, positions: jnp.ndarray, live: jnp.ndarray):
    """One block on ``(b, cur, d)`` new tokens at ``positions`` through a
    ``generate._LatentKV`` store: the tokens' latents are written into
    their pages first, then the absorbed queries attend through the block
    table.  Mirrors :class:`PanguBlock` (the module's attention is the
    same function expanded).  ``live`` ``(b, cur)``: the rows that are
    real tokens; the others reach no routed expert.  Returns ``(x,
    (chosen, counts) or None)``, the latter :func:`dropless_moe`'s."""
    b, cur, d = x.shape
    h, c = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    attn = p["attn"]
    u = _rms(p["rms_in"], x, cfg.rms_norm_eps)
    q_lat, q_rope, c_kv, k_rope = absorbed_queries(cfg, attn, u, positions)
    store.write(c_kv, k_rope)
    o_lat = store.attend(q_lat, q_rope)  # (b, cur, H, c)
    w_v = attn["wkv_b"]["kernel"].astype(cfg.dtype).reshape(
        c, h, dn + dv)[..., dn:]
    o = jnp.einsum("bqhc,chv->bqhv", o_lat, w_v).reshape(b, cur, h * dv)
    x = x + _rms(p["rms_post_attn"], _mm(attn["wo"], o, cfg.dtype),
                 cfg.rms_norm_eps).astype(cfg.dtype)
    u = _rms(p["rms_pre_mlp"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    routed = None
    if index < cfg.first_k_dense_replace:
        m = swiglu(p["mlp"], u, cfg.dtype)
    else:
        m, routed = routed_and_shared(
            p["moe"], p["shared"], u, top_k=cfg.num_experts_per_tok,
            first_expert=cfg.first_expert, normalize=cfg.norm_topk_prob,
            scaling=cfg.routed_scaling_factor, dtype=cfg.dtype,
            live=live.reshape(-1))
    return x + _rms(p["rms_post_mlp"], m,
                    cfg.rms_norm_eps).astype(cfg.dtype), routed


def forward_paged(cfg: PanguConfig, params: dict, tokens: jnp.ndarray, pool,
                  table: jnp.ndarray, pos: jnp.ndarray, active: jnp.ndarray,
                  impl: str | None = None, *, last=None,
                  routed: list | None = None):
    """``(b, cur)`` tokens at per-slot depths ``pos`` (or one shared
    scalar depth: a prefill chunk) through the latent page pool:
    ``(logits, pool)``.  Every layer works on the WHOLE stacked pool
    buffers (writes scatter at ``[layer, page, ...]``, reads index the
    layer inside the gather or the kernel's block spec), so no slice of
    the pool is ever a value of its own and nothing restacks.

    ``impl``: ``'einsum'`` or ``'kernel'``
    (``ops.paged_attention.latent_paged_attention``); ``None`` is the
    kernel on an accelerator and einsum on the CPU (the engine's rule for
    an unset ``paged_attn``).

    ``last`` (a traced scalar; prefill): rows past it are the chunk's
    padding, and only row ``last`` goes through the head (``logits``
    ``(b, 1, vocab)``).  Rows of inactive slots and of padding are live
    nowhere: they reach no routed expert.  ``routed``, when a list, takes
    each expert layer's ``(chosen, counts)`` in layer order (a
    trace-time out-parameter, like the page store)."""
    from tpudp.models.generate import _LatentKV

    if impl is None:
        impl = "einsum" if jax.default_backend() == "cpu" else "kernel"
    b, cur = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
    positions = pos[:, None] + jnp.arange(cur)
    live = jnp.broadcast_to(active[:, None], (b, cur))
    if last is not None:
        live = live & (jnp.arange(cur) <= last)
    x = params["wte"]["embedding"].astype(cfg.dtype)[tokens]
    store = _LatentKV(cfg, tuple(pool), table, pos, active, impl)
    for i in range(cfg.num_hidden_layers):
        store.layer = i
        x, out = block_paged(cfg, params[f"h_{i}"], x, i, store, positions,
                             live)
        if out is not None and routed is not None:
            routed.append(out)
    if last is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    x = _rms(params["rms_out"], x, cfg.rms_norm_eps).astype(cfg.dtype)
    return (_mm(params["lm_head"], x, cfg.dtype).astype(jnp.float32),
            type(pool)(*store.pages))
