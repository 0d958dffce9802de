"""LFM2-MoE decoder (LiquidAI LFM2-8B-A1B, ``model_type`` ``lfm2_moe``):
blocks of two kinds chosen per layer, and an expert layer held by share.

With ``h`` the residual stream and ``RMS`` an RMSNorm with a learned scale,
every projection without bias:

  * block ``i``: ``h = h + op_i(RMS(h))``; ``h = h + ffn_i(RMS(h))``; after
    the last block a final RMSNorm and the tied embedding as output head.
  * ``op`` where ``layer_types[i] == 'conv'`` (:class:`ShortConv`): a gated
    short convolution.  ``B, C, x = split3(W_in u)``; ``z = B * x``;
    ``c_t = sum_j w[:, j] * z_{t-(L-1)+j}`` (depthwise and causal, ``z``
    zero before the sequence, ``L = conv_L_cache`` taps); ``W_out (C * c)``.
  * ``op`` where it is ``'full_attention'`` (:class:`QkNormAttention`):
    grouped-query causal attention with an RMSNorm over each head of q and
    k (one scale vector each) before rotate-half RoPE.
  * ``ffn`` of the first ``num_dense_layers`` blocks: SwiGLU,
    ``W_2 (silu(W_1 u) * W_3 u)``, width ``intermediate_size``.
  * ``ffn`` of the others: ``tpudp.models.moe.DroplessMoe``, SwiGLU experts
    of width ``moe_intermediate_size``, top-``k`` of ``sigmoid(logits) +
    expert_bias``, weights from the unbiased scores.  The module holds
    ``num_experts`` of the ``num_experts_routed`` experts the router
    scores, from ``first_expert`` on: one chip's share of an
    expert-parallel layer (docs/ARCHITECTURE.md, "The expert share").

Reuses ``llama.apply_rope`` and ``ops.attention.multihead_attention`` (so
``attn_impl='flash'`` is the owned flash kernel).  ``train`` is accepted for
Trainer compatibility.

``remat`` wraps each block in ``nn.remat`` under the model's one policy,
``REMAT_POLICY``.  Beside its input (``2d`` bytes a token a layer in bf16,
``d = hidden_size``) a block keeps for its backward pass, each named with
``checkpoint_name`` where it is produced:

  * the experts its tokens chose (``moe.ROUTE_NAME``): a recomputed choice
    can break a near tie the other way, and the backward pass would run
    other experts than the forward pass did;
  * an attention block: flash's output and log-sum-exp
    (``flash_attention.OUT_NAME``, ``LSE_NAME``; ``2d + 4h`` bytes a token
    for ``h`` heads).  They are all the backward kernels read of the
    forward one, so the step runs one forward flash call, not two;
  * a conv block: ``in_proj``'s output before the split (``IN_PROJ_NAME``;
    ``6d`` bytes a token), the block's largest product;
  * every block: what its operator adds to the residual stream, the output
    of ``wo`` / ``out_proj`` (``OP_NAME``; ``2d`` bytes a token).

Everything else is recomputed: q, k, v with their norm and RoPE, the
convolution's elementwise tail, the norms, the dense SwiGLU
(its ``gate`` and ``up`` are ``4 x intermediate_size`` bytes a token: at the
release's widths they no longer fit a v5e beside the rest) and the whole
expert layer, whose row buffers have ``T x k`` rows whatever the held
experts' load (which is why the blocks are under remat at all): none of
them is ever kept.  There is no knob: a step that cannot hold these bytes
takes a smaller batch or ``grad_accum``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from tpudp.models.llama import apply_rope
from tpudp.models.moe import ROUTE_NAME, DroplessMoe
from tpudp.ops.flash_attention import LSE_NAME, OUT_NAME

IN_PROJ_NAME = "conv_in_proj"  # ShortConv's in_proj output, before the split
OP_NAME = "lfm2_op"  # what the block's operator adds to the residual stream
# What a block under ``remat`` keeps beside its input (module docstring).
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    ROUTE_NAME, OUT_NAME, LSE_NAME, IN_PROJ_NAME, OP_NAME)


@dataclass(frozen=True)
class Lfm2Config:
    """The published ``config.json``'s keys under their own names, then
    what this repo adds.  Defaults are a small model, not the release."""

    vocab_size: int = 1024
    hidden_size: int = 256
    intermediate_size: int = 512  # dense SwiGLU width
    moe_intermediate_size: int = 128  # expert SwiGLU width
    num_hidden_layers: int = 4
    num_dense_layers: int = 1
    layer_types: tuple = ("conv", "full_attention", "conv", "conv")
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    num_experts: int = 8  # HELD here
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    max_position_embeddings: int = 128_000  # documentation; RoPE has no table
    # --- this repo's
    num_experts_routed: int | None = None  # None: all routed are held
    first_expert: int = 0
    attn_impl: str = "dense"  # 'dense' | 'flash'
    moe_impl: str = "gmm"  # 'gmm' | 'dense'
    remat: bool = False
    dtype: Any = jnp.float32

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}; choose "
                             "from 'dense', 'flash'")
        if self.conv_bias:
            raise ValueError("conv_bias=True is not implemented (the "
                             "release has none)")
        h, kv = self.num_attention_heads, self.num_key_value_heads
        if self.hidden_size % h or h % kv or (self.hidden_size // h) % 2:
            raise ValueError(
                f"hidden_size {self.hidden_size} / {h} heads / {kv} KV heads "
                "must divide evenly, with an even head size for RoPE")

    @classmethod
    def from_dict(cls, config: dict, **overrides) -> "Lfm2Config":
        """From a ``config.json``-style mapping: the keys this class has
        are taken, every other key ignored."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{**{k: v for k, v in config.items() if k in names},
                      **overrides})


def _rms(cfg: Lfm2Config, name: str) -> nn.RMSNorm:
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32, name=name)


def _dense(cfg: Lfm2Config, features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name)


class ShortConv(nn.Module):
    """The gated short-convolution operator, ``(B, T, d) -> (B, T, d)``."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        d, taps = cfg.hidden_size, cfg.conv_L_cache
        gate_b, gate_c, x = jnp.split(checkpoint_name(
            _dense(cfg, 3 * d, "in_proj")(u), IN_PROJ_NAME), 3, axis=-1)
        w = self.param("conv_w", nn.initializers.variance_scaling(
            1.0, "fan_in", "uniform", in_axis=1, out_axis=0), (d, taps),
            jnp.float32)
        # three shifted multiply-adds in float32 (one fused elementwise
        # pass), not a convolution op: z_{t-(taps-1)+j} is z shifted right
        z = (gate_b * x).astype(jnp.float32)
        t = z.shape[1]
        padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        c = sum(w[:, j] * padded[:, j:j + t] for j in range(taps))
        return _dense(cfg, d, "out_proj")(
            (gate_c.astype(jnp.float32) * c).astype(cfg.dtype))


class QkNormAttention(nn.Module):
    """Causal GQA with per-head RMSNorm on q and k before RoPE."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, u: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        b, t, d = u.shape
        h, kv = cfg.num_attention_heads, cfg.num_key_value_heads
        dh = d // h
        q = _dense(cfg, h * dh, "wq")(u).reshape(b, t, h, dh)
        k = _dense(cfg, kv * dh, "wk")(u).reshape(b, t, kv, dh)
        v = _dense(cfg, kv * dh, "wv")(u).reshape(b, t, kv, dh)
        # norm and rotation in float32, one rounding on the way out
        q = apply_rope(_rms(cfg, "q_norm")(q), positions,
                       cfg.rope_theta).astype(cfg.dtype)
        k = apply_rope(_rms(cfg, "k_norm")(k), positions,
                       cfg.rope_theta).astype(cfg.dtype)
        if kv != h:  # each KV head serves h / kv query heads
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
        from tpudp.ops.attention import multihead_attention

        out = multihead_attention(q, k, v, causal=True, impl=cfg.attn_impl,
                                  dtype=cfg.dtype)
        return _dense(cfg, d, "wo")(out.reshape(b, t, d))


class Lfm2Block(nn.Module):
    config: Lfm2Config
    index: int

    @nn.compact
    def __call__(self, h: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg, i = self.config, self.index
        u = _rms(cfg, "rms_op")(h)
        if cfg.layer_types[i] == "conv":
            op = ShortConv(cfg, name="conv")(u)
        else:
            op = QkNormAttention(cfg, name="attn")(u, positions)
        h = h + checkpoint_name(op, OP_NAME)
        u = _rms(cfg, "rms_ffn")(h)
        if i < cfg.num_dense_layers:
            gate = _dense(cfg, cfg.intermediate_size, "w1")(u)
            up = _dense(cfg, cfg.intermediate_size, "w3")(u)
            return h + _dense(cfg, cfg.hidden_size, "w2")(nn.silu(gate) * up)
        return h + DroplessMoe(
            num_experts=cfg.num_experts, hidden=cfg.moe_intermediate_size,
            top_k=cfg.num_experts_per_tok,
            num_experts_routed=cfg.num_experts_routed,
            first_expert=cfg.first_expert, score_fn="sigmoid",
            selection_bias=cfg.use_expert_bias,
            normalize=cfg.norm_topk_prob,
            scaling=cfg.routed_scaling_factor, impl=cfg.moe_impl,
            dtype=cfg.dtype, name="moe")(u)


class Lfm2(nn.Module):
    """Decoder-only LM: ``(B, T) int tokens -> (B, T, vocab) float32
    logits`` through the tied embedding."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, tokens: jnp.ndarray,
                 train: bool = False) -> jnp.ndarray:
        del train
        cfg = self.config
        positions = jnp.arange(tokens.shape[1])
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       name="wte")
        block = nn.remat(Lfm2Block, policy=REMAT_POLICY) if cfg.remat \
            else Lfm2Block
        h = wte(tokens)
        for i in range(cfg.num_hidden_layers):
            h = block(cfg, i, name=f"h_{i}")(h, positions)
        h = _rms(cfg, "rms_out")(h)
        return wte.attend(h.astype(cfg.dtype)).astype(jnp.float32)
