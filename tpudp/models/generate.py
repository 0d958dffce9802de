"""Autoregressive generation for the GPT-2 and LLaMA families — KV-cached
decode.

The reference has no inference path at all (it is a CNN training
assignment, SURVEY.md §0); a complete LM framework needs one.  TPU-first
design:

  * ONE jitted program: prompt prefill + ``max_new_tokens`` decode steps
    under ``lax.scan`` — static shapes throughout (the cache is a fixed
    ``(layers, batch, max_len, kv_heads, head_dim)`` buffer written with
    ``dynamic_update_slice``; attention masks by position instead of
    growing the sequence), so XLA compiles it once and the MXU sees fixed
    matmul shapes every step.
  * The decode step drives the raw param tree directly (same
    ``h_i/attn/qkv`` layout the training model creates — the raw-param
    twin pattern of ``tpudp.parallel.pipeline``); a parity test pins it to
    the training model's logits exactly, so train and serve can never
    drift.
  * Greedy (``temperature=0``) or temperature sampling with a JAX PRNG key.

Dense-MLP, dense-attention configs.  Both decoder families dispatch here:
GPT-2 (learned positions, LayerNorm/GELU, tied head) and LLaMA (RoPE,
RMSNorm/SwiGLU, untied head — ``tpudp.models.llama``'s raw-param twins).
Cache memory is ``2 * L * B * max_len * d_model * kv_heads / num_heads``
— GQA configs shrink it by the group factor, and the grouped attention
in ``llama.block_decode`` never widens it back; for generation lengths
where the cache is the constraint, raise ``max_len`` only as far as
needed (static shape).

Two more families are served through the PAGED forward only
(:func:`_forward_paged`): latent attention with routed experts
(``tpudp.models.pangu``), whose cache is :class:`LatentPages`, and
window and full attention layers mixed, with routed experts
(``tpudp.models.laguna``), whose cache is :class:`WindowedPages`.
Neither has a dense cache twin, so ``generate()`` and ``beam_search()``
refuse them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from tpudp.models.gpt2 import GPT2Config, embed_tokens, lm_head


class KVCache(NamedTuple):
    """The DENSE arena: ``generate()``, ``beam_search()``, the unpaged
    engine's slot rows, the speculation draft's scratch and the copy
    prefix cache's blocks.  The page pool is not stored in this form:
    :class:`KVPages`."""

    k: jnp.ndarray  # (layers, batch, max_len, kv_heads, head_dim)
    v: jnp.ndarray

    @classmethod
    def geometry(cls, cfg) -> tuple:
        """What two models must share to share one page pool."""
        # GQA configs (LlamaConfig.kv_heads < num_heads) allocate the
        # cache at KV width — the group factor is exactly the decode
        # memory GQA exists to save; MHA configs (GPT-2) are unchanged.
        return (cfg.num_layers, getattr(cfg, "kv_heads", cfg.num_heads),
                cfg.d_model // cfg.num_heads, str(cfg.dtype))

    @classmethod
    def zeros(cls, cfg, batch: int, max_len: int) -> "KVCache":
        layers, kv_heads, dh, _ = cls.geometry(cfg)
        shape = (layers, batch, max_len, kv_heads, dh)
        return cls(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


class KVPages(NamedTuple):
    """Page-pool buffers of the ``heads`` layout (GPT-2, LLaMA) in the
    compute dtype: a cached token is ONE row a layer, its ``kv_heads``
    heads of ``head_dim`` side by side on the lanes.  This is the form
    the paged kernels read (``tpudp.ops.paged_attention``: a page block
    is ``(page_tokens, kv_heads * head_dim)`` and a head is a lane slice
    of the row), so the step programs touch the pool only through row
    writes into the donated buffers and page reads by table value.  The
    dense arena's ``(..., kv_heads, head_dim)`` minor pair, with a head
    of 64 on the 128 lanes, made XLA keep the pool in one tiling and
    hand the kernels another: four copies of the whole pool a program
    (PERF.md section 6, PR 35).  The arena itself (:class:`KVCache`)
    keeps its shape; only pages are stored flat."""

    k: jnp.ndarray  # (layers, pages, page_tokens, kv_heads * head_dim)
    v: jnp.ndarray

    geometry = KVCache.geometry

    @classmethod
    def zeros(cls, cfg, num_pages: int, page_tokens: int) -> "KVPages":
        layers, kv_heads, dh, _ = cls.geometry(cfg)
        shape = (layers, num_pages, page_tokens, kv_heads * dh)
        return cls(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


class Int8Pages(NamedTuple):
    """Quantized page-pool buffers (``Engine(kv_dtype="int8")``): k/v
    stored int8 in :class:`KVPages`' form (one token row of ``kv_heads *
    head_dim`` values a layer) with per-(layer, page, token, head) fp32
    scales — half
    the KV bytes per token of an fp32 pool behind the SAME block-table
    indirection (block ids, allocation order, and the radix tree are
    identical to the fp pool; only page payloads quantize).  Symmetric
    absmax quantization over the head dim: ``scale = max|x| / 127``,
    ``q = round(x / scale)`` — dequantized reads feed the exact same
    attention math, so outputs track the fp engine within quantization
    tolerance rather than bit-exactly (tests bound it)."""

    k: jnp.ndarray        # (layers, pages, page_tokens, kv_heads * dh) int8
    v: jnp.ndarray
    k_scale: jnp.ndarray  # (layers, pages, page_tokens, kv_heads) fp32
    v_scale: jnp.ndarray

    geometry = KVCache.geometry

    @classmethod
    def zeros(cls, cfg, num_pages: int, page_tokens: int) -> "Int8Pages":
        layers, kv_heads, dh, _ = cls.geometry(cfg)
        shape = (layers, num_pages, page_tokens, kv_heads * dh)
        scales = (layers, num_pages, page_tokens, kv_heads)
        return cls(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                   jnp.ones(scales, jnp.float32),
                   jnp.ones(scales, jnp.float32))


class LatentPages(NamedTuple):
    """Page-pool buffers of a latent-attention (MLA) family
    (``tpudp.models.pangu``): a token leaves ONE row a layer, its
    normalised latent ``c_kv`` and its rotated shared key ``k_rope``, in
    the compute dtype; nothing per head.  The two parts live in two
    buffers, each with a minor dimension that is a multiple of the 128
    lanes (``k_rope`` zero-padded up to one: ``pangu.latent_pad``; 512 + 64
    would be 4.5 lane tiles in one buffer), so that XLA has no reason to
    pad or relayout either: the step programs touch the pool only through
    row writes into the donated buffers and page gathers out of them.
    Block ids, the table, the radix tree and the trailing scratch page are
    the other page types' (:class:`KVPages`, :class:`Int8Pages`)."""

    c: jnp.ndarray  # (layers, pages, page_tokens, kv_lora_rank)
    r: jnp.ndarray  # (layers, pages, page_tokens, latent_pad)

    @classmethod
    def geometry(cls, cfg) -> tuple:
        from tpudp.models.pangu import latent_pad

        return ("latent", cfg.num_layers, cfg.kv_lora_rank, latent_pad(cfg),
                str(jnp.dtype(cfg.dtype)))

    @classmethod
    def zeros(cls, cfg, num_pages: int, page_tokens: int) -> "LatentPages":
        _, layers, c, r, _ = cls.geometry(cfg)
        return cls(jnp.zeros((layers, num_pages, page_tokens, c), cfg.dtype),
                   jnp.zeros((layers, num_pages, page_tokens, r), cfg.dtype))


class WindowedPages(NamedTuple):
    """Page pools of a family whose attention layers are of two kinds
    (``tpudp.models.laguna``): ``full`` holds the full-attention layers'
    K/V and ``window`` the sliding-window layers', each a :class:`KVPages`
    over the layers of its kind (both kinds have the same KV heads and
    head size, so one row width; a pool a kind because their pages live
    differently long).  A full layer's page stays until its request
    ends.  A window layer's page is dead once the window has passed its
    last token, so the engine keeps a block table a pool, frees the
    window table's entries behind the window, and sizes the window pool
    by the window and not by the context (``window_pages``); each pool
    has its own trailing scratch page.  A library caller with nothing to
    free passes one table for both and equal counts."""

    full: KVPages
    window: KVPages

    @classmethod
    def geometry(cls, cfg) -> tuple:
        kinds = tuple(kind == "sliding_attention"
                      for kind in cfg.layer_types)
        return ("windowed", kinds, cfg.sliding_window,
                cfg.num_key_value_heads, cfg.head_dim,
                str(jnp.dtype(cfg.dtype)))

    @classmethod
    def zeros(cls, cfg, num_pages: int, page_tokens: int,
              window_pages: int | None = None) -> "WindowedPages":
        """``num_pages`` pages a full layer (scratch included, as the
        other page types count them) and ``window_pages`` a window layer:
        ``None`` the same count."""
        _, kinds, _, kv_heads, dh, _ = cls.geometry(cfg)
        wp = num_pages if window_pages is None else window_pages

        def pages(layers, count):
            shape = (layers, count, page_tokens, kv_heads * dh)
            return KVPages(jnp.zeros(shape, cfg.dtype),
                           jnp.zeros(shape, cfg.dtype))

        return cls(pages(kinds.count(False), num_pages),
                   pages(kinds.count(True), wp))


def page_layout(cfg) -> str:
    """``'heads'`` (K and V per KV head: GPT-2, LLaMA; :class:`KVPages`
    or :class:`Int8Pages`), ``'latent'`` (:class:`LatentPages`) or
    ``'windowed'`` (:class:`WindowedPages`), from the model config."""
    return getattr(cfg, "page_layout", "heads")


def page_type(cfg, kv_dtype: str | None = None):
    """The page-pool pytree class a config's cache lives in.  Each has
    ``zeros(cfg, num_pages, page_tokens)`` and ``geometry(cfg)``, the
    tuple two models must share to share one pool."""
    layout = page_layout(cfg)
    if layout != "heads":
        if kv_dtype is not None:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} is not implemented for {layout} "
                "pages: they are kept in the compute dtype")
        return LatentPages if layout == "latent" else WindowedPages
    return Int8Pages if kv_dtype == "int8" else KVPages


def _quantize_kv(x: jnp.ndarray):
    """(..., dh) fp -> (int8 payload, fp32 per-vector scale).  A zero
    vector keeps scale 1 so dequantization stays exact-zero."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _token_rows(x: jnp.ndarray) -> jnp.ndarray:
    """``(..., kv_heads, dh)`` -> ``(..., kv_heads * dh)``: a token's heads
    side by side, the stored form of a ``heads`` page row."""
    return x.reshape(*x.shape[:-2], -1)


def gather_pages(cfg, pool, table: jnp.ndarray) -> KVCache:
    """Materialize the logical dense view of a paged KV arena: per-slot
    block table ``(num_slots, max_pages)`` int32 into a page pool
    (:class:`KVPages` or :class:`Int8Pages` of shape ``(layers,
    num_pages+1, page_tokens, kv_heads * dh)``; the LAST page is the write
    scratch) -> ``(layers, num_slots, max_pages*page_tokens, kv_heads,
    dh)`` KVCache in ``cfg.dtype`` (the gathered rows split back into
    heads: the arena's shape).

    Unmapped entries (``-1``) clamp to the scratch page: their garbage
    lands only at positions beyond the owning slot's length, which the
    attention visibility mask already excludes — exactly the standing
    garbage-beyond-``pos`` contract of the dense arena, so the gathered
    view's attention output is bit-identical to reading dense rows
    holding the same values."""
    scratch = pool.k.shape[1] - 1
    tbl = jnp.where(table >= 0, table, scratch)
    kv_heads = KVCache.geometry(cfg)[1]

    def grab(buf):  # (L, P, T, w) -> (L, S, M*T, w), w a row or its scales
        g = buf[:, tbl]  # (L, S, M, T, w) advanced-index gather
        return g.reshape(g.shape[0], g.shape[1], -1, g.shape[-1])

    def heads(buf):  # the gathered rows, split back into heads
        g = grab(buf)
        return g.reshape(*g.shape[:-1], kv_heads, -1)

    if isinstance(pool, Int8Pages):
        k = (heads(pool.k).astype(jnp.float32)
             * grab(pool.k_scale)[..., None]).astype(cfg.dtype)
        v = (heads(pool.v).astype(jnp.float32)
             * grab(pool.v_scale)[..., None]).astype(cfg.dtype)
        return KVCache(k, v)
    return KVCache(heads(pool.k).astype(cfg.dtype),
                   heads(pool.v).astype(cfg.dtype))


def scatter_pages(pool, view: KVCache, table: jnp.ndarray,
                  pos: jnp.ndarray, cur: int, active: jnp.ndarray):
    """Write the view pages a forward just touched back into the pool.

    ``view`` is the updated dense view (the forward wrote ``cur`` new
    tokens at per-slot positions ``[pos, pos+cur)``); only the pages
    covering those positions are written back — everything else in the
    pool is untouched, which is what makes shared (copy-on-write)
    pages safe to map into many tables: a slot only ever writes pages
    it exclusively owns (the scheduler's allocation invariant).
    Inactive slots' writes — and the statically-unrolled spare page of
    a window that did not actually cross a page boundary — are routed
    to the scratch page (last pool page), never to a real block.
    ``cur`` is static (it bounds the unroll: a ``cur``-token window
    touches at most ``(cur + T - 2) // T + 1`` pages)."""
    T = pool.k.shape[2]
    n_pages = table.shape[1]
    scratch = pool.k.shape[1] - 1
    first = pos // T
    last = (pos + cur - 1) // T

    def cut(buf, starts):  # (L, S, M*T, kv, dh) -> (L, S, T, kv, dh)
        return jax.vmap(
            lambda b, p: lax.dynamic_slice_in_dim(b, p, T, axis=1),
            in_axes=(1, 0), out_axes=1)(buf, starts)

    for j in range((cur + T - 2) // T + 1):
        pidx = first + j  # (S,)
        safe = jnp.clip(pidx, 0, n_pages - 1)
        page = jnp.take_along_axis(table, safe[:, None], axis=1)[:, 0]
        valid = active & (pidx <= last) & (pidx < n_pages) & (page >= 0)
        page = jnp.where(valid, page, scratch)
        ck = cut(view.k, safe * T)
        cv = cut(view.v, safe * T)
        if isinstance(pool, Int8Pages):
            qk, sk = _quantize_kv(ck)
            qv, sv = _quantize_kv(cv)
            pool = Int8Pages(pool.k.at[:, page].set(_token_rows(qk)),
                             pool.v.at[:, page].set(_token_rows(qv)),
                             pool.k_scale.at[:, page].set(sk),
                             pool.v_scale.at[:, page].set(sv))
        else:
            pool = KVPages(
                pool.k.at[:, page].set(_token_rows(ck).astype(pool.k.dtype)),
                pool.v.at[:, page].set(_token_rows(cv).astype(pool.v.dtype)))
    return pool


def write_token_pages(pages, k_new: jnp.ndarray, v_new: jnp.ndarray,
                      table: jnp.ndarray, pos: jnp.ndarray,
                      active: jnp.ndarray, layer: int | None = None):
    """Commit a ``cur``-token window's K/V directly into the pages
    holding positions ``[pos, pos+cur)`` — the single-page committed
    write that replaces :func:`scatter_pages`'s page-level unroll on
    the gather-free paths: each (slot, window position) writes exactly
    ONE token row of exactly the page containing that position
    (``dynamic_update_slice``-style ``.at[page, off].set``), so a
    decode step's write traffic is one token's worth of KV, not a
    whole-page (let alone whole-view) rewrite.

    ``pages`` is one LAYER's page buffers — ``(k, v)`` fp, each
    ``(pages, page_tokens, row)``: a token is one row, and ``k_new`` /
    ``v_new`` ``(b, cur, kv_heads, dh)`` are written as rows of
    ``kv_heads * dh`` values (:class:`KVPages`; the two buffers may
    differ in the row's width: :class:`LatentPages`' ``(c, r)`` with
    ``k_new`` ``(b, cur, c)`` the latents and ``v_new`` the rotary keys
    commit through this same function) or
    ``(k, v, k_scale, v_scale)`` int8 (new vectors quantize per head with
    the same symmetric-absmax math as :func:`scatter_pages`; since that
    quantization is idempotent on already-quantized vectors, the pool
    bytes match the old whole-page rewrite exactly).  Writes of
    inactive slots, and of positions past the table (never expected —
    the engine preallocates), route to the trailing scratch page.

    With ``layer`` (the kernel build's whole-pool mode) ``pages`` are
    the FULL stacked pool buffers ``(layers, pages, T, ...)`` and every
    write scatters at ``[layer, page, ...]`` directly — same values at
    the same pool coordinates as the per-layer-slice form, but no layer
    slice has to stay live past its block and the end-of-forward
    restack disappears, which is where the kernel programs' committed
    peak-live drop below their einsum twins comes from."""
    ix = () if layer is None else (layer,)
    T = pages[0].shape[1 + len(ix)]
    n_pages = table.shape[1]
    scratch = pages[0].shape[len(ix)] - 1
    b, cur = k_new.shape[0], k_new.shape[1]
    if len(pages) == 4:
        qk, sk = _quantize_kv(k_new)
        qv, sv = _quantize_kv(v_new)
        new = (_token_rows(qk), _token_rows(qv), sk, sv)
    else:  # (b, cur, kv_heads, dh) or (b, cur, row) -> stored rows
        new = (k_new.reshape(b, cur, -1), v_new.reshape(b, cur, -1))
    pos = jnp.asarray(pos)
    scalar_pos = not pos.ndim
    if scalar_pos:
        pos = jnp.broadcast_to(pos, (b,))
    if scalar_pos and cur == T:
        # The page-aligned prefill chunk (the ONLY scalar-pos caller;
        # chunk starts are page multiples by the engine contract, which
        # the alignment term below enforces by routing any violation to
        # scratch): the window IS one whole page, so commit it with ONE
        # page-row write per buffer instead of T chained single-token
        # scatters — trace size and the dependent-write chain stay O(1)
        # in chunk width (a production-sized chunk x deep model would
        # otherwise mint tens of thousands of scatter eqns).
        pidx = pos // T
        safe = jnp.clip(pidx, 0, n_pages - 1)
        page = jnp.take_along_axis(table, safe[:, None], axis=1)[:, 0]
        valid = (active & (pidx < n_pages) & (page >= 0)
                 & (pos % T == 0))
        page = jnp.where(valid, page, scratch)
        return tuple(buf.at[(*ix, page)].set(val.astype(buf.dtype))
                     for buf, val in zip(pages, new))
    for j in range(cur):
        p = pos + j
        pidx = p // T
        off = p % T
        safe = jnp.clip(pidx, 0, n_pages - 1)
        page = jnp.take_along_axis(table, safe[:, None], axis=1)[:, 0]
        valid = active & (pidx < n_pages) & (page >= 0)
        page = jnp.where(valid, page, scratch)
        # (every slice before the first write: the order of the pinned traces)
        rows = [val[:, j] for val in new]
        pages = tuple(buf.at[(*ix, page, off)].set(row.astype(buf.dtype))
                      for buf, row in zip(pages, rows))
    return pages


def _layer_pages(pool, i: int):
    """One layer's page-buffer slice of the pool: ``(k, v)`` or the
    int8 quadruple — the unit :class:`_PagedKV` reads/writes, so only
    one layer's tiles are ever transient at a time."""
    return tuple(buf[i] for buf in pool)


def _stack_pages(pool, layers: list):
    """Reassemble the pool pytree from per-layer page buffers (the
    paged mirror of ``_forward_cached``'s ``jnp.stack`` over layer
    caches; the donated pool aliases in place under XLA)."""
    return type(pool)(*(jnp.stack(bufs) for bufs in zip(*layers)))


class _PagedKV:
    """One layer's gather-free paged KV store, threaded through the
    family block twins (``_block_decode(..., paged=store)`` /
    ``llama.block_decode``): ``write`` lands the window's new K/V as
    single-token page writes (:func:`write_token_pages`), ``attend``
    reads K/V THROUGH the block table inside the attention contraction
    (``tpudp.ops.paged_attention`` — bit-exact blockwise einsums by
    default, the Pallas decode kernel on the opt-in path).  The slot's
    dense logical view is never materialized.  Trace-time mutable:
    ``write`` rebinds ``pages``; the paged forward collects them per
    layer."""

    __slots__ = ("cfg", "pages", "table", "pos", "active", "grouped",
                 "impl", "layer", "window")

    def __init__(self, cfg, pages, table, pos, active, *, grouped, impl,
                 layer=None, window=None):
        self.cfg = cfg
        self.pages = pages
        self.table = table
        self.pos = pos
        self.active = active
        self.grouped = grouped
        self.impl = impl
        # Whole-pool mode (kernel builds): ``pages`` are the FULL
        # stacked pool buffers and ``layer`` picks the stratum — write
        # scatters at [layer, ...] and attend indexes the layer inside
        # the kernel's BlockSpec, so a per-layer slice never exists as
        # an XLA value (the kernel programs' peak-live edge over their
        # einsum twins).
        self.layer = layer
        # A sliding-window layer's span (``laguna``): queries also mask
        # keys ``window`` or more positions behind them.  None: causal.
        self.window = window

    def write(self, k: jnp.ndarray, v: jnp.ndarray) -> None:
        self.pages = write_token_pages(self.pages, k, v, self.table,
                                       self.pos, self.active,
                                       layer=self.layer)

    def attend(self, q: jnp.ndarray) -> jnp.ndarray:
        from tpudp.ops.paged_attention import paged_attention

        return paged_attention(q, self.pages, self.table, self.pos,
                               dtype=self.cfg.dtype, grouped=self.grouped,
                               impl=self.impl, layer=self.layer,
                               window=self.window)


def _row_major(x: jnp.ndarray) -> jnp.ndarray:
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


class _LatentKV:
    """The latent family's page store (``pangu.block_paged``): like
    :class:`_PagedKV` in whole-pool mode, over :class:`LatentPages`'
    ``(c, r)`` buffers.  ``layer`` is set by the forward before each
    block; ``write`` commits the window's latents as single-page token
    writes (:func:`write_token_pages`), ``attend`` runs the absorbed
    attention through the block table, as XLA contractions
    (``impl='einsum'``) or as the ``latent_attn`` Mosaic call
    (``'kernel'``)."""

    __slots__ = ("cfg", "pages", "table", "pos", "active", "impl", "layer")

    def __init__(self, cfg, pages, table, pos, active, impl):
        self.cfg, self.pages, self.table = cfg, pages, table
        self.pos, self.active, self.impl, self.layer = pos, active, impl, None

    def write(self, c_kv: jnp.ndarray, k_rope: jnp.ndarray) -> None:
        # a whole page-aligned chunk (b == 1) commits as ONE page write,
        # which write_token_pages takes from a scalar position
        pos = self.pos[0] if c_kv.shape[0] == 1 else self.pos
        # Row-major rows, said out loud.  The 576-wide projection they
        # come from is no multiple of the 128 lanes, XLA lays its output
        # out token-minor, a page write takes its update's layout to its
        # operand, and the WHOLE pool was transposed into that layout and
        # back: 3.4 GB each way a prefill chunk (AOT compile for the v5e).
        c_kv, k_rope = _row_major(c_kv), _row_major(k_rope)
        self.pages = write_token_pages(self.pages, c_kv, k_rope, self.table,
                                       pos, self.active, layer=self.layer)

    def attend(self, q_lat: jnp.ndarray, q_rope: jnp.ndarray) -> jnp.ndarray:
        from tpudp.ops.paged_attention import latent_paged_attention

        return latent_paged_attention(
            q_lat, q_rope, self.pages, self.table, self.pos,
            scale=self.cfg.score_scale, dtype=self.cfg.dtype,
            layer=self.layer, impl=self.impl)


class _TreePagedKV:
    """One layer's READ-ONLY paged store for the tree-verify forward:
    ``attend`` runs the tree kernel over the slot's cache pages (strict
    ``< pos0`` visibility, through the block table) jointly with the
    in-flight window K/V under the ancestor-or-self mask — the window
    never touches the pages (rejected branches must leave zero pool
    bytes), so unlike :class:`_PagedKV` there is no ``write``."""

    __slots__ = ("cfg", "pages", "table", "pos0", "anc")

    def __init__(self, cfg, pages, table, pos0, anc):
        self.cfg = cfg
        self.pages = pages
        self.table = table
        self.pos0 = pos0
        self.anc = anc

    def attend(self, q: jnp.ndarray, k: jnp.ndarray,
               v: jnp.ndarray) -> jnp.ndarray:
        from tpudp.ops.paged_attention import tree_paged_attention

        return tree_paged_attention(q, self.pages, self.table, self.pos0,
                                    k, v, self.anc, dtype=self.cfg.dtype)


def _forward_tree_paged(cfg, params: dict, tokens: jnp.ndarray, pool,
                        table: jnp.ndarray, pos0, depths: tuple,
                        anc: tuple):
    """Kernelized paged twin of :func:`_forward_tree`: node queries
    attend the committed cache THROUGH the block table (the tree-verify
    kernel — no dense view, no gather) jointly with the in-window
    ancestor set.  Returns ``(logits, wk, wv)`` exactly like the dense
    tree forward; the pool is read-only here (the caller commits the
    accepted path via ``write_token_pages`` afterwards).  fp pools only
    — the engine keeps int8 pools on the einsum/gather fallback and
    records the dispatch."""
    from tpudp.models import llama as _llama

    pos0 = jnp.asarray(pos0)
    positions = pos0[:, None] + jnp.asarray(depths, jnp.int32)[None, :]
    is_llama = isinstance(cfg, _llama.LlamaConfig)
    if is_llama:
        x = _llama.embed_tokens(cfg, params, tokens)
    else:
        x = embed_tokens(cfg, params, tokens, positions)
    wk, wv = [], []
    for i in range(cfg.num_layers):
        store = _TreePagedKV(cfg, _layer_pages(pool, i), table, pos0, anc)
        if is_llama:
            x, k_i, v_i = _llama.block_tree(
                cfg, params[f"h_{i}"], x, None, None, pos0, positions,
                anc, paged=store)
        else:
            x, k_i, v_i = _block_tree(cfg, params[f"h_{i}"], x, None,
                                      None, pos0, anc, paged=store)
        wk.append(k_i)
        wv.append(v_i)
    head = _llama.lm_head if is_llama else lm_head
    return head(cfg, params, x), jnp.stack(wk), jnp.stack(wv)


def _forward_paged(cfg, params: dict, tokens: jnp.ndarray, pool,
                   table: jnp.ndarray, pos: jnp.ndarray,
                   active: jnp.ndarray, impl: str | None = None, *,
                   last=None, routed: list | None = None):
    """Page-table-indirected twin of :func:`_forward_cached` for the
    serve engine's paged arena.  Returns ``(logits, pool)``.

    Four families dispatch here: GPT-2, LLaMA, and the two expert
    families, which take ``last`` and ``routed`` (see their own
    ``forward_paged``): latent attention (``tpudp.models.pangu``:
    absorbed MLA over :class:`LatentPages`) and window and full attention
    layers mixed (``tpudp.models.laguna``: :class:`WindowedPages`,
    ``table`` one array or the pair of the two pools' tables).  Both take
    ``impl`` einsum or kernel as below (no ``'gather'``: neither has a
    dense view), unset the kernels on an accelerator and einsum on the
    CPU.  For GPT-2 and LLaMA an unset ``impl`` is ``'einsum'``.

    ``impl='einsum'`` (the engine default) and ``'kernel'`` are
    GATHER-FREE: each layer's block twin writes the window's new K/V
    straight into the pages containing ``[pos, pos+cur)``
    (:func:`write_token_pages` — one token row per position, never a
    page unroll) and reads K/V through the table inside the attention
    contraction (:class:`_PagedKV` → ``tpudp.ops.paged_attention``).
    The einsum path's fp outputs are BITWISE identical to the dense
    math on the gathered view (the paged-parity contract), while the
    full ``(layers, slots, max_len, ...)`` view — and its whole-pool
    scatter — no longer exist, which the committed budget ledger's
    peak-live drop proves.  ``'kernel'`` additionally routes
    single-token decode through the Pallas paged-decode kernel
    (tolerance-bounded like flash).

    ``impl='gather'`` is PR 13's original path — gather the dense view,
    run the exact dense forward, scatter written pages back — kept as
    the bench comparison baseline and the kernel tests' oracle."""
    if page_layout(cfg) == "latent":
        from tpudp.models import pangu as _pangu

        return _pangu.forward_paged(cfg, params, tokens, pool, table, pos,
                                    active, impl, last=last, routed=routed)
    if page_layout(cfg) == "windowed":
        from tpudp.models import laguna as _laguna

        return _laguna.forward_paged(cfg, params, tokens, pool, table, pos,
                                     active, impl, last=last, routed=routed)
    impl = impl or "einsum"
    if impl == "gather":
        view = gather_pages(cfg, pool, table)
        logits, view = _forward_cached(cfg, params, tokens, view, pos)
        spos = jnp.asarray(pos)
        if not spos.ndim:
            spos = jnp.broadcast_to(spos, (tokens.shape[0],))
        return logits, scatter_pages(pool, view, table, spos,
                                     tokens.shape[1], active)
    from tpudp.models import llama as _llama

    pos = jnp.asarray(pos)
    is_llama = isinstance(cfg, _llama.LlamaConfig)
    if is_llama:
        x = _llama.embed_tokens(cfg, params, tokens)
    else:
        offsets = jnp.arange(tokens.shape[1])
        positions = (pos[:, None] + offsets) if pos.ndim else pos + offsets
        x = embed_tokens(cfg, params, tokens, positions)
    # Kernel builds run whole-pool mode: every layer's store shares the
    # full stacked buffers (writes scatter at [layer, ...]; attend
    # slices its stratum lazily), so no per-layer page slice stays live
    # past its block and the end-of-forward restack disappears — the
    # committed peak-live drop of every *_kernel program below its
    # einsum twin.  The einsum path keeps the slice-and-restack form
    # that its pinned traces were committed against.
    whole = impl == "kernel"
    bufs = tuple(pool) if whole else None
    layers = []
    for i in range(cfg.num_layers):
        store = _PagedKV(cfg, bufs if whole else _layer_pages(pool, i),
                         table, pos, active, grouped=is_llama, impl=impl,
                         layer=i if whole else None)
        if is_llama:
            x, _, _ = _llama.block_decode(cfg, params[f"h_{i}"], x, None,
                                          None, pos, paged=store)
        else:
            x, _, _ = _block_decode(cfg, params[f"h_{i}"], x, None, None,
                                    pos, paged=store)
        if whole:
            bufs = store.pages
        else:
            layers.append(store.pages)
    head = _llama.lm_head if is_llama else lm_head
    new_pool = (type(pool)(*bufs) if whole else
                _stack_pages(pool, layers))
    return head(cfg, params, x), new_pool


def _layer_norm(p: dict, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Exactly the training model's LayerNorm (flax apply on the raw
    subtree, same epsilon), so decode can never drift numerically from
    Block's."""
    import flax.linen as nn

    return nn.LayerNorm(dtype=jnp.float32, epsilon=eps).apply(
        {"params": p}, x)


def _dense(p: dict, x: jnp.ndarray, dtype) -> jnp.ndarray:
    return x.astype(dtype) @ p["kernel"].astype(dtype) + p["bias"].astype(dtype)


def update_cache_rows(cache: jnp.ndarray, new: jnp.ndarray,
                      pos: jnp.ndarray) -> jnp.ndarray:
    """Write ``new`` ``(b, cur, heads, dh)`` into ``cache``
    ``(b, max_len, heads, dh)`` starting at PER-ROW positions ``pos``
    ``(b,)`` — the serve engine's slot arena, where every slot sits at a
    different depth.  A vmapped ``dynamic_update_slice`` so shapes stay
    static regardless of the position values (no recompiles across
    admission/retirement churn)."""
    return jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice(c, n, (p, 0, 0)))(
            cache, new, pos)


def _block_decode(cfg: GPT2Config, p: dict, x: jnp.ndarray,
                  k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                  pos: jnp.ndarray, paged=None):
    """One pre-LN block on ``(batch, cur, d)`` new tokens at absolute
    positions ``pos .. pos+cur-1``, reading/writing the KV cache.

    With ``paged`` (a :class:`_PagedKV` store — the serve engine's
    gather-free paged mode) the KV write/read goes through the block
    table instead of the dense cache: single-token page writes, then
    attention THROUGH the table — bit-identical outputs to the dense
    einsums below on the same stored values (the op's contract), with
    everything outside the KV indirection shared line-for-line so the
    two paths can never drift.

    ``pos`` is either a scalar shared by the whole batch (generate /
    beam_search, where every row is at the same depth) or a ``(batch,)``
    vector of per-row positions (the serve engine's slot arena).  The
    scalar path compiles to exactly the program it always did; the vector
    path scatters each row's KV at its own depth and masks per row, and
    runs attention PER WINDOW POSITION (a vmap over ``cur``): XLA lowers
    a width-1 and a width-W contraction to different gemv/gemm reduction
    blockings, so the batched einsum is bitwise-stable only across equal
    widths — the vmapped form makes a speculative k+1-token verify
    window bit-identical to feeding one token at a time (the engine's
    exact-greedy-parity contract), while the weight matmuls (the decode
    bottleneck) stay batched over the window.

    Mirrors tpudp.models.gpt2.Block exactly (the parity test referee);
    attention spans the cache up to ``pos`` plus a causal mask within the
    new tokens."""
    b, cur, d = x.shape
    h = cfg.num_heads
    dh = d // h

    hN = _layer_norm(p["ln_1"], x, cfg.ln_eps)
    qkv = _dense(p["attn"]["qkv"], hN, cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, cur, h, dh)
    k = k.reshape(b, cur, h, dh)
    v = v.reshape(b, cur, h, dh)
    pos = jnp.asarray(pos)
    if paged is not None:
        # Gather-free paged KV: write-before-attend order preserved
        # (the dense branch's cache update precedes its read too).
        paged.write(k, v)
        out = paged.attend(q)
    else:
        if pos.ndim:  # per-row slot positions (serve engine)
            k_cache = update_cache_rows(k_cache, k, pos)
            v_cache = update_cache_rows(v_cache, v, pos)
        else:
            k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
            v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))

        # Same op/dtype sequence as ops.attention.multihead_attention's
        # dense path (einsum in cfg.dtype, fp32 softmax) — in bf16,
        # rounding QK^T differently would break exact argmax parity
        # with the training model.
        max_len = k_cache.shape[1]
        scale = dh ** -0.5
        if pos.ndim:
            # Key j visible to new-token query i iff j <= pos + i, per
            # row.  One attention per window position (see docstring):
            # each slice is exactly the 1-token step's contraction, so
            # a k+1 verify window is bit-identical to k+1 single-token
            # decodes.
            q_pos = pos[:, None] + jnp.arange(cur)  # (b, cur)

            def _attend(qj, pj):  # qj (b, h, dh), pj (b,)
                lg = jnp.einsum("bhd,bkhd->bhk", qj, k_cache) * scale
                vis = jnp.arange(max_len)[None, None, :] \
                    <= pj[:, None, None]
                lg = jnp.where(vis, lg, jnp.finfo(lg.dtype).min)
                pr = jax.nn.softmax(lg.astype(jnp.float32),
                                    axis=-1).astype(cfg.dtype)
                return jnp.einsum("bhk,bkhd->bhd", pr, v_cache)

            out = jax.vmap(_attend, in_axes=(1, 1), out_axes=1)(q, q_pos)
        else:
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) * scale
            q_pos = pos + jnp.arange(cur)[:, None]
            visible = jnp.arange(max_len)[None, :] <= q_pos
            logits = jnp.where(visible[None, None], logits,
                               jnp.finfo(logits.dtype).min)
            probs = jax.nn.softmax(logits.astype(jnp.float32),
                                   axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_cache)
    x = x + _dense(p["attn"]["proj"], out.reshape(b, cur, d), cfg.dtype)

    hN = _layer_norm(p["ln_2"], x, cfg.ln_eps)
    m = jax.nn.gelu(_dense(p["mlp_fc"], hN, cfg.dtype))
    x = x + _dense(p["mlp_proj"], m, cfg.dtype)
    return x, k_cache, v_cache


def _forward_cached(cfg, params: dict, tokens: jnp.ndarray,
                    cache: KVCache, pos) -> tuple[jnp.ndarray, KVCache]:
    """Token ids ``(batch, cur)`` at absolute position ``pos`` ->
    ``(batch, cur, vocab)`` fp32 logits + updated cache.

    ``pos`` is a scalar (whole batch at the same depth — generate /
    beam_search) or a ``(batch,)`` vector of per-row depths (the serve
    engine's slot-masked decode step; see tpudp.serve).

    Dispatches on the config family: GPT-2 (learned positions in the
    embedding, LayerNorm/GELU blocks, tied head) or LLaMA (RoPE inside
    the blocks, RMSNorm/SwiGLU, GQA-width cache, untied head) — both via
    raw-param twins kept in lockstep with their training ``__call__`` and
    pinned by the greedy-parity tests."""
    from tpudp.models import llama as _llama

    pos = jnp.asarray(pos)
    if isinstance(cfg, _llama.LlamaConfig):
        x = _llama.embed_tokens(cfg, params, tokens)
        block = lambda p, x, k, v: _llama.block_decode(cfg, p, x, k, v, pos)
        head = _llama.lm_head
    else:
        offsets = jnp.arange(tokens.shape[1])
        positions = (pos[:, None] + offsets) if pos.ndim else pos + offsets
        x = embed_tokens(cfg, params, tokens, positions)
        block = lambda p, x, k, v: _block_decode(cfg, p, x, k, v, pos)
        head = lm_head
    new_k, new_v = [], []
    for i in range(cfg.num_layers):
        x, k_i, v_i = block(params[f"h_{i}"], x, cache.k[i], cache.v[i])
        new_k.append(k_i)
        new_v.append(v_i)
    logits = head(cfg, params, x)
    return logits, KVCache(jnp.stack(new_k), jnp.stack(new_v))


def _block_tree(cfg: GPT2Config, p: dict, x: jnp.ndarray,
                k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                pos0: jnp.ndarray, anc: tuple, paged=None):
    """One pre-LN block over a speculative token TREE of ``T+1`` nodes
    (node 0 = the row's last committed token; see
    ``tpudp.serve.speculate.TreeShape``) — the NO-WRITE twin of
    :func:`_block_decode`'s vector-pos path.

    Sibling nodes at one depth share a logical cache position, so the
    write-then-attend scheme cannot hold them; instead the window K/V
    stay out of the cache and each node attends the committed cache
    (positions ``< pos0``, uniform — node 0's own KV is not yet
    written, exactly like the verify window's first slot) JOINTLY with
    its in-window ancestors-or-self (``anc``, the shape's static
    ``(T+1, T+1)`` matrix) under one softmax.  The caller commits the
    ACCEPTED path's K/V afterwards — rejected branches never touch the
    cache.  Same op/dtype sequence as :func:`_block_decode` (einsum in
    ``cfg.dtype``, fp32 softmax), vmapped per node; the joint reduction
    spans ``max_len + T + 1`` keys, so outputs are tolerance-bounded —
    not bitwise — against the sequential write-then-attend program
    (the tree engine's documented opt-in contract)."""
    b, T1, d = x.shape
    h = cfg.num_heads
    dh = d // h

    hN = _layer_norm(p["ln_1"], x, cfg.ln_eps)
    qkv = _dense(p["attn"]["qkv"], hN, cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, T1, h, dh)
    k = k.reshape(b, T1, h, dh)
    v = v.reshape(b, T1, h, dh)
    if paged is not None:
        # Kernelized paged tree read (_TreePagedKV → tree kernel): the
        # window K/V ride as kernel operands, never entering the pages.
        out = paged.attend(q, k, v)
    else:
        max_len = k_cache.shape[1]
        scale = dh ** -0.5
        kk = jnp.concatenate([k_cache, k], axis=1)
        vv = jnp.concatenate([v_cache, v], axis=1)
        cache_vis = jnp.arange(max_len)[None, :] < pos0[:, None]  # (b, M)
        anc_m = jnp.asarray(anc, bool)

        def _attend(qj, ancj):  # qj (b, h, dh), ancj (T1,)
            lg = jnp.einsum("bhd,bkhd->bhk", qj, kk) * scale
            vis = jnp.concatenate(
                [cache_vis, jnp.broadcast_to(ancj[None], (b, T1))], axis=1)
            lg = jnp.where(vis[:, None, :], lg, jnp.finfo(lg.dtype).min)
            pr = jax.nn.softmax(lg.astype(jnp.float32),
                                axis=-1).astype(cfg.dtype)
            return jnp.einsum("bhk,bkhd->bhd", pr, vv)

        out = jax.vmap(_attend, in_axes=(1, 0), out_axes=1)(q, anc_m)
    x = x + _dense(p["attn"]["proj"], out.reshape(b, T1, d), cfg.dtype)

    hN = _layer_norm(p["ln_2"], x, cfg.ln_eps)
    m = jax.nn.gelu(_dense(p["mlp_fc"], hN, cfg.dtype))
    x = x + _dense(p["mlp_proj"], m, cfg.dtype)
    return x, k, v


def _forward_tree(cfg, params: dict, tokens: jnp.ndarray, view: KVCache,
                  pos0, depths: tuple, anc: tuple):
    """Tree-verify forward: node tokens ``(batch, T+1)`` (node 0 = each
    row's last committed token) against a READ-ONLY dense cache view at
    per-row root positions ``pos0`` -> ``(logits (batch, T+1, vocab),
    wk, wv)`` where ``wk``/``wv`` ``(layers, batch, T+1, kv_heads, dh)``
    are the window K/V the caller commits for accepted nodes only.

    ``depths``/``anc`` are the static shape tables
    (``TreeShape.depths``/``.ancestors``); node positions decouple from
    storage — GPT-2's learned embeddings and LLaMA's RoPE both rotate
    at ``pos0 + depth`` while the window K/V never enter the cache
    (:func:`_block_tree` / ``llama.block_tree``).  The cache view is
    NOT returned: this forward writes nothing, which is what makes
    rejected tree branches literally free."""
    from tpudp.models import llama as _llama

    pos0 = jnp.asarray(pos0)
    positions = pos0[:, None] + jnp.asarray(depths, jnp.int32)[None, :]
    is_llama = isinstance(cfg, _llama.LlamaConfig)
    if is_llama:
        x = _llama.embed_tokens(cfg, params, tokens)
    else:
        x = embed_tokens(cfg, params, tokens, positions)
    wk, wv = [], []
    for i in range(cfg.num_layers):
        if is_llama:
            x, k_i, v_i = _llama.block_tree(
                cfg, params[f"h_{i}"], x, view.k[i], view.v[i], pos0,
                positions, anc)
        else:
            x, k_i, v_i = _block_tree(cfg, params[f"h_{i}"], x,
                                      view.k[i], view.v[i], pos0, anc)
        wk.append(k_i)
        wv.append(v_i)
    head = _llama.lm_head if is_llama else lm_head
    return head(cfg, params, x), jnp.stack(wk), jnp.stack(wv)


def validate_decode_config(cfg, fn_name: str) -> None:
    """Reject configs the raw-param decode twins cannot serve faithfully.

    ``attn_impl='flash'`` is rejected alongside 'ring' (round-5 advisor):
    decode always runs the dense-math raw-param twins, and the Pallas
    online-softmax rounds bf16 differently from the XLA dense chain, so a
    flash-trained config would silently lose the documented EXACT greedy
    train/decode parity.  The weights themselves are fine — rebuild the
    config with ``attn_impl='dense'`` to decode them.  Shared by the
    generate()/beam_search() entry points and tpudp.serve.Engine."""
    mlp_impl = getattr(cfg, "mlp_impl", "dense")  # LlamaConfig: dense only
    # (the two expert families' expert layers ARE served: pangu.block_paged,
    # laguna.block_paged; their configs' attn_impl names the MODULE's
    # attention and 'dense' is its one value)
    if cfg.attn_impl != "dense" or mlp_impl != "dense":
        raise ValueError(
            f"{fn_name} supports configs with attn_impl='dense' and a "
            f"dense MLP or a served expert layer (the dropless one of the "
            f"pangu and laguna families; GPT-2's mlp_impl='moe' has no "
            f"decode twin): decode runs the dense-math twins, and a "
            f"flash/ring-trained config would decode with different "
            f"rounding than it trained with — rebuild the config with "
            f"attn_impl='dense' to decode its weights; got "
            f"attn_impl={cfg.attn_impl!r} mlp_impl={mlp_impl!r}")


def _validate_decode(cfg, prompt, max_new_tokens: int, fn_name: str) -> int:
    """Shared decode-entry checks; returns the total sequence length."""
    if page_layout(cfg) != "heads":
        raise ValueError(
            f"{fn_name} has no dense-cache twin for a "
            f"{page_layout(cfg)}-pages config ({type(cfg).__name__}): its "
            f"cache exists only as pages; serve it through "
            f"tpudp.serve.Engine(kv_pages=N)")
    validate_decode_config(cfg, fn_name)
    prompt_len = prompt.shape[1]
    total = prompt_len + max_new_tokens
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds max_seq_len ({cfg.max_seq_len})")
    return total


def generate(
    model,
    params: dict,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    key: jax.Array | None = None,
) -> jnp.ndarray:
    """Generate ``(batch, prompt_len + max_new_tokens)`` token ids.

    ``model`` is a tpudp GPT2 or Llama (dense attention/MLP); ``prompt`` is
    ``(batch, prompt_len)`` int32.  ``temperature=0`` is greedy argmax;
    otherwise softmax sampling at that temperature using ``key``, optionally
    truncated to the ``top_k`` highest-probability tokens and/or the
    smallest nucleus whose cumulative probability reaches ``top_p``.
    The whole prefill+decode loop jit-compiles as one program; total
    length is capped at ``model.config.max_seq_len`` (the position table).
    """
    cfg = model.config
    total = _validate_decode(cfg, prompt, max_new_tokens, "generate()")
    if temperature > 0 and key is None:
        raise ValueError("temperature sampling needs a PRNG key")
    if (top_k is not None or top_p is not None) and temperature == 0.0:
        raise ValueError("top_k/top_p require temperature > 0 (greedy "
                         "decoding ignores them)")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if key is None:
        key = jax.random.PRNGKey(0)

    new_tokens = _generate_jit(cfg, params, prompt, key,
                               max_new_tokens=max_new_tokens,
                               temperature=float(temperature),
                               top_k=top_k, top_p=top_p, total=total)
    return jnp.concatenate([prompt, new_tokens], axis=1)


def _truncate_logits(logits, top_k, top_p):
    """Mask logits outside the top-k set / the top-p nucleus to -inf.
    The nucleus always includes the highest-probability token even when
    ``top_p`` is smaller than its probability.

    Thin static wrapper over ``tpudp.ops.sampling.truncate_logits`` —
    the ONE truncation implementation, shared with the serve engine's
    per-row sampling and the speculative verify op, so the static and
    traced paths cannot drift (a parity test pins them bitwise).
    ``None`` statics broadcast to the op's disabled sentinels (k=0,
    p=1); fully disabled truncation skips the call (and its vocab
    sorts) entirely, and a top-k-only static keeps ``lax.top_k``'s
    partial selection instead of paying the traced op's full-vocab
    sorts — the mask rule (``>= kth``, ties kept) is the shared op's,
    and the parity test asserts the shortcut bitwise-equal to it.
    """
    if top_k is None and top_p is None:
        return logits
    if top_p is None:
        if top_k >= logits.shape[-1]:
            return logits
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        return jnp.where(logits >= kth, logits, -jnp.inf)
    from tpudp.ops.sampling import truncate_logits

    lead = logits.shape[:-1]
    k_arr = jnp.full(lead, 0 if top_k is None else top_k, jnp.int32)
    p_arr = jnp.full(lead, 1.0 if top_p is None else top_p, jnp.float32)
    return truncate_logits(logits, k_arr, p_arr)


# Module-level jit keyed on (cfg, shapes, statics): repeated generate()
# calls with the same geometry reuse the compiled prefill+decode program
# instead of recompiling per call.
@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "temperature",
                                    "top_k", "top_p", "total"))
def _generate_jit(cfg, params, prompt, key, *, max_new_tokens, temperature,
                  top_k, top_p, total):
    b, prompt_len = prompt.shape
    cache = KVCache.zeros(cfg, b, total)
    logits, cache = _forward_cached(cfg, params, prompt, cache, 0)
    last = logits[:, -1]

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        logits = _truncate_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def step(carry, i):
        cache, last_logits, key = carry
        key, sub = jax.random.split(key)
        tok = sample(last_logits, sub)
        logits, cache = _forward_cached(
            cfg, params, tok[:, None], cache, prompt_len + i)
        return (cache, logits[:, -1], key), tok

    _, toks = lax.scan(step, (cache, last, key), jnp.arange(max_new_tokens))
    return toks.swapaxes(0, 1)  # (batch, max_new_tokens)


def beam_search(
    model,
    params: dict,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    beam_width: int = 4,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decoding over the same KV-cached decode path.

    Returns ``(sequences, scores)``: the highest-scoring beam per batch
    element as ``(batch, prompt_len + max_new_tokens)`` token ids and its
    total log-probability ``(batch,)``.  The whole search (prefill +
    ``max_new_tokens`` expand/select steps, including the per-step KV-cache
    reorder by parent beam) compiles as one program.  No EOS handling —
    beams all run to ``max_new_tokens`` (the framework's corpora are
    untokenized streams with no terminator symbol).
    """
    cfg = model.config
    total = _validate_decode(cfg, prompt, max_new_tokens, "beam_search()")
    if beam_width < 1:
        raise ValueError(f"beam_width must be >= 1, got {beam_width}")
    return _beam_jit(cfg, params, prompt,
                     max_new_tokens=max_new_tokens, beam_width=beam_width,
                     total=total)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "beam_width",
                                    "total"))
def _beam_jit(cfg, params, prompt, *, max_new_tokens, beam_width, total):
    b, prompt_len = prompt.shape
    w = beam_width
    bw = b * w

    # Prefill ONCE at batch b (all beams share the prompt), then fan the
    # cache and last-token logits out to beam-major (bw, ...) — beam_width
    # byte-identical prompt forwards would cost w times the prefill FLOPs
    # and activation memory for nothing.
    cache = KVCache.zeros(cfg, b, total)
    logits, cache = _forward_cached(cfg, params, prompt, cache, 0)
    cache = KVCache(jnp.repeat(cache.k, w, axis=1),
                    jnp.repeat(cache.v, w, axis=1))
    last = jnp.repeat(logits[:, -1], w, axis=0)  # (bw, vocab)
    # Only beam 0 is live initially so the first step picks w DISTINCT
    # continuations instead of w copies of the argmax.
    scores = jnp.tile(jnp.asarray([0.0] + [-jnp.inf] * (w - 1)), (b, 1))
    new_tokens = jnp.zeros((b, w, max_new_tokens), jnp.int32)
    batch_offset = (jnp.arange(b) * w)[:, None]  # (b, 1)

    def step(carry, i):
        cache, last, scores, new_tokens = carry
        v = last.shape[-1]
        logprobs = jax.nn.log_softmax(last.astype(jnp.float32), axis=-1)
        cand = scores[:, :, None] + logprobs.reshape(b, w, v)
        top_scores, top_idx = lax.top_k(cand.reshape(b, w * v), w)
        parent = top_idx // v          # (b, w) parent beam per winner
        tok = (top_idx % v).astype(jnp.int32)
        gp = (batch_offset + parent).reshape(-1)  # global parent rows (bw,)
        # Reorder beam-major state by parent.
        cache = KVCache(cache.k[:, gp], cache.v[:, gp])
        new_tokens = jnp.take_along_axis(
            new_tokens, parent[:, :, None], axis=1)
        new_tokens = new_tokens.at[:, :, i].set(tok)
        logits, cache = _forward_cached(
            cfg, params, tok.reshape(bw, 1), cache, prompt_len + i)
        return (cache, logits[:, -1], top_scores, new_tokens), None

    (cache, last, scores, new_tokens), _ = lax.scan(
        step, (cache, last, scores, new_tokens), jnp.arange(max_new_tokens))

    best = jnp.argmax(scores, axis=-1)  # (b,)
    best_new = jnp.take_along_axis(
        new_tokens, best[:, None, None], axis=1)[:, 0]  # (b, max_new)
    return (jnp.concatenate([prompt, best_new], axis=1),
            jnp.take_along_axis(scores, best[:, None], axis=-1)[:, 0])
