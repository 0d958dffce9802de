"""Gather-free paged attention — index KV pages inside the attention
contraction, never materializing a slot's dense logical view.

PR 13's paged engine bought its capacity win (one shared refcounted page
pool, per-slot block tables, copy-on-write prefix reuse) by paying HBM
bandwidth every step: each paged program ran ``gather_pages`` (table →
full ``(layers, slots, max_len, kv_heads, dh)`` dense view), the exact
dense math, then ``scatter_pages`` — so a decode step that adds ONE
token's worth of state still streamed every live page through HBM
twice and held the whole view live across the forward.  Decode on TPU
is HBM-bandwidth-bound, not FLOP-bound (PAPERS.md arXiv:2204.06514), so
that traffic was the paged engine's perf ceiling.  This module removes
it: attention reads K/V **through the block table**, block-wise over
``(pages, page_size)`` tiles, one layer at a time.

A page is ``(page_tokens, kv_heads * dh)``: one cached token a row, its
KV heads side by side on the lanes (``generate.KVPages``; int8 payloads
the same, with a ``(page_tokens, kv_heads)`` block of scales beside
them).  The kernels take a page block in exactly that form and find a
head as a lane slice of the row, and the einsum backend splits the
GATHERED rows back into heads, so nothing ever reshapes, pads or
relayouts the pool itself: stored ``(..., kv_heads, 64)`` it cost four
copies of the whole pool a step program on the v5e (XLA kept the
compact tiling, Mosaic fixed a lane-padded one; PERF.md section 6, PR 35).

Two backends behind one op:

  * ``impl='einsum'`` (the engine default) — **bit-exact**: per-page
    tiles ``pool[table]`` feed the contraction directly
    (``...d,bptkd->...pt``) and the flattened ``(pages·page_size)``
    logit axis gets exactly the dense path's visibility mask, fp32
    softmax, and P·V einsum.  XLA canonicalizes the ``(p, t)``
    contraction to the same gemm as the dense ``max_len`` axis, so fp
    outputs are **bitwise identical** to the dense math — which is what
    preserves the PR 13 parity oracle (paged ≡ dense ≡ ``generate()``)
    while the dense view and its scatter are gone (the committed budget
    ledger pins the peak-live drop).
  * ``impl='kernel'`` — a Pallas paged-decode kernel: grid over
    ``(slot, kv_pages)``, online-softmax carry (running max /
    denominator / output accumulator) in VMEM scratch exactly like the
    flash kernel, the block table and per-slot positions ride as
    SCALAR PREFETCH so each grid step's page is DMA'd straight from the
    pool by table value, ``-1`` (unmapped) entries skip their compute
    via ``pl.when``, and int8 pages dequantize in-kernel.  Tolerance-
    bounded like flash (online softmax rounds differently from the XLA
    chain), so the engine treats it as an explicit opt-in
    (``Engine(paged_attn='kernel')``).  Runs in interpret mode on the
    CPU platform (and only there unless ``interpret=True`` is passed) so
    the same code is unit-testable on the CPU host.

The op covers both attention families the decode twins use: the GPT-2
MHA einsum forms and LLaMA's grouped (GQA) forms — selected by
``grouped`` so each family's paged math mirrors ITS dense twin
op-for-op (the bitwise contract is per-family).

:func:`latent_paged_attention` is the third family's: absorbed latent
(MLA) attention over ``generate.LatentPages`` under an online softmax a
page tile at a time (no dense twin to be bitwise with; tolerance-bounded
against the expanded form).  ``impl='einsum'`` runs the tiles as XLA
contractions in a ``fori_loop``; ``impl='kernel'`` is ONE Mosaic call a
layer (``latent_attn``) whose score, softmax and value tiles never leave
VMEM: absorbed MLA is multi-query attention, so a token's heads are
query ROWS against one shared key row a cached token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30
_LANES = 128  # per-row online-softmax scratch, broadcast over one lane tile
# The window/tree kernels' m/l stats keep one value per row; a narrow
# 8-lane declaration is enough (an f32 VMEM tile is (8, 128) — the
# array is lane-padded physically either way, but the narrow shape
# keeps the committed budget ledger honest about bytes the kernel
# actually carries).
_STAT_LANES = 8


def _interpret_default() -> bool:
    """Interpret mode is the CPU platform's stand-in for Mosaic, and only
    that: every other backend compiles the kernel (or fails loudly) unless
    the caller passes ``interpret=True`` itself."""
    return jax.default_backend() == "cpu"


def page_tiles(pages, table, dtype, dh: int):
    """Per-slot ``(b, M, T, kv, dh)`` K/V tiles indexed by the block
    table — the read half of the gather-free contract.  ``pages`` is
    the per-layer page buffer pair ``(k, v)`` (fp), each ``(pages + 1,
    T, kv * dh)``: the GATHERED rows are split back into heads of
    ``dh``, the pool itself never is; or the quadruple
    ``(k, v, k_scale, v_scale)`` (int8; dequantized here with exactly
    ``generate.gather_pages``'s math, so int8 tile values match the
    gather path's bit-for-bit).  Unmapped table entries (``-1``) clamp
    to the trailing scratch page; its garbage only ever lands at
    positions the visibility mask excludes — the same standing contract
    as the dense arena's garbage-beyond-``pos`` rows."""
    scratch = pages[0].shape[0] - 1
    tbl = jnp.where(table >= 0, table, scratch)

    def heads(buf):  # (b, M, T, kv * dh) -> (b, M, T, kv, dh)
        rows = buf[tbl]
        return rows.reshape(*rows.shape[:-1], -1, dh)

    if len(pages) == 4:
        k8, v8, ks, vs = pages
        k = (heads(k8).astype(jnp.float32) * ks[tbl][..., None]).astype(dtype)
        v = (heads(v8).astype(jnp.float32) * vs[tbl][..., None]).astype(dtype)
        return k, v
    k, v = pages
    return heads(k).astype(dtype), heads(v).astype(dtype)


def _seen(k_pos, q_pos, window):
    """Which keys a query at ``q_pos`` attends: those at or before it
    and, on a sliding-window layer, fewer than ``window`` positions
    behind it.  ``window=None`` adds nothing to the causal trace."""
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (k_pos > q_pos - window)


def _einsum_paged(q, pages, table, pos, *, dtype, grouped, window=None):
    """The bit-exact blockwise path.  ``q``: ``(b, cur, h, dh)``;
    ``pos``: ``(b,)`` per-row depths (window position ``j`` attends
    keys ``<= pos + j`` — one contraction per position, the vmapped
    form that keeps a k+1 verify window bitwise equal to k+1 single
    steps) or a scalar (the prefill window: ONE batched contraction
    over the whole window, mirroring the scalar-``pos`` dense path).
    ``window``: a sliding layer's span (:func:`_seen`)."""
    b, cur, h, dh = q.shape
    kt, vt = page_tiles(pages, table, dtype, dh)  # (b, M, T, kv, dh)
    kv = kt.shape[3]
    max_len = kt.shape[1] * kt.shape[2]
    scale = dh ** -0.5
    pos = jnp.asarray(pos)

    if grouped:
        g = h // kv
        qg = q.reshape(b, cur, kv, g, dh)
        if pos.ndim:
            q_pos = pos[:, None] + jnp.arange(cur)  # (b, cur)

            def _attend(qj, pj):  # qj (b, kv, g, dh), pj (b,)
                lg = (jnp.einsum("bkgd,bptkd->bkgpt", qj, kt)
                      * scale).reshape(b, kv, g, max_len)
                vis = _seen(jnp.arange(max_len)[None, None, None, :],
                            pj[:, None, None, None], window)
                lg = jnp.where(vis, lg, jnp.finfo(lg.dtype).min)
                pr = jax.nn.softmax(lg.astype(jnp.float32),
                                    axis=-1).astype(dtype)
                return jnp.einsum("bkgpt,bptkd->bkgd",
                                  pr.reshape(b, kv, g, *kt.shape[1:3]), vt)

            out = jax.vmap(_attend, in_axes=(1, 1), out_axes=1)(qg, q_pos)
        else:
            lg = (jnp.einsum("bqkgd,bptkd->bkgqpt", qg, kt)
                  * scale).reshape(b, kv, g, cur, max_len)
            q_pos = pos + jnp.arange(cur)[:, None]
            visible = _seen(jnp.arange(max_len)[None, :], q_pos, window)
            lg = jnp.where(visible[None, None, None], lg,
                           jnp.finfo(lg.dtype).min)
            pr = jax.nn.softmax(lg.astype(jnp.float32),
                                axis=-1).astype(dtype)
            out = jnp.einsum("bkgqpt,bptkd->bqkgd",
                             pr.reshape(b, kv, g, cur, *kt.shape[1:3]), vt)
        return out.reshape(b, cur, h, dh)

    if pos.ndim:
        q_pos = pos[:, None] + jnp.arange(cur)  # (b, cur)

        def _attend(qj, pj):  # qj (b, h, dh), pj (b,)
            lg = (jnp.einsum("bhd,bpthd->bhpt", qj, kt)
                  * scale).reshape(b, h, max_len)
            vis = _seen(jnp.arange(max_len)[None, None, :],
                        pj[:, None, None], window)
            lg = jnp.where(vis, lg, jnp.finfo(lg.dtype).min)
            pr = jax.nn.softmax(lg.astype(jnp.float32),
                                axis=-1).astype(dtype)
            return jnp.einsum("bhpt,bpthd->bhd",
                              pr.reshape(b, h, *kt.shape[1:3]), vt)

        return jax.vmap(_attend, in_axes=(1, 1), out_axes=1)(q, q_pos)

    lg = (jnp.einsum("bqhd,bpthd->bhqpt", q, kt)
          * scale).reshape(b, h, cur, max_len)
    q_pos = pos + jnp.arange(cur)[:, None]
    visible = _seen(jnp.arange(max_len)[None, :], q_pos, window)
    lg = jnp.where(visible[None, None], lg, jnp.finfo(lg.dtype).min)
    pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1).astype(dtype)
    return jnp.einsum("bhqpt,bpthd->bqhd",
                      pr.reshape(b, h, cur, *kt.shape[1:3]), vt)


def latent_paged_attention(q_lat, q_rope, pages, table, pos, *, scale: float,
                           dtype, layer: int, impl: str = "einsum",
                           interpret: bool | None = None):
    """Absorbed latent (MLA) attention over table-indirected latent pages:
    one op for a decode step and a prefill window.

    ``q_lat`` ``(b, cur, heads, c)`` are the queries already carried into
    the latent space (``q_nope W_kvb,k^T``), ``q_rope`` ``(b, cur, heads,
    r)`` their rotated parts; ``pages`` the WHOLE stacked pool ``(c_pages
    (layers, pages + 1, T, c), r_pages (layers, pages + 1, T, r))``
    (``generate.LatentPages``; the last page is the write scratch), of
    which stratum ``layer`` is read, a page a slot at a time, by gather:
    no slice of the pool is ever a value.  ``table`` ``(b, max_pages)``,
    ``-1`` unmapped; ``pos`` ``(b,)`` per-row depths or a scalar: window
    position ``j`` attends the cached tokens ``<= pos + j`` (causal inside
    a prefill window, whose own latents are in their page already).

    ``score = (q_lat . c_kv + q_rope . k_rope) * scale`` in float32,
    softmax in float32 ONLINE over ``(page_tokens,)`` tiles (running
    maximum, denominator and ``(b, cur, heads, c)`` accumulator, as the
    flash kernels carry them), so the ``(heads, cur, max_len)`` score
    tensor of a 512-token window over 8,192 positions never exists; the
    loop runs over the pages that the deepest row of the call reaches (a
    traced bound), not over the table's width.  Returns ``sum_t p_t
    c_kv,t`` ``(b, cur, heads, c)`` in ``dtype``: the caller carries it
    out of the latent space (``W_kvb,v``).  Every row's first page holds a
    visible token (position 0), so the running maximum is finite from the
    first tile on and a masked score weighs exactly zero.

    ``impl='kernel'`` runs the same recurrence on the same operand types
    as one Mosaic call (:func:`_latent_paged`; interpreted on the CPU
    platform unless ``interpret`` says otherwise), tolerance-bounded against
    this loop like the other paged kernels against theirs.  There a row
    that sees no key at all comes back as zeros."""
    if impl not in ("einsum", "kernel"):
        raise ValueError(
            f"unknown latent paged-attention impl {impl!r}; choose from "
            f"'einsum' (XLA contractions a page tile) or 'kernel' (Mosaic)")
    if impl == "kernel":
        return _latent_paged(
            q_lat, q_rope, pages, table, pos, scale=scale, dtype=dtype,
            layer=layer,
            interpret=_interpret_default() if interpret is None
            else interpret)
    c_pages, r_pages = pages
    b, cur, h, c = q_lat.shape
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
    tokens, max_pages = c_pages.shape[2], table.shape[1]
    tbl = jnp.where(table >= 0, table, c_pages.shape[1] - 1)
    q_pos = pos[:, None] + jnp.arange(cur)  # (b, cur)
    reach = jnp.minimum((jnp.max(pos) + cur + tokens - 1) // tokens,
                        max_pages)
    f32 = jnp.float32

    def tile(m, carry):
        top, den, acc = carry
        page = jax.lax.dynamic_index_in_dim(tbl, m, axis=1, keepdims=False)
        ct = c_pages[layer, page].astype(dtype)  # (b, T, c)
        rt = r_pages[layer, page].astype(dtype)
        s = (jnp.einsum("bqhc,btc->bqht", q_lat, ct,
                        preferred_element_type=f32)
             + jnp.einsum("bqhr,btr->bqht", q_rope, rt,
                          preferred_element_type=f32)) * scale
        seen = (m * tokens + jnp.arange(tokens))[None, None, :] \
            <= q_pos[:, :, None]
        s = jnp.where(seen[:, :, None, :], s, _NEG_INF)
        new_top = jnp.maximum(top, jnp.max(s, axis=-1))
        p = jnp.exp(s - new_top[..., None])
        keep = jnp.exp(top - new_top)
        return (new_top, den * keep + jnp.sum(p, axis=-1),
                acc * keep[..., None]
                + jnp.einsum("bqht,btc->bqhc", p.astype(dtype), ct,
                             preferred_element_type=f32))

    # the scope names the loop in a profile read by hand; the benchmark's
    # reader (perf/metrics/latent_attn_ms.py) knows it by this carry
    with jax.named_scope("latent_attn"):
        _, den, acc = jax.lax.fori_loop(
            0, reach, tile, (jnp.full((b, cur, h), _NEG_INF, f32),
                             jnp.zeros((b, cur, h), f32),
                             jnp.zeros((b, cur, h, c), f32)))
        return (acc / den[..., None]).astype(dtype)


# ------------------------------------------------ latent Pallas kernel

#: Where the running maximum starts.  Above the mask's ``_NEG_INF``, so a
#: masked score weighs ``exp(-1e30 + 1e20) == 0`` even before a row has
#: met its first visible key, and below every score a model can produce.
_LATENT_MAX0 = -1e20


def _latent_block_rows(rows: int) -> int:
    """Query rows a grid step: the largest divisor of ``rows`` up to 1,024
    that fills whole sublane tiles (a prefill chunk's 8 tokens x 128
    heads; a decode slot's ``heads`` rows are one block)."""
    for cand in range(min(rows, 1024), 7, -1):
        if rows % cand == 0 and cand % 8 == 0:
            return cand
    return rows


def _latent_kernel(tbl_ref, pos_ref, ql_ref, qr_ref, c_ref, r_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, heads: int, block_rows: int,
                   page_tokens: int, n_pages: int, scale: float, dtype):
    """One ``(slot, row block, table entry)`` grid step of absorbed latent
    attention.  Query row ``r`` of a slot is head ``r % heads`` of window
    token ``r // heads`` and sees the keys ``<= pos[slot] + r // heads``;
    every row reads the SAME key row a cached token (the latent and the
    rotary lanes) and the same value row (the latent), so a page is one
    ``(rows, c) x (c, T)`` + ``(rows, r) x (r, T)`` score product and one
    ``(rows, T) x (T, c)`` value product, operands in ``dtype``, float32
    accumulation and softmax.  Entries that are ``-1`` or lie wholly past
    the block's last query do nothing (and fetched nothing: the index map
    held the resident page); pages wholly visible to the block's first
    query skip the mask."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    s, i, m = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row0 = i * block_rows
    first = pos_ref[s] + row0 // heads  # the block's first query position
    last = pos_ref[s] + (row0 + block_rows - 1) // heads
    page0 = m * page_tokens

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _LATENT_MAX0)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(masked: bool):
        ct = c_ref[0].astype(dtype)  # (T, c): keys AND values
        rt = r_ref[0].astype(dtype)  # (T, r)
        nt = (((1,), (1,)), ((), ()))  # contract both operands' lanes
        sc = (lax.dot_general(ql_ref[0], ct, nt,
                              preferred_element_type=jnp.float32)
              + lax.dot_general(qr_ref[0], rt, nt,
                                preferred_element_type=jnp.float32)) * scale
        if masked:
            k_pos = page0 + lax.broadcasted_iota(
                jnp.int32, (block_rows, page_tokens), 1)
            q_pos = pos_ref[s] + (row0 + lax.broadcasted_iota(
                jnp.int32, (block_rows, 1), 0)) // heads
            sc = jnp.where(k_pos <= q_pos, sc, _NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]  # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)  # (rows, T)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(dtype), ct, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    wanted = (tbl_ref[s * n_pages + m] >= 0) & (page0 <= last)
    whole = page0 + page_tokens - 1 <= first
    pl.when(wanted & whole)(functools.partial(tile, False))
    pl.when(wanted & jnp.logical_not(whole))(functools.partial(tile, True))

    @pl.when(m == pl.num_programs(2) - 1)
    def _finalize():  # a row that met no key: 0 / 1e-30, zeros
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
            o_ref.dtype)


# tpudp: kernel-program(serve.decode_paged_latent_kernel)
def _latent_paged(q_lat, q_rope, pages, table, pos, *, scale, dtype, layer,
                  interpret):
    """Dispatch :func:`latent_paged_attention` through the Mosaic kernel,
    a decode step (vector ``pos``) and a prefill window (scalar ``pos``)
    alike.  The queries are viewed as rows ``(b, cur * heads, ...)`` (free
    reshapes), the grid is ``(slot, row block, table entry)`` with the
    online-softmax carry in VMEM scratch across the innermost axis, the
    block table and ``pos`` ride as scalar prefetch, and a page block is
    DMA'd from the WHOLE stacked pool by table value with ``layer`` picked
    in the BlockSpec: no slice of the pool is ever a value.  An entry the
    row block does not reach maps to the last page it does, which is
    resident already, so skipped grid steps move no bytes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c_pages, r_pages = pages
    b, cur, h, c = q_lat.shape
    rope = q_rope.shape[-1]
    page_tokens, n_pages = c_pages.shape[2], table.shape[1]
    scratch_page = c_pages.shape[1] - 1
    rows = cur * h
    block_rows = _latent_block_rows(rows)

    tbl = jnp.asarray(table, jnp.int32).reshape(-1)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    # the table entries the deepest row of the call reaches (a traced
    # bound, the XLA loop's): the grid does not walk the table's width
    reach = jnp.clip((jnp.max(pos) + cur + page_tokens - 1) // page_tokens,
                     1, n_pages)

    def rows_map(s, i, m, tbl_ref, pos_ref):
        return (s, i, 0)

    def page_map(s, i, m, tbl_ref, pos_ref):
        last = pos_ref[s] + (i * block_rows + block_rows - 1) // h
        t = tbl_ref[s * n_pages + jnp.minimum(m, last // page_tokens)]
        return (layer, jnp.where(t >= 0, t, scratch_page), 0, 0)

    kernel = functools.partial(
        _latent_kernel, heads=h, block_rows=block_rows,
        page_tokens=page_tokens, n_pages=n_pages, scale=scale, dtype=dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, rows // block_rows, reach),
        in_specs=[
            pl.BlockSpec((1, block_rows, c), rows_map),
            pl.BlockSpec((1, block_rows, rope), rows_map),
            pl.BlockSpec((None, 1, page_tokens, c), page_map),
            pl.BlockSpec((None, 1, page_tokens, rope), page_map),
        ],
        out_specs=pl.BlockSpec((1, block_rows, c), rows_map),
        scratch_shapes=[
            pltpu.VMEM((block_rows, c), jnp.float32),
            pltpu.VMEM((block_rows, _LANES), jnp.float32),
            pltpu.VMEM((block_rows, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, c), dtype),
        interpret=interpret,
        name="latent_attn",
    )(tbl, pos, q_lat.reshape(b, rows, c), q_rope.reshape(b, rows, rope),
      c_pages, r_pages)
    return out.reshape(b, cur, h, c)


# ------------------------------------------------------- Pallas kernel
#
# A page block is ``(page_tokens, kv * dh)``: one cached token a row, its
# KV heads side by side on the lanes (``generate.KVPages``), which is how
# the pool is stored, so a block is DMA'd as it lies and XLA never
# relayouts the pool around a call.  A head is a lane slice of the row.


def _head(blk, scales, ki: int, dh: int):
    """KV head ``ki`` of a float32 page block ``(T, kv * dh)`` ->
    ``(T, dh)``, dequantised by its per-token scale column where the
    block is an int8 payload (``scales`` ``(T, kv)``)."""
    x = blk[:, ki * dh:(ki + 1) * dh]
    return x if scales is None else x * scales[:, ki:ki + 1]


def _decode_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                   kv: int, groups: int, page_tokens: int, n_pages: int,
                   scale: float, int8: bool, window: int | None = None):
    """One ``(slot, page)`` grid step of the paged-decode kernel.

    The block specs already fetched THIS slot's page ``m`` by table
    value (the index maps read the scalar-prefetched table), so the
    kernel body only runs the online-softmax recurrence over the page's
    ``page_tokens`` keys — running max / denominator / accumulator
    carried in VMEM scratch across the page axis, exactly the flash
    kernel's recurrence with the K-block stream replaced by a
    table-indirected page stream."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    if int8:  # int8 payloads ride two extra per-vector scale blocks
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    m = pl.program_id(1)
    h = kv * groups

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mapped = tbl_ref[s * n_pages + m] >= 0

    @pl.when(mapped)  # -1 (unmapped) pages: skip — nothing to attend
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (h, dh)
        dh = q.shape[-1]
        k_blk = k_ref[0].astype(jnp.float32)      # (T, kv * dh)
        v_blk = v_ref[0].astype(jnp.float32)
        ks = ks_ref[0].astype(jnp.float32) if int8 else None  # (T, kv)
        vs = vs_ref[0].astype(jnp.float32) if int8 else None
        # Query head j attends KV head j // groups (the GQA mapping;
        # groups == 1 is MHA).  Static per-KV-head 2D dots keep the MXU
        # happy — kv is a small compile-time constant.
        rows = []
        for ki in range(kv):
            qk = q[ki * groups:(ki + 1) * groups]  # (g, dh)
            rows.append(jnp.dot(qk, _head(k_blk, ks, ki, dh).T,
                                preferred_element_type=jnp.float32))
        s_blk = jnp.concatenate(rows, axis=0)  # (h, T)
        k_pos = m * page_tokens + lax.broadcasted_iota(
            jnp.int32, (h, page_tokens), 1)
        # (a mapped page wholly behind a sliding layer's window weighs
        # one a key until the first visible key's alpha wipes it: zero)
        s_blk = jnp.where(_seen(k_pos, pos_ref[s], window), s_blk, _NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)  # (h, 1)
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)  # (h, T)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        pv = []
        for ki in range(kv):
            pv.append(jnp.dot(p[ki * groups:(ki + 1) * groups],
                              _head(v_blk, vs, ki, dh),
                              preferred_element_type=jnp.float32))
        acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(pv, axis=0)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(m == n_pages - 1)
    def _finalize():
        l_safe = jnp.maximum(jnp.max(l_ref[...], axis=-1, keepdims=True),
                             1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


# tpudp: kernel-program(serve.decode_paged_kernel)
def _kernel_paged(q, pages, table, pos, *, dtype, interpret, layer=None,
                  window=None):
    """Dispatch one decode step (``cur == 1``) through the Pallas
    paged-decode kernel.  ``q``: ``(b, 1, h, dh)``; the grid is
    ``(b, M)`` with the online-softmax carry persisting across the
    inner (page) axis; the table row and per-slot positions are scalar
    prefetch, so each page block is DMA'd by TABLE VALUE — the gather
    never exists even as a transient.

    With ``layer`` (the engine's whole-pool mode) ``pages`` carry the
    FULL stacked pool ``(layers, ...)`` and the BlockSpec picks the
    stratum (a ``None`` block axis, squeezed out of the refs) — the
    layer slice is never materialized as an XLA value, so nothing
    beyond the pool itself is ever live at the call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, cur, h, dh = q.shape
    assert cur == 1, "the paged-decode kernel is a 1-token decode kernel"
    int8 = len(pages) == 4
    lx = () if layer is None else (layer,)
    pb = (None,) * len(lx)  # layer block axis, squeezed out of the refs
    k_pages, v_pages = pages[0], pages[1]
    n_real = k_pages.shape[len(lx)] - 1  # trailing page is write scratch
    page_tokens = k_pages.shape[1 + len(lx)]
    row = k_pages.shape[2 + len(lx)]  # kv * dh: a token's stored row
    kv = row // dh
    n_pages = table.shape[1]
    groups = h // kv
    scale = dh ** -0.5
    scratch_page = n_real

    tbl = jnp.asarray(table, jnp.int32).reshape(-1)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def page_map(s, m, tbl_ref, pos_ref):  # payload and scale blocks
        t = tbl_ref[s * n_pages + m]
        return (*lx, jnp.where(t >= 0, t, scratch_page), 0, 0)

    kernel = functools.partial(
        _decode_kernel, kv=kv, groups=groups, page_tokens=page_tokens,
        n_pages=n_pages, scale=scale, int8=int8, window=window)
    ins = (pages[0], pages[1]) + ((pages[2], pages[3]) if int8 else ())
    in_specs = [
        pl.BlockSpec((1, h, dh), lambda s, m, t, p: (s, 0, 0)),
        pl.BlockSpec((*pb, 1, page_tokens, row), page_map),
        pl.BlockSpec((*pb, 1, page_tokens, row), page_map),
    ]
    if int8:
        in_specs += [pl.BlockSpec((*pb, 1, page_tokens, kv), page_map),
                     pl.BlockSpec((*pb, 1, page_tokens, kv), page_map)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, dh), lambda s, m, t, p: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, dh), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), dtype),
        interpret=interpret,
        # a sliding layer's calls under a name of their own: the trace
        # tells the two layer kinds apart
        name="paged_decode" if window is None else "paged_decode_window",
    )(tbl, pos, q[:, 0], *ins)
    return out[:, None]


def _window_tile(width: int) -> int:
    """Largest query-tile width ≤ 32 dividing the window — the chunk
    axis of the prefill grid (``chunk_tiles × kv_pages``).  Verify
    windows (k+1 ≤ 32) always fit one tile."""
    for cand in range(min(width, 32), 0, -1):
        if width % cand == 0:
            return cand
    return width


def _window_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                   kv: int, groups: int, width: int, page_tokens: int,
                   n_pages: int, scale: float, int8: bool,
                   window: int | None = None):
    """One ``(slot, query-tile, page)`` grid step of the paged
    flash-window kernel — the multi-token generalization of
    ``_decode_kernel`` that covers chunked prefill (scalar base
    position, ``width`` = chunk tile) and the k+1 speculative verify
    window (vector base positions, one tile).

    Query rows are flattened KV-head-major — row
    ``r = ki·(width·groups) + j·groups + gi`` — so each KV head's rows
    are one contiguous 2D dot against its page slice, and the causal
    in-window mask is per ROW: window position ``j`` sees keys
    ``<= pos[slot] + j`` (the engine writes the window's K/V into pages
    BEFORE attending, so in-window causality and cache visibility are
    the same comparison)."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    if int8:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    t = pl.program_id(1)
    m = pl.program_id(2)
    rows = kv * width * groups
    dh = q_ref.shape[-1]

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mapped = tbl_ref[s * n_pages + m] >= 0

    @pl.when(mapped)  # -1 (unmapped) pages: skip — nothing to attend
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (width, h, dh)
        k_blk = k_ref[0].astype(jnp.float32)      # (T, kv * dh)
        v_blk = v_ref[0].astype(jnp.float32)
        ks = ks_ref[0].astype(jnp.float32) if int8 else None  # (T, kv)
        vs = vs_ref[0].astype(jnp.float32) if int8 else None
        blocks = []
        for ki in range(kv):
            qk = q[:, ki * groups:(ki + 1) * groups, :].reshape(
                width * groups, dh)
            blocks.append(jnp.dot(qk, _head(k_blk, ks, ki, dh).T,
                                  preferred_element_type=jnp.float32))
        s_blk = jnp.concatenate(blocks, axis=0)  # (rows, T)
        k_pos = m * page_tokens + lax.broadcasted_iota(
            jnp.int32, (rows, page_tokens), 1)
        row_ids = lax.broadcasted_iota(jnp.int32, (rows, page_tokens), 0)
        win_j = t * width + (row_ids % (width * groups)) // groups
        s_blk = jnp.where(_seen(k_pos, pos_ref[s] + win_j, window), s_blk,
                          _NEG_INF)
        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)  # (rows, 1)
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)  # (rows, T)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        pv = []
        for ki in range(kv):
            pv.append(jnp.dot(
                p[ki * width * groups:(ki + 1) * width * groups],
                _head(v_blk, vs, ki, dh),
                preferred_element_type=jnp.float32))
        acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(pv, axis=0)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(m == n_pages - 1)
    def _finalize():
        l_safe = jnp.maximum(jnp.max(l_ref[...], axis=-1, keepdims=True),
                             1e-30)
        out = acc_ref[...] / l_safe  # (rows, dh), kv-head-major
        for ki in range(kv):
            blk = out[ki * width * groups:(ki + 1) * width * groups]
            o_ref[0, :, ki * groups:(ki + 1) * groups, :] = (
                blk.reshape(width, groups, dh).astype(o_ref.dtype))


# tpudp: kernel-program(serve.verify_paged_kernel)
def _window_paged(q, pages, table, pos, *, dtype, interpret, layer=None,
                  window=None):
    """Dispatch a multi-token window (k+1 verify, vector ``pos``; or a
    prefill chunk, scalar ``pos``) through the flash-window kernel.
    Grid ``(b, chunk_tiles, M)`` with the online-softmax carry
    persisting across the inner page axis — the prefill grid the ISSUE
    names, with verify as the one-tile case.  ``layer`` selects a
    stratum of a full stacked pool via the BlockSpec (see
    :func:`_kernel_paged`) — no layer slice is ever materialized."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, cur, h, dh = q.shape
    int8 = len(pages) == 4
    lx = () if layer is None else (layer,)
    pb = (None,) * len(lx)
    k_pages = pages[0]
    page_tokens = k_pages.shape[1 + len(lx)]
    row = k_pages.shape[2 + len(lx)]  # kv * dh: a token's stored row
    kv = row // dh
    n_pages = table.shape[1]
    groups = h // kv
    scale = dh ** -0.5
    scratch_page = k_pages.shape[len(lx)] - 1
    width = _window_tile(cur)
    q_tiles = cur // width
    rows = kv * width * groups

    tbl = jnp.asarray(table, jnp.int32).reshape(-1)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def page_map(s, t, m, tbl_ref, pos_ref):  # payload and scale blocks
        pg = tbl_ref[s * n_pages + m]
        return (*lx, jnp.where(pg >= 0, pg, scratch_page), 0, 0)

    kernel = functools.partial(
        _window_kernel, kv=kv, groups=groups, width=width,
        page_tokens=page_tokens, n_pages=n_pages, scale=scale, int8=int8,
        window=window)
    ins = (pages[0], pages[1]) + ((pages[2], pages[3]) if int8 else ())
    in_specs = [
        pl.BlockSpec((1, width, h, dh),
                     lambda s, t, m, tb, p: (s, t, 0, 0)),
        pl.BlockSpec((*pb, 1, page_tokens, row), page_map),
        pl.BlockSpec((*pb, 1, page_tokens, row), page_map),
    ]
    if int8:
        in_specs += [pl.BlockSpec((*pb, 1, page_tokens, kv), page_map),
                     pl.BlockSpec((*pb, 1, page_tokens, kv), page_map)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, q_tiles, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, width, h, dh),
                               lambda s, t, m, tb, p: (s, t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, dh), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, cur, h, dh), dtype),
        interpret=interpret,
        name="paged_prefill" if window is None else "paged_prefill_window",
    )(tbl, pos, q, *ins)


def _tree_kernel(tbl_ref, pos_ref, anc_ref, q_ref, k_ref, v_ref,
                 wk_ref, wv_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 kv: int, groups: int, t1: int, page_tokens: int,
                 n_pages: int, scale: float):
    """One ``(slot, page-or-window)`` grid step of the tree-verify
    kernel.  Steps ``m < n_pages`` stream the slot's CACHE pages with
    strict visibility ``k_pos < pos0[slot]`` (tree nodes occupy
    ``pos0..``, so committed state is everything strictly before); the
    extra final step ``m == n_pages`` folds the T+1 in-flight window
    keys into the same online softmax under the ancestor-or-self mask,
    which rides as a scalar-prefetched per-shape constant (the parents
    tuple is static engine config, part of the compile key)."""
    import jax.lax as lax
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    m = pl.program_id(1)
    rows = kv * t1 * groups
    dh = q_ref.shape[-1]

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _scores(k_src):
        q = q_ref[0].astype(jnp.float32) * scale  # (t1, h, dh)
        blocks = []
        for ki in range(kv):
            qk = q[:, ki * groups:(ki + 1) * groups, :].reshape(
                t1 * groups, dh)
            blocks.append(jnp.dot(qk, _head(k_src, None, ki, dh).T,
                                  preferred_element_type=jnp.float32))
        return jnp.concatenate(blocks, axis=0)  # (rows, n_keys)

    def _update(s_blk, v_src):
        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        pv = []
        for ki in range(kv):
            pv.append(jnp.dot(
                p[ki * t1 * groups:(ki + 1) * t1 * groups],
                _head(v_src, None, ki, dh),
                preferred_element_type=jnp.float32))
        acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(pv, axis=0)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    mi = jnp.minimum(m, n_pages - 1)  # keep the SMEM read in bounds
    mapped = (m < n_pages) & (tbl_ref[s * n_pages + mi] >= 0)

    @pl.when(mapped)
    def _cache_page():
        k_blk = k_ref[0].astype(jnp.float32)  # (T, kv * dh)
        v_blk = v_ref[0].astype(jnp.float32)
        s_blk = _scores(k_blk)
        k_pos = mi * page_tokens + lax.broadcasted_iota(
            jnp.int32, (rows, page_tokens), 1)
        s_blk = jnp.where(k_pos < pos_ref[s], s_blk, _NEG_INF)
        _update(s_blk, v_blk)

    @pl.when(m == n_pages)
    def _window_block():
        wk = wk_ref[0].astype(jnp.float32)  # (t1, kv * dh): page rows
        wv = wv_ref[0].astype(jnp.float32)
        s_blk = _scores(wk)  # (rows, t1)
        anc = jnp.array([[anc_ref[j * t1 + c] for c in range(t1)]
                         for j in range(t1)])  # (t1, t1) from SMEM
        per_node = jnp.broadcast_to(
            anc[:, None, :], (t1, groups, t1)).reshape(t1 * groups, t1)
        mask = jnp.broadcast_to(
            per_node[None], (kv, t1 * groups, t1)).reshape(rows, t1)
        s_blk = jnp.where(mask > 0, s_blk, _NEG_INF)
        _update(s_blk, wv)
        l_safe = jnp.maximum(jnp.max(l_ref[...], axis=-1, keepdims=True),
                             1e-30)
        out = acc_ref[...] / l_safe
        for ki in range(kv):
            blk = out[ki * t1 * groups:(ki + 1) * t1 * groups]
            o_ref[0, :, ki * groups:(ki + 1) * groups, :] = (
                blk.reshape(t1, groups, dh).astype(o_ref.dtype))


# tpudp: kernel-program(serve.tree_verify_paged_kernel)
def _tree_paged(q, pages, table, pos0, wk, wv, anc, *, dtype, interpret):
    """Dispatch the static tree-verify forward through the tree kernel:
    grid ``(b, M + 1)`` — the cache pages plus ONE extra grid step for
    the in-flight window keys (never written to pages; rejected
    branches must leave zero pool bytes, so the window rides as its own
    VMEM block).  fp pools only — int8 pools fall back to the einsum
    tree path at the engine layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if len(pages) == 4:
        raise NotImplementedError(
            "the tree-verify kernel reads fp pages only; int8 pools take "
            "the einsum fallback (Engine records the dispatch)")
    b, t1, h, dh = q.shape
    k_pages = pages[0]
    page_tokens, row = k_pages.shape[1], k_pages.shape[2]
    kv = row // dh
    n_pages = table.shape[1]
    groups = h // kv
    scale = dh ** -0.5
    scratch_page = k_pages.shape[0] - 1

    tbl = jnp.asarray(table, jnp.int32).reshape(-1)
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))
    anc_flat = jnp.asarray(anc, jnp.int32).reshape(-1)

    def page_map(s, m, tbl_ref, pos_ref, anc_ref):
        mi = jnp.minimum(m, n_pages - 1)
        pg = tbl_ref[s * n_pages + mi]
        pg = jnp.where((m < n_pages) & (pg >= 0), pg, scratch_page)
        return (pg, 0, 0)

    def slot_map(s, m, tbl_ref, pos_ref, anc_ref):
        return (s, 0, 0, 0)

    def window_map(s, m, tbl_ref, pos_ref, anc_ref):
        return (s, 0, 0)

    kernel = functools.partial(
        _tree_kernel, kv=kv, groups=groups, t1=t1,
        page_tokens=page_tokens, n_pages=n_pages, scale=scale)
    rows = kv * t1 * groups
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages + 1),
        in_specs=[
            pl.BlockSpec((1, t1, h, dh), slot_map),
            pl.BlockSpec((1, page_tokens, row), page_map),
            pl.BlockSpec((1, page_tokens, row), page_map),
            pl.BlockSpec((1, t1, row), window_map),
            pl.BlockSpec((1, t1, row), window_map),
        ],
        out_specs=pl.BlockSpec((1, t1, h, dh), slot_map),
        scratch_shapes=[
            pltpu.VMEM((rows, dh), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t1, h, dh), dtype),
        interpret=interpret,
        name="paged_tree",
    )(tbl, pos0, anc_flat, q, *pages, wk.reshape(b, t1, row),
      wv.reshape(b, t1, row))  # the window's K/V as page rows


# ----------------------------------------------------------- public op


def paged_attention(q, pages, table, pos, *, dtype, grouped: bool = False,
                    impl: str = "einsum", interpret: bool | None = None,
                    layer: int | None = None,
                    window: int | None = None) -> jnp.ndarray:
    """Attention for already-projected queries over table-indirected
    K/V pages — the ONE paged-attention op behind the serve engine's
    gather-free step programs.

    ``q``: ``(b, cur, heads, dh)`` queries (RoPE already applied for
    LLaMA).  ``pages``: one LAYER's page buffers — ``(k, v)`` each
    ``(num_pages + 1, page_tokens, kv_heads * dh)`` (a token's KV heads
    side by side in one row, ``generate.KVPages``; the last page is
    the write scratch; ``kv_heads`` is the row's width over ``q``'s
    ``dh``), or ``(k, v, k_scale, v_scale)`` for int8 payloads (scales
    ``(num_pages + 1, page_tokens, kv_heads)``).
    ``table``: ``(b, max_pages)`` int32 block table, ``-1``
    unmapped.  ``pos``: ``(b,)`` per-row depths (window position ``j``
    attends keys ``<= pos[b] + j``; the serve engine's vector-position
    contract) or a scalar (the prefill window's shared depth).
    ``grouped`` selects the GQA einsum family (LLaMA's dense-twin
    forms) over the MHA family (GPT-2's) so the fp path stays bitwise
    identical to whichever dense twin the caller mirrors.

    ``impl='einsum'`` is bit-exact vs the dense math on the gathered
    view; ``impl='kernel'`` routes the whole serving hot path through
    Pallas: single-token vector-position calls hit the paged-decode
    kernel, multi-token windows (the k+1 verify window) and scalar-
    position prefill chunks hit the flash-window kernel.  Both are
    tolerance-bounded like flash (online softmax rounds differently
    from the XLA chain); the einsum path stays the bit-exact fallback
    the engine selects per-program when a feature lacks kernel
    support.

    ``layer`` (kernel impl only) is whole-pool mode: ``pages`` carry
    the FULL stacked pool and the kernels' BlockSpecs pick the stratum
    — the per-layer slice never exists as an XLA value.

    ``window`` (static) makes the layer a sliding-window one: a query
    also masks the keys ``window`` or more positions behind it (``k_pos >
    q_pos - window``).  The mask alone decides what it sees: a table
    entry behind the window may be ``-1``, its page freed (the kernels
    skip it, the einsum path masks the scratch page it reads), or still
    mapped.  Its Mosaic calls are named ``paged_decode_window`` /
    ``paged_prefill_window``.  ``None`` traces exactly the causal
    program."""
    if impl not in ("einsum", "kernel"):
        raise ValueError(
            f"unknown paged-attention impl {impl!r}; choose from "
            f"'einsum' (bit-exact blockwise) or 'kernel' (Pallas decode)")
    if layer is not None and impl != "kernel":
        raise ValueError("whole-pool layer indexing is kernel-impl only")
    pos = jnp.asarray(pos)
    if impl == "kernel":
        if interpret is None:
            interpret = _interpret_default()
        if pos.ndim and q.shape[1] == 1:
            return _kernel_paged(q, pages, table, pos, dtype=dtype,
                                 interpret=interpret, layer=layer,
                                 window=window)
        return _window_paged(q, pages, table, pos, dtype=dtype,
                             interpret=interpret, layer=layer,
                             window=window)
    return _einsum_paged(q, pages, table, pos, dtype=dtype,
                         grouped=grouped, window=window)


def tree_paged_attention(q, pages, table, pos0, wk, wv, anc, *, dtype,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Tree-structured attention over table-indirected cache pages plus
    an in-flight node window — the kernel half of ``tree_verify_paged``.

    ``q``: ``(b, T+1, heads, dh)`` node queries; ``pages``: one layer's
    ``(k, v)``, each ``(num_pages + 1, page_tokens, kv * dh)``;
    ``wk``/``wv``: ``(b, T+1, kv, dh)`` window K/V (computed this
    forward, NEVER written to pages — rejected branches must leave zero
    pool bytes; they enter the kernel as page rows of their own);
    ``anc``: the static ``(T+1, T+1)`` ancestor-or-self mask (row j
    sees column c iff c is an ancestor of j or j itself), entering the
    kernel as a scalar-prefetched per-shape constant.  Cache visibility
    is strict ``k_pos < pos0`` — the committed prefix only.  fp pools
    only; the engine keeps int8 tree traffic on the einsum fallback."""
    if interpret is None:
        interpret = _interpret_default()
    return _tree_paged(q, pages, table, pos0, wk, wv, anc, dtype=dtype,
                       interpret=interpret)
