"""Pallas TPU grouped (ragged) matrix products: the expert layer's hot op.

A dropless expert layer sorts its token rows by expert, so expert ``g``
owns the contiguous rows ``[ends[g-1], ends[g])`` of one ``(M, K)`` buffer
(``ends = cumsum(group_sizes)``) and the rows from ``sum(group_sizes)`` on
belong to no expert held here.  THE INVARIANT the layer builds on: **those
rows are undefined and nobody reads them**; neither kernel ever does.  Two
kernels and a custom VJP cover the layer's nine products a step:

  * ``gmm(lhs (M, K), rhs (G, K, N), group_sizes) -> (M, N)``: each group's
    rows times its own matrix (HLO name ``moe_gmm``).  ``transpose_rhs``
    takes ``rhs`` as ``(G, N, K)``, which is the data gradient's product
    with the same weights.  Rows past the last group are never read and
    come back as zeros: the public entry's contract, paid for by one pass
    over all ``M`` rows after the kernel.  The expert layer calls
    :func:`gmm_walk` instead, under visit tables it made once
    (:func:`visits`), which leaves the tiles past the last visit UNWRITTEN
    and can add the product to a buffer it is given (``plus``).
  * ``tgmm(lhs (M, K), rhs (M, N), group_sizes) -> (G, K, N)``: each
    group's ``lhs_gᵀ · rhs_g`` (``moe_tgmm``), the weight gradient.  An
    empty group's matrix is zeros; rows past the last group are never read.

The grid walks VISITS, not row tiles.  A visit is one (group, row tile)
pair; a tile that a group boundary crosses is visited once by each group it
holds rows of, and tiles past the last group are not visited at all (the
visit count is a traced grid bound, so they cost nothing).  The visit
tables ride in SMEM (scalar prefetch) and the block index maps read them.
A visit whose tile lies wholly inside its group runs unmasked; one that a
boundary crosses masks by row: ``gmm`` at the store (earlier groups' rows of
the resident output block are kept, the rest zeroed), ``tgmm`` on both
operands (a zeroed row against a NaN would still poison the sum).

Operands reach the MXU in ``lhs``'s dtype (bf16 for a bf16 model; ``rhs``
is cast to it outside the kernel) and accumulate in float32.  ``gmm``
holds ``K`` whole in a block (expert widths are a few thousand), so it has
no reduction axis and no scratch; ``tgmm`` reduces over its visits into a
float32 VMEM accumulator that is written out when the group changes.
Blocks come from the shape (:func:`choose_blocks`) under ``_VMEM_BUDGET``.
Interpret mode on the CPU platform, Mosaic elsewhere, as
``ops/flash_attention.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# What a grid step may hold by `vmem_bytes`' count, and the limit Mosaic is
# given (the v5e's default scoped limit is 16 MiB of its 128 MiB).
_VMEM_BUDGET = 48 * 2**20
# Most rows a tile takes.  A group boundary costs a second visit of its
# tile, G - 1 extra tiles a call whatever the tile's height, and a taller
# tile re-reads `rhs` less often.  On the v5e at the LFM2 expert layer's
# shapes (131,072 rows, 32,903 of them in eight groups; PERF.md section 6,
# PR 29) 256 rows read 1.52 ms a `gmm` call against 1.53-1.55 at 128 and
# 1.59-1.62 at 512; `tgmm` 1.68 against 1.77 and 1.69.
_MAX_BLOCK_M = 256
_NN = (((1,), (0,)), ((), ()))  # a·b
_NT = (((1,), (1,)), ((), ()))  # a·bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ·b


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------- block choice


def vmem_bytes(kernel: str, block_m: int, block_k: int, block_n: int,
               in_dtype, out_dtype) -> int:
    """Upper count of the VMEM one grid step of ``kernel`` (``'gmm'`` or
    ``'tgmm'``) holds: its double-buffered in and out blocks and the
    float32 product (``gmm``: a value; ``tgmm``: the scratch accumulator
    and the step's own product beside it)."""
    isz, osz = jnp.dtype(in_dtype).itemsize, jnp.dtype(out_dtype).itemsize
    if kernel == "gmm":  # lhs (bm, K) | rhs (K, bn) | out (bm, bn)
        io = 2 * (block_m * block_k * isz + block_k * block_n * isz
                  + block_m * block_n * osz)
        return io + block_m * block_n * 4
    if kernel == "tgmm":  # lhs (bm, bk) | rhs (bm, bn) | out (bk, bn)
        io = 2 * (block_m * block_k * isz + block_m * block_n * isz
                  + block_k * block_n * osz)
        return io + 2 * block_k * block_n * 4
    raise ValueError(f"unknown grouped kernel {kernel!r}")


def _sides(x: int, cap: int | None = None) -> list[int]:
    """Block sides for an extent: its divisors that are multiples of 128
    (at most ``cap``); the extent itself when it is no multiple of 128
    (interpret mode only: Mosaic needs the lane alignment)."""
    if x % _LANES:
        return [x]
    return [b for b in range(_LANES, min(x, cap or x) + 1, _LANES)
            if x % b == 0]


def row_tile(m: int) -> int:
    """The row tile every kernel of a layer of ``m`` rows walks in."""
    return _sides(m, _MAX_BLOCK_M)[-1]


def choose_blocks(kernel: str, m: int, k: int, n: int, in_dtype,
                  out_dtype) -> tuple[int, int, int]:
    """``(block_m, block_k, block_n)`` for one kernel from the shape alone.

    ``block_m`` is the tallest side of ``m`` up to ``_MAX_BLOCK_M``.
    ``gmm`` holds ``k`` whole and takes the widest ``block_n`` that fits
    ``_VMEM_BUDGET`` (``lhs`` is read once per column of blocks).  ``tgmm``
    takes the ``(block_k, block_n)`` with the largest output block that
    fits, then the wider one (``lhs`` is read ``n / block_n`` times, ``rhs``
    ``k / block_k`` times)."""
    bm = row_tile(m)
    if kernel == "gmm":
        fits = [(bm, k, bn) for bn in _sides(n)
                if vmem_bytes(kernel, bm, k, bn, in_dtype, out_dtype)
                <= _VMEM_BUDGET]
    else:
        fits = [(bm, bk, bn) for bk in _sides(k) for bn in _sides(n)
                if vmem_bytes(kernel, bm, bk, bn, in_dtype, out_dtype)
                <= _VMEM_BUDGET]
    if not fits:
        raise ValueError(
            f"no {kernel} block of ({m}, {k}, {n}) fits {_VMEM_BUDGET} "
            "bytes of VMEM")
    return max(fits, key=lambda b: (b[1] * b[2], b[2]))


def supported(m: int, k: int, n: int) -> bool:
    """Whether Mosaic can tile these extents (all multiples of 128)."""
    return not (m % _LANES or k % _LANES or n % _LANES)


# --------------------------------------------------------------- visits


def _visits(group_sizes, m: int, block_m: int, visit_empty: bool):
    """The visit tables: ``(group of visit v, row tile of visit v, first
    row of each group, end row of each group, number of visits)``.  A
    group visits the tiles its rows touch, in order; an empty group visits
    none, or one (masked to nothing) under ``visit_empty``, which is how
    ``tgmm`` zeroes its matrix.  Entries past the count repeat the last
    group and stay in range; they are never run."""
    g = group_sizes.shape[0]
    tiles = m // block_m
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // block_m, tiles - 1)
    last = jnp.minimum(jnp.maximum(ends - 1, starts) // block_m, tiles - 1)
    count = jnp.where(sizes > 0, last - first + 1, int(visit_empty))
    cum = jnp.cumsum(count)
    v = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    gid = jnp.minimum(jnp.searchsorted(cum, v, side="right"),
                      g - 1).astype(jnp.int32)
    tile = jnp.clip(first[gid] + v - (cum[gid] - count[gid]), 0, tiles - 1)
    return gid, tile.astype(jnp.int32), starts, ends, cum[-1]


def visits(group_sizes, m: int, visit_empty: bool = False):
    """The visit tables of ``group_sizes`` over ``m`` rows at the default
    row tile, for :func:`gmm_walk` (and the row kernels of
    tpudp/ops/expert_rows.py), or with ``visit_empty`` for
    :func:`tgmm_walk`: an expert layer computes them once and every kernel
    of the layer walks under them.  The last entry is the visit count."""
    return _visits(group_sizes, m, row_tile(m), visit_empty)


def visited_rows(group_sizes, m: int, block_m: int | None = None):
    """Rows ``gmm`` runs over at these group sizes (its visits times the
    tile height): what a boundary-crossing tile and a ragged last tile add
    to ``sum(group_sizes)``.  A counter for ``TrainState.obs_moe``."""
    bm = block_m or row_tile(m)
    return _visits(group_sizes, m, bm, False)[4] * bm


def _row_mask(shape, row0, lo, hi):
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= lo) & (rows < hi)


def _params(interpret, semantics):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_BUDGET)}


# One trace and one lowering of each kernel for all the expert layers of a
# model that call it at one shape (PR 27's set-up finding for flash).
_gmm_jit = functools.partial(jax.jit, static_argnames=(
    "transpose_rhs", "block_m", "block_n", "interpret", "zero_tail"))
_tgmm_jit = functools.partial(jax.jit, static_argnames=(
    "out_dtype", "block_m", "block_k", "block_n", "interpret"))


# ------------------------------------------------------------------ gmm


def _gmm_kernel(gid_ref, tile_ref, start_ref, end_ref, lhs_ref, rhs_ref,
                *rest, block_m: int, transpose_rhs: bool):
    out_ref = rest[-1]
    v = pl.program_id(1)
    g = gid_ref[v]
    row0 = tile_ref[v] * block_m
    lo, hi = start_ref[g], end_ref[g]
    acc = lax.dot_general(lhs_ref[...], rhs_ref[0],
                          _NT if transpose_rhs else _NN,
                          preferred_element_type=jnp.float32)
    if len(rest) == 2:  # `plus`: its block is fetched once for all the
        # visits of a tile (the index does not change between them), so
        # every group adds to what `plus` held before the call
        acc = acc + rest[0][...].astype(jnp.float32)
    inside = (lo <= row0) & (row0 + block_m <= hi)

    @pl.when(inside)
    def _whole():
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(inside))
    def _crossed():
        # Groups are contiguous from row 0, so the rows of this tile before
        # `lo` were written by the visits just before this one (the block
        # is still resident); on a tile's first visit there are none.  Rows
        # from `hi` on are zeroed: a later group's visit fills its own, and
        # what no group owns stays zero.
        rows = row0 + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        kept = jnp.where(rows < lo, out_ref[...].astype(jnp.float32), 0.0)
        out_ref[...] = jnp.where((rows >= lo) & (rows < hi), acc,
                                 kept).astype(out_ref.dtype)


@_gmm_jit
def _gmm_impl(lhs, rhs, walk, transpose_rhs, block_m, block_n, interpret,
              zero_tail=True, plus=None):
    """``walk``: :func:`visits` of the group sizes at this ``block_m``.
    Without ``zero_tail`` the tiles past the last visit are left unwritten
    (the expert layer's form: nobody reads them).  ``plus`` ``(M, N)`` is
    added to the product in float32, and gives the result its buffer."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    bm, _, bn = choose_blocks("gmm", m, k, n, lhs.dtype, lhs.dtype)
    bm, bn = block_m or bm, block_n or bn
    gid, tile, starts, ends, count = walk
    rhs_spec = pl.BlockSpec(
        (1, bn, k) if transpose_rhs else (1, k, bn),
        (lambda j, v, gid, *_: (gid[v], j, 0)) if transpose_rhs
        else (lambda j, v, gid, *_: (gid[v], 0, j)))
    out_spec = pl.BlockSpec((bm, bn), lambda j, v, gid, tile, *_:
                            (tile[v], j))
    more = () if plus is None else (plus,)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, block_m=bm,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # columns outermost: the visits of one row tile are then
            # consecutive, so its output block stays resident between them
            grid=(n // bn, count),
            in_specs=[
                pl.BlockSpec((bm, k), lambda j, v, gid, tile, *_:
                             (tile[v], 0)),
                rhs_spec,
            ] + [out_spec] * len(more),
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        input_output_aliases={6: 0} if more else {},
        interpret=interpret,
        name="moe_gmm",
        **_params(interpret, ("parallel", "arbitrary")),
    )(gid, tile, starts, ends, lhs, rhs.astype(lhs.dtype), *more)
    if not zero_tail:
        return out
    # tiles no group visits were never written
    live = jnp.arange(m)[:, None] < ends[-1]
    return jnp.where(live, out, jnp.zeros((), out.dtype))


# ----------------------------------------------------------------- tgmm


def _tgmm_kernel(gid_ref, tile_ref, start_ref, end_ref, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, block_m: int):
    v = pl.program_id(2)
    last_v = pl.num_programs(2) - 1
    g = gid_ref[v]
    row0 = tile_ref[v] * block_m
    lo, hi = start_ref[g], end_ref[g]

    @pl.when((v == 0) | (gid_ref[jnp.maximum(v - 1, 0)] != g))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _add(a, b):
        acc_ref[...] += lax.dot_general(a, b, _TN,
                                        preferred_element_type=jnp.float32)

    inside = (lo <= row0) & (row0 + block_m <= hi)

    @pl.when(inside)
    def _whole():
        _add(lhs_ref[...], rhs_ref[...])

    @pl.when(jnp.logical_not(inside) & (hi > lo))
    def _crossed():
        a, b = lhs_ref[...], rhs_ref[...]
        _add(jnp.where(_row_mask(a.shape, row0, lo, hi), a,
                       jnp.zeros((), a.dtype)),
             jnp.where(_row_mask(b.shape, row0, lo, hi), b,
                       jnp.zeros((), b.dtype)))

    @pl.when((v == last_v) | (gid_ref[jnp.minimum(v + 1, last_v)] != g))
    def _store():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@_tgmm_jit
def _tgmm_impl(lhs, rhs, walk, out_dtype, block_m, block_k, block_n,
               interpret):
    """``walk``: :func:`visits` with ``visit_empty`` at this ``block_m``."""
    m, k = lhs.shape
    n = rhs.shape[1]
    bm, bk, bn = choose_blocks("tgmm", m, k, n, lhs.dtype, out_dtype)
    bm, bk, bn = block_m or bm, block_k or bk, block_n or bn
    gid, tile, starts, ends, count = walk
    groups = starts.shape[0]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, block_m=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // bk, n // bn, count),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, v, gid, tile, *_:
                             (tile[v], i)),
                pl.BlockSpec((bm, bn), lambda i, j, v, gid, tile, *_:
                             (tile[v], j)),
            ],
            out_specs=pl.BlockSpec((1, bk, bn), lambda i, j, v, gid, *_:
                                   (gid[v], i, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        interpret=interpret,
        name="moe_tgmm",
        **_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(gid, tile, starts, ends, lhs, rhs.astype(lhs.dtype))


# ------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, block_m, block_n, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, transpose_rhs, block_m, block_n,
                    interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, transpose_rhs, block_m, block_n,
             interpret):
    bm = block_m or row_tile(lhs.shape[0])
    out = _gmm_impl(lhs, rhs, _visits(group_sizes, lhs.shape[0], bm, False),
                    transpose_rhs, bm, block_n, interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(transpose_rhs, block_m, block_n, interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    m = lhs.shape[0]
    bm = block_m or row_tile(m)
    # dlhs: the same weights, transposed, on the same groups of rows
    dlhs = _gmm_impl(dout, rhs, _visits(group_sizes, m, bm, False),
                     not transpose_rhs, bm, None, interpret)
    # drhs in rhs's own layout and dtype (float32 parameters get the
    # float32 accumulator, not a bf16 rounding of it)
    a, b = (dout, lhs) if transpose_rhs else (lhs, dout)
    drhs = _tgmm_impl(a, b, _visits(group_sizes, m, bm, True),
                      jnp.dtype(rhs.dtype), bm, None, None, interpret)
    return dlhs, drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _check(lhs, rhs_rows, group_sizes, block_m, interpret):
    if lhs.ndim != 2 or group_sizes.ndim != 1:
        raise ValueError(f"expected lhs (M, K) and group_sizes (G,), got "
                         f"{lhs.shape} and {group_sizes.shape}")
    m = lhs.shape[0]
    if rhs_rows is not None and rhs_rows.shape[:1] != (m,):
        raise ValueError(f"row counts differ: {lhs.shape}, {rhs_rows.shape}")
    if block_m is not None and m % block_m:
        raise ValueError(f"{m} rows not divisible by block_m={block_m}")
    if not interpret and m % _LANES:
        raise ValueError(
            f"compiled TPU mode needs a row count that is a multiple of 128 "
            f"(got {m}); use interpret=True or a plain loop over the groups")


def gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, *,
        transpose_rhs: bool = False, block_m: int | None = None,
        block_n: int | None = None,
        interpret: bool | None = None) -> jnp.ndarray:
    """``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``.

    ``lhs`` ``(M, K)`` holds the groups' rows back to back from row 0 in
    group order, ``group_sizes`` ``(G,)`` integers with ``sum <= M``, ``rhs``
    ``(G, K, N)``, or ``(G, N, K)`` under ``transpose_rhs``.  Returns
    ``(M, N)`` in ``lhs``'s dtype; rows from ``sum(group_sizes)`` on are
    zeros and their ``lhs`` is never read.  Differentiable in ``lhs`` and
    ``rhs`` (the latter's gradient in its own dtype, by :func:`tgmm`).
    Blocks default to :func:`choose_blocks`; ``M`` must be divisible by
    ``block_m``, ``N`` by ``block_n``, and compiled mode needs all extents
    to be multiples of 128 (:func:`supported`)."""
    if interpret is None:
        interpret = _interpret_default()
    if rhs.ndim != 3 or rhs.shape[0] != group_sizes.shape[0] \
            or rhs.shape[2 if transpose_rhs else 1] != lhs.shape[1]:
        raise ValueError(f"rhs {rhs.shape} does not match lhs {lhs.shape} "
                         f"and {group_sizes.shape[0]} groups")
    _check(lhs, None, group_sizes, block_m, interpret)
    return _gmm(lhs, rhs, group_sizes, transpose_rhs, block_m, block_n,
                interpret)


def gmm_walk(lhs, rhs, walk, *, transpose_rhs: bool = False, plus=None):
    """:func:`gmm` under tables made once (:func:`visits`), at the default
    blocks, with the tiles past the last visit LEFT UNWRITTEN: the expert
    layer's form, whose every consumer walks under the same tables.
    ``plus`` ``(M, N)``: the product is added to it (float32, one rounding)
    in its own buffer, which is how two data gradients into one buffer sum
    without a pass of their own.  Not differentiable (the layer's VJP
    composes it)."""
    return _gmm_impl(lhs, rhs, walk, transpose_rhs, None, None,
                     _interpret_default(), zero_tail=False, plus=plus)


def tgmm_walk(lhs, rhs, walk, out_dtype):
    """:func:`tgmm` under ``visits(..., visit_empty=True)``."""
    return _tgmm_impl(lhs, rhs, walk, jnp.dtype(out_dtype), None, None, None,
                      _interpret_default())


def tgmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, *,
         out_dtype=jnp.float32, block_m: int | None = None,
         block_k: int | None = None, block_n: int | None = None,
         interpret: bool | None = None) -> jnp.ndarray:
    """``out[g] = lhs[rows of g]ᵀ @ rhs[rows of g]``: ``(M, K)`` and
    ``(M, N)`` in, ``(G, K, N)`` out in ``out_dtype`` from a float32
    accumulator.  Rows past ``sum(group_sizes)`` are never read; an empty
    group's matrix is zeros.  Not differentiable (it is the VJP)."""
    if interpret is None:
        interpret = _interpret_default()
    _check(lhs, rhs, group_sizes, block_m, interpret)
    bm = block_m or row_tile(lhs.shape[0])
    return _tgmm_impl(lhs, rhs, _visits(group_sizes, lhs.shape[0], bm, True),
                      jnp.dtype(out_dtype), bm, block_k, block_n, interpret)
