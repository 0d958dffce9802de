"""The dropless expert layer's row operations: everything that runs between
the sort and the combine beside the grouped products
(tpudp/ops/grouped_matmul.py), bounded by the same walk.

The layer keeps its assignments' rows in ``(M, n)`` buffers sized for the
worst case (``M`` = tokens x top-k) and sorted by held expert, so the rows
from ``total = sum(loads)`` on belong to experts on other chips.  THE
INVARIANT: **those rows are undefined and nobody reads them.**  Every
operation here walks the row tiles that ``grouped_matmul.visits`` lists for
``gmm`` (a traced count, so the other tiles cost nothing) and leaves the
rest of its output unwritten:

  * ``swiglu`` / ``swiglu_bwd``: elementwise Pallas kernels on a grid of
    the walked tiles (``moe_swiglu``, ``moe_swiglu_bwd``), float32 inside,
    one rounding at the store.  ``swiglu`` also applies each row's combine
    weight and ``swiglu_bwd`` returns that weight's gradient, so the
    weighting has no pass of its own (nor has the sum of the two data
    gradients: ``grouped_matmul.gmm_walk(plus=)``).
  * ``gather_rows``: ``x[token_of]`` for the first ``total`` rows, XLA's
    own gather a chunk of rows at a time under a traced trip count, into a
    buffer nobody initialised.  (Mosaic does not take the one-DMA-a-row
    kernel: a one-row slice of a tiled HBM or VMEM ref is refused,
    ``Slice shape along dimension 0 must be aligned to tiling (8)``.)
  * ``combine_rows``: each token's sum of its held rows: the held rows
    brought into token order by that gather, then ``moe_combine``, a
    Pallas kernel over token tiles that adds each token's rows (now next
    to each other) as one 0/1 selection product a window of rows.  A row
    past ``total`` is selected away before it is multiplied.

Interpret mode on the CPU platform, Mosaic elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudp.ops import grouped_matmul as gm

# Rows one trip of `gather_rows` takes.  On the v5e at the LFM2 expert
# layer's shapes (34,800 rows to gather of 131,072; PERF.md section 6,
# PR 30) 1,024 / 2,048 / 4,096 / 8,192 / 16,384 read 1.68 / 1.67 / 1.77 /
# 1.93 / 2.28 ms a gather: a trip is cheap, a chunk past the last owned
# row is gathered for nothing.
_GATHER_CHUNK = 4096


def _map_kernel(*refs, fn, n_in: int):
    outs = fn(*(ref[...] for ref in refs[:n_in]))
    for ref, val in zip(refs[n_in:], outs):
        ref[...] = val.astype(ref.dtype)


def _map_rows(name: str, fn, walk, ins, outs, interpret: bool,
              aliases: dict | None = None):
    """``fn`` over the row tiles the walk reaches (they run from row 0, so
    their count is the grid) of ``ins`` (``(M, n)`` arrays, any widths)
    into ``outs`` (shapes and dtypes).  ``aliases``: input -> output that
    takes its buffer (a tile is read whole before it is written)."""
    bm = gm.row_tile(ins[0].shape[0])
    spec = lambda a: pl.BlockSpec((bm, a.shape[1]),  # noqa: E731
                                  lambda v: (v, 0))
    return pl.pallas_call(
        functools.partial(_map_kernel, fn=fn, n_in=len(ins)),
        grid=((walk[3][-1] + bm - 1) // bm,),
        in_specs=[spec(a) for a in ins], out_specs=[spec(o) for o in outs],
        out_shape=outs, input_output_aliases=aliases or {},
        interpret=interpret, name=name,
        **gm._params(interpret, ("arbitrary",)),
    )(*ins)


def _like(a, dtype=None, width=None):
    return jax.ShapeDtypeStruct((a.shape[0], width or a.shape[1]),
                                dtype or a.dtype)


_f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
# One trace and one lowering of each kernel for all the layers of a model.
_jit = functools.partial(jax.jit, static_argnames=("interpret",))


def _swiglu_bwd_fn(h1, h3, u, w):
    h1, h3, u = _f32(h1), _f32(h3), _f32(u)
    s = jax.nn.sigmoid(h1)
    d = w * u
    return (d * h3 * s * (1.0 + h1 * (1.0 - s)), d * h1 * s,
            jnp.sum(h1 * s * h3 * u, axis=1, keepdims=True))


@_jit
def _swiglu(h1, h3, w_rows, walk, interpret):
    return _map_rows(
        "moe_swiglu", lambda a, b, w: (w * jax.nn.silu(_f32(a)) * _f32(b),),
        walk, (h1, h3, w_rows[:, None]), (_like(h1),), interpret)[0]


@_jit
def _swiglu_bwd(h1, h3, u, w_rows, walk, interpret):
    return _map_rows(
        "moe_swiglu_bwd", _swiglu_bwd_fn, walk, (h1, h3, u, w_rows[:, None]),
        (_like(h1), _like(h3), _like(h1, jnp.float32, 1)), interpret, {2: 0})


def swiglu(h1, h3, w_rows, walk):
    """``w * silu(h1) * h3`` on the walked tiles: ``(M, f)``, ``w_rows``
    ``(M,)`` float32 (a row's combine weight, applied here, where the
    value is float32 anyway, so that no pass of its own applies it)."""
    return _swiglu(h1, h3, w_rows, walk, gm._interpret_default())


def swiglu_bwd(h1, h3, u, w_rows, walk):
    """``(d_h1, d_h3, d_w)`` of :func:`swiglu` for the cotangent ``u`` of
    its result; ``d_w`` ``(M, 1)`` float32, the row sum ``silu(h1) * h3 *
    u``.  ``d_h1`` takes ``u``'s buffer."""
    return _swiglu_bwd(h1, h3, u, w_rows, walk, gm._interpret_default())


# ------------------------------------------------------------ the gathers


def _unwritten(rows: int, after, interpret: bool):
    """``(rows, width of after)`` that no pass has filled: what a bounded
    writer starts from.  It takes ``after`` (and reads nothing of it) so
    that XLA cannot allocate it before its writer's source exists: with no
    operand every such buffer of a step was live from the step's start."""
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        lambda after_ref, out_ref: None, in_specs=[hbm], out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct((rows, after.shape[1]), after.dtype),
        interpret=interpret, name="moe_unwritten")(after)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gather_rows(x, token_of, total, chunk, interpret):
    m = token_of.shape[0]
    c = min(chunk, m)

    def trip(i, rows):
        at = jnp.minimum(i * c, m - c)  # the last chunk of a ragged M
        idx = lax.dynamic_slice(token_of, (at,), (c,))
        return lax.dynamic_update_slice(rows, x[idx], (at, 0))

    return lax.fori_loop(0, (total + c - 1) // c, trip,
                         _unwritten(m, x, interpret))


def gather_rows(x, token_of, total, chunk: int = _GATHER_CHUNK):
    """``x[token_of]`` for rows ``[0, total)`` rounded up to the chunk;
    ``(T, d)`` and ``(M,)`` in, ``(M, d)`` out with the rest unwritten."""
    return _gather_rows(x, token_of, total, chunk, gm._interpret_default())


# Rows one trip of `moe_combine` takes for its (up to) 256 tokens: at an
# even load a step's rows are a few more than its tokens, and a trip starts
# on a multiple of 128 rows, up to 127 before the step's first.
_COMBINE_WINDOW = 384


def combine_tokens(t: int) -> int:
    """Tokens a grid step of :func:`segment_sum` takes: the largest power
    of two from 256 down to 16 that divides ``t``, else all of it."""
    return next((b for b in (256, 128, 64, 32, 16) if t % b == 0), t)


def _combine_kernel(off_ref, tok_hbm, z_hbm, y_ref, z_buf, tok_buf, acc_ref,
                    sems, *, tokens: int, window: int):
    i = pl.program_id(0)
    total = off_ref[pl.num_programs(0)]
    lo, hi = off_ref[i], off_ref[i + 1]
    first = (lo // 128) * 128
    trips = jnp.where(hi > lo, (hi - first + window - 1) // window, 0)
    m = z_hbm.shape[0]

    def window_sum(s):
        """The step's tokens' sums over the rows of its window ``s``."""
        want = first + s * window
        at = pl.multiple_of(jnp.minimum(want, m - window), 128)
        copies = [
            pltpu.make_async_copy(z_hbm.at[pl.ds(at, window)], z_buf,
                                  sems.at[0]),
            pltpu.make_async_copy(tok_hbm.at[:, pl.ds(at, window)], tok_buf,
                                  sems.at[1])]
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

        @pl.when(at + window > total)
        def _undefined_rows():  # 0 x NaN would still poison the product
            rows = at + lax.broadcasted_iota(jnp.int32, z_buf.shape, 0)
            z_buf[...] = jnp.where(rows < total, _f32(z_buf[...]),
                                   0.0).astype(z_buf.dtype)

        # S[t, r] = 1 where window row r is token t's: S @ z adds each
        # token's rows, exactly (1 x a value of z's dtype, float32 sums)
        row = at + lax.broadcasted_iota(jnp.int32, (1, window), 1)
        mine = ((tok_buf[...] - i * tokens
                 == lax.broadcasted_iota(jnp.int32, (tokens, window), 0))
                & (row >= want) & (row < total))
        z = z_buf[...]
        return lax.dot_general(  # selected in 32 bits, as the mask is made
            jnp.where(mine, 1.0, 0.0).astype(z.dtype), z, gm._NN,
            preferred_element_type=jnp.float32,
            precision=(lax.Precision.HIGHEST if z.dtype == jnp.float32
                       else None))

    @pl.when(trips == 0)
    def _no_row():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(trips == 1)  # an even load: no accumulator to fill and read
    def _one_window():
        y_ref[...] = window_sum(0).astype(y_ref.dtype)

    @pl.when(trips > 1)
    def _more():
        acc_ref[...] = window_sum(0)

        def add(s, carry):
            acc_ref[...] += window_sum(s)
            return carry

        lax.fori_loop(1, trips, add, 0)
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _segment_sum(z, tok, offsets, t, interpret):
    m, d = z.shape
    tokens, window = combine_tokens(t), min(_COMBINE_WINDOW, m)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_combine_kernel, tokens=tokens, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t // tokens,),
            in_specs=[hbm, hbm],
            out_specs=pl.BlockSpec((tokens, d), lambda i, off: (i, 0)),
            scratch_shapes=[pltpu.VMEM((window, d), z.dtype),
                            pltpu.VMEM((1, window), jnp.int32),
                            pltpu.VMEM((tokens, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((t, d), z.dtype),
        interpret=interpret, name="moe_combine",
        **gm._params(interpret, ("arbitrary",)),
    )(offsets, tok[None], z)


def segment_sum(z, tok, offsets, t: int):
    """``y[tok[r]] += z[r]`` for the rows ``r`` before ``total``, which lie
    in token order: ``z`` ``(M, d)``, ``tok`` ``(M,)`` rising,
    ``offsets[j]`` the first row of token ``j * combine_tokens(t)`` and its
    last entry ``total``.  ``(t, d)`` out, every token's row written (zeros
    where it has none), summed in float32 and rounded once."""
    return _segment_sum(z, tok, offsets, t, gm._interpret_default())


def combine_plan(slot_of, total):
    """For the held assignments in TOKEN order (``slot_of`` ``(T, k)``, an
    assignment is held when its slot lies before ``total``): ``(the row each
    sits in, its token, the first of them of every token and, last,
    total)``: index vectors, made once a layer for both combines."""
    t, k = slot_of.shape
    held = slot_of < total
    # one sort carries the rows and the places along: a gather by index
    # over T x k scalars costs ten times what the sort does on the v5e
    _, row_of, place = lax.sort(
        (jnp.logical_not(held).reshape(-1).astype(jnp.int32),
         slot_of.reshape(-1), jnp.arange(t * k, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(held.sum(axis=1)).astype(jnp.int32)])
    return row_of, place // k, first


def combine_rows(rows, plan):
    """``y[t] = sum_j rows[slot_of[t, j]]`` over the held assignments,
    ``(M, d) -> (T, d)``: the held rows are brought into token order (the
    bounded gather again) and summed by :func:`segment_sum`.  ``plan``:
    :func:`combine_plan`.  A row from ``total`` on is never read."""
    row_of, tok, first = plan
    t = first.shape[0] - 1
    return segment_sum(gather_rows(rows, row_of, first[-1]), tok,
                       first[::combine_tokens(t)], t)
