"""Pallas TPU flash attention — the framework's owned hot-op kernel.

The reference delegates every op to ATen's C++ kernels (SURVEY.md §2.3);
here the attention hot op is a first-party Pallas kernel instead of an XLA
einsum chain:

  * Blocked online-softmax forward (flash-attention recurrence): the
    ``(t, t)`` score matrix is never materialized — and K/V are BLOCKED
    THROUGH THE GRID, not staged whole into VMEM: the grid is
    ``(batch·head, q_blocks, k_blocks)`` with the online-softmax state
    (running max / denominator / output accumulator) carried across the
    innermost K dimension in VMEM scratch.
  * Custom VJP with the standard two-kernel backward (a dq kernel gridded
    over (q_blocks, k_blocks) and a dk/dv kernel gridded over
    (k_blocks, q_blocks)), recomputing probabilities from the saved
    log-sum-exp rather than storing them — same grid-blocked structure.
    The dk/dv kernel works on the TRANSPOSED score tile (``k·qᵀ``), so the
    saved ``(1, block_q)`` log-sum-exp / delta rows broadcast as they are
    stored and no ``(block_q, block_k)`` tile is ever transposed.
  * Blocks are chosen from the shape (:func:`choose_blocks`): a grid step
    has a fixed cost of a microsecond or so on a v5e whatever it holds (at
    128 x 128 that cost, not the MXU, set the time), so each kernel takes
    the largest multiples of 128 that divide ``t``, stay at or under
    ``_MAX_BLOCK`` a side and keep :func:`vmem_bytes` within
    ``_VMEM_BUDGET``.  The cap bounds a step at any sequence length:
    per-invocation VMEM is O((block_q + block_k)·dh + block_q·block_k),
    never O(t), so the kernel keeps scaling at t = 8k/16k+ where a
    whole-sequence K/V stage would overflow VMEM.  ``block_q``/``block_k``
    override the choice.
  * Every matmul feeds the MXU the INPUT dtype (bf16 operands for a bf16
    model, float32 for float32 inputs) and accumulates in float32.  The
    softmax statistics (running max, denominator, log-sum-exp, delta) and
    the accumulators are float32; ``p`` and ``ds`` are rounded to the input
    dtype only where they enter the second matmul — where the dense path
    (ops/attention.py) rounds its ``probs``.
  * Causal masking (:func:`_for_tile`): a tile wholly above the diagonal
    is skipped (``pl.when``) and its index map is clamped to the block
    already resident, so the skipped step starts no DMA; a tile wholly
    below it runs unmasked; a square tile ON the diagonal runs as
    ``_DIAG_CHUNK``-row slabs that leave out what lies above the diagonal
    (9/16 of a 1,024-wide tile's elements remain), so a large block does
    not pay for the half of its diagonal tile that is masked.
  * Runs in interpret mode on the CPU platform (and only there unless
    ``interpret=True`` is passed), so the same code is unit-testable on the
    CPU simulator mesh (tests/test_flash_attention.py checks fwd and grads
    against a dense oracle).

Layouts: public API takes ``(batch, time, heads, head_dim)`` (the layout the
models use); the kernels run per ``(batch·head)`` with ``(time, head_dim)``
blocks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# The one VMEM budget: what a grid step may hold by `vmem_bytes`' count, and
# the limit Mosaic is given (the v5e's default scoped limit is 16 MiB of its
# 128 MiB; v4/v5p/v6e have at least as much).
_VMEM_BUDGET = 32 * 2**20
# Largest block side the chooser takes.  The standalone sweep on the v5e
# (PERF.md section 6, PR 27) has 1,024 x 1,024 first in all three kernels at
# t = 1,024 and t = 8,192; twice that no longer fits the budget.
_MAX_BLOCK = 1024
# Rows of a slab of a square diagonal tile (same sweep: 128 is first or
# within 6% of it in every kernel at both lengths; 256 and 512 leave 5/8 and
# 3/4 of the tile's elements where 128 leaves 9/16).
_DIAG_CHUNK = 128
_NT = (((1,), (1,)), ((), ()))  # a·bᵀ: contract the last dimension of both
# The forward kernel's two outputs, for jax.checkpoint policies: a policy
# that keeps both leaves a rematerialised backward pass no reason to run
# the forward kernel again (models/lfm2.py: REMAT_POLICY).  Under any other
# policy, or outside a checkpoint, a name lowers to nothing.
OUT_NAME = "flash_out"
LSE_NAME = "flash_lse"


def _interpret_default() -> bool:
    """Interpret mode is the CPU platform's stand-in for Mosaic, and only
    that: every other backend compiles the kernel (or fails loudly) unless
    the caller passes ``interpret=True`` itself."""
    return jax.default_backend() == "cpu"


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """MXU matmul in the operands' dtype, float32 accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _scale_split(dh: int) -> tuple[float, float]:
    """``(q_mult, s_mult)``: a power-of-two softmax scale (head size 64:
    1/8) multiplies the ``(block, dh)`` q tile, exactly in any dtype; any
    other multiplies the float32 scores, so its rounding never depends on
    the input dtype."""
    scale = 1.0 / math.sqrt(dh)
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _mul(x, c: float):
    return x if c == 1.0 else x * c


def _for_tile(causal, qi, ki, bq, bk, tile, *, transposed=False):
    """Run ``tile(rows_q, rows_k, mask)`` over this grid step's
    ``(bq, bk)`` score tile as causality needs it.

    Not at all above the diagonal; whole and unmasked (``mask=None``) below
    it or without ``causal``.  A tile the diagonal crosses runs with the
    iota mask, and when the blocks are square (then ``qi == ki`` and the
    mask is static) it runs as slabs that leave out the part above the
    diagonal: ``_DIAG_CHUNK`` rows of the kernel's OWN state at a time
    (Q rows in the forward and dq kernels, K rows in the transposed dk/dv
    kernel), each against all the other side's rows it can see, so every
    statistic and accumulator row is still updated once a step.  ``mask``
    has the score slab's shape (``transposed``: K rows by Q columns)."""
    whole = slice(None)
    if not causal:
        tile(whole, whole, None)
        return
    runs = ki * bk < (qi + 1) * bq  # some element is unmasked
    crossed = (ki + 1) * bk - 1 > qi * bq  # some element is masked

    @pl.when(runs & jnp.logical_not(crossed))
    def _below():
        tile(whole, whole, None)

    @pl.when(runs & crossed)
    def _on():
        w = min(_DIAG_CHUNK, bq)
        slabs = bq == bk and bq % w == 0
        shape = (w, bq) if slabs else (bk, bq) if transposed else (bq, bk)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # q position minus k position, less the tile's (slab's) offset
        ahead = cols - rows if transposed else rows - cols
        if not slabs:
            tile(whole, whole, ahead >= ki * bk - qi * bq)
            return
        for lo in range(0, bq, w):
            own = slice(lo, lo + w)
            if transposed:  # K rows lo.. against the Q rows from lo on
                tile(slice(lo, bq), own, ahead[:, :bq - lo] >= 0)
            else:  # Q rows lo.. against the K rows up to theirs
                tile(own, slice(0, lo + w), ahead[:, :lo + w] >= -lo)


# ---------------------------------------------------------- block choice


def vmem_bytes(kernel: str, block_q: int, block_k: int, dh: int,
               dtype) -> int:
    """Upper count of the VMEM one grid step of ``kernel`` (``'fwd'``,
    ``'dq'`` or ``'dkv'``) holds: its double-buffered in/out blocks (a head
    size under 128 pads to the 128 lanes, a ``(1, block_q)`` statistics row
    to 8 sublanes), its float32 scratch, and the score-shaped float32 values
    live at once (forward: s, p and the rounded p; backward: s/p, dp, ds and
    the rounded copies)."""
    isz = jnp.dtype(dtype).itemsize
    row = -(-dh // _LANES) * _LANES
    q_blk, k_blk = block_q * row * isz, block_k * row * isz
    stats = 8 * block_q * 4
    tile = block_q * block_k * 4
    if kernel == "fwd":  # q, o | k, v | lse ; acc, m, l
        io = 2 * (2 * q_blk + 2 * k_blk + stats)
        scratch = block_q * row * 4 + 2 * block_q * _LANES * 4
        live = 3
    elif kernel == "dq":  # q, do, dq | k, v | lse, delta ; dq_acc
        io = 2 * (3 * q_blk + 2 * k_blk + 2 * stats)
        scratch = block_q * row * 4
        live = 4
    elif kernel == "dkv":  # q, do | k, v, dk, dv | lse, delta ; dk/dv_acc
        io = 2 * (2 * q_blk + 4 * k_blk + 2 * stats)
        scratch = 2 * block_k * row * 4
        live = 4
    else:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    return io + scratch + live * tile


def choose_blocks(kernel: str, t: int, dh: int, dtype,
                  causal: bool) -> tuple[int, int]:
    """``(block_q, block_k)`` for one kernel from the shape alone.

    Both divide ``t``; when ``t`` is a multiple of 128 both are multiples
    of 128, at most ``_MAX_BLOCK``, and the pair is the one with the most
    work a step (``block_q·block_k``, then the squarest: under ``causal``
    the diagonal tiles of a square pair waste the least) whose
    :func:`vmem_bytes` fits ``_VMEM_BUDGET``.  Any other ``t`` (interpret
    mode only: Mosaic needs the 128-lane alignment) is one whole block.
    ``causal`` is part of the shape a caller states; the rule is the same
    either way (only causal shapes were swept, and squares cost a
    non-causal call nothing)."""
    if t % _LANES:
        return t, t
    sides = [b for b in range(_LANES, min(t, _MAX_BLOCK) + 1, _LANES)
             if t % b == 0]
    fits = [(bq, bk) for bq in sides for bk in sides
            if vmem_bytes(kernel, bq, bk, dh, dtype) <= _VMEM_BUDGET]
    # (128, 128) is ~1 MiB at any head size: `fits` is never empty.
    return max(fits, key=lambda p: (p[0] * p[1], -abs(p[0] - p[1])))


def _blocks(kernel, q, causal, block_q, block_k):
    if block_q is not None and block_k is not None:
        return block_q, block_k
    _, t, dh = q.shape
    bq, bk = choose_blocks(kernel, t, dh, q.dtype, causal)
    return block_q or bq, block_k or bk


# One trace and one lowering of each kernel for all the layers of a model
# that call it at one shape (a 24-layer step otherwise lowers 72 kernels).
_kernel_jit = functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))


def _params(interpret):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BUDGET)}


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, nk: int):
    bq, dh = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_mult, s_mult = _scale_split(dh)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _tile(rq, rk, mask):
        v_blk = v_ref[0, rk]
        s = _mul(_dot(_mul(q_ref[0, rq], q_mult), k_ref[0, rk], _NT), s_mult)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        # A causal row always sees column 0, which the first K block holds:
        # from then on its running max is finite and exp() of a masked
        # score is exactly 0, so p needs no second select.
        m_prev = m_ref[rq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[rq] = l_ref[rq] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[rq] = acc_ref[rq] * alpha + _dot(p.astype(v_blk.dtype),
                                                 v_blk)
        m_ref[rq] = m_new

    _for_tile(causal, qi, ki, bq, bk, _tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l_safe)).reshape(1, bq)


def _kv_spec(block_q, block_k, dh, causal):
    """K/V block of grid step ``(b, i, j)`` in the forward and dq kernels.
    Under causal, past the last block a Q block sees, name that block
    again (resident: no DMA)."""
    def k_block(i, j):
        last = ((i + 1) * block_q - 1) // block_k
        return jnp.minimum(j, last) if causal else j

    return pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, k_block(i, j), 0))


@_kernel_jit
def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret):
    """q,k,v: (bh, t, dh) fp32/bf16 -> (o (bh,t,dh), lse (bh,t) f32)."""
    bh, t, dh = q.shape
    block_q, block_k = _blocks("fwd", q, causal, block_q, block_k)
    nk = t // block_k
    q_spec = pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0))
    kv_spec = _kv_spec(block_q, block_k, dh, causal)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, nk=nk),
        grid=(bh, t // block_q, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
        **_params(interpret),
    )(q, k, v)
    return o, lse.reshape(bh, t)


# --------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, causal: bool, nk: int):
    bq, dh = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_mult, s_mult = _scale_split(dh)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _tile(rq, rk, mask):
        k_blk = k_ref[0, rk]
        s = _mul(_dot(_mul(q_ref[0, rq], q_mult), k_blk, _NT), s_mult)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, rq].reshape(-1, 1))
        dp = _dot(do_ref[0, rq], v_ref[0, rk], _NT)
        ds = p * (dp - delta_ref[0, :, rq].reshape(-1, 1))
        dq_acc[rq] += _dot(ds.astype(k_blk.dtype), k_blk)

    _for_tile(causal, qi, ki, bq, bk, _tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * (q_mult * s_mult)).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool, nq: int):
    """Everything score-shaped here is TRANSPOSED, ``(block_k, block_q)``:
    the statistics rows broadcast over sublanes as stored, and dv / dk are
    plain ``pᵀ·do`` / ``dsᵀ·q`` matmuls with nothing to transpose."""
    bk, dh = k_ref.shape[1], k_ref.shape[2]
    bq = q_ref.shape[1]
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    q_mult, s_mult = _scale_split(dh)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _tile(rq, rk, mask):
        q = _mul(q_ref[0, rq], q_mult)
        do = do_ref[0, rq]
        s_t = _mul(_dot(k_ref[0, rk], q, _NT), s_mult)
        if mask is not None:
            s_t = jnp.where(mask, s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[0, :, rq])
        dv_acc[rk] += _dot(p_t.astype(do.dtype), do)
        dp_t = _dot(v_ref[0, rk], do, _NT)
        ds_t = p_t * (dp_t - delta_ref[0, :, rq])
        # with the scale folded into q, dk = dsᵀ·(q·scale) is complete
        dk_acc[rk] += _dot(ds_t.astype(q.dtype), q)

    _for_tile(causal, qi, ki, bq, bk, _tile, transposed=True)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = _mul(dk_acc[...], s_mult).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@_kernel_jit
def _flash_bwd_dq_impl(q, k, v, do, lse3, delta, causal, block_q, block_k,
                       interpret):
    bh, t, dh = q.shape
    block_q, block_k = _blocks("dq", q, causal, block_q, block_k)
    nk = t // block_k
    q_spec = pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0))
    kv_spec = _kv_spec(block_q, block_k, dh, causal)
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, nk=nk),
        grid=(bh, t // block_q, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **_params(interpret),
    )(q, k, v, do, lse3, delta)


@_kernel_jit
def _flash_bwd_dkv_impl(q, k, v, do, lse3, delta, causal, block_q, block_k,
                        interpret):
    bh, t, dh = q.shape
    block_q, block_k = _blocks("dkv", q, causal, block_q, block_k)
    nq = t // block_q

    def q_block(i, j):
        # Under causal, before the first Q block a K block is seen by,
        # name that block already (it is fetched once, ahead of its use).
        return jnp.maximum(j, (i * block_k) // block_q) if causal else j

    q_spec = pl.BlockSpec((1, block_q, dh),
                          lambda b, i, j: (b, q_block(i, j), 0))
    kv_spec = pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda b, i, j: (b, 0, q_block(i, j)))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, nq=nq),
        grid=(bh, t // block_k, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dh), k.dtype),
            jax.ShapeDtypeStruct((bh, t, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        **_params(interpret),
    )(q, k, v, do, lse3, delta)


def _flash_bwd_impl(q, k, v, o, lse, do, causal, block_q, block_k, interpret):
    bh, t, _ = q.shape
    # delta_i = rowsum(do_i * o_i) — the softmax-jacobian correction term.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, t)
    args = (q, k, v, do, lse.reshape(bh, 1, t), delta, causal, block_q,
            block_k, interpret)
    dq = _flash_bwd_dq_impl(*args)
    dk, dv = _flash_bwd_dkv_impl(*args)
    return dq, dk, dv


# ------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    o, lse = checkpoint_name(o, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, o, lse, do, causal, block_q, block_k,
                           interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blocked flash attention. ``q, k, v``: ``(batch, time, heads, head_dim)``.

    ``block_q``/``block_k`` default to ``None``: each of the three kernels
    (forward, dq, dk/dv) takes the pair :func:`choose_blocks` gives for
    ``(time, head_dim, dtype, causal)`` — legal for any ``time`` the caller
    may pass (divisors of ``time``, multiples of 128 whenever ``time`` is,
    within ``_VMEM_BUDGET`` at any length).  An explicit value overrides the
    choice in all three kernels; ``time`` must be divisible by it (it is
    clamped to ``time`` when longer).

    The dots run in the inputs' dtype with float32 accumulation (bf16 in,
    bf16 MXU operands; float32 in, float32 dots); softmax statistics and
    accumulators are float32.  Differentiable (custom VJP);
    ``interpret=None`` means Pallas interpret mode on the CPU platform (so
    tests work on the CPU simulator) and a Mosaic-compiled kernel on every
    other backend.

    Compiled (TPU) mode requires lane-aligned blocks: ``block_q``/``block_k``
    must be multiples of 128 (Mosaic tiling: the log-sum-exp blocks put
    ``block_q`` in the lane dimension), so ``time`` must be one too.
    Interpret mode has no such limit.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, dh = q.shape
    block_q = None if block_q is None else min(block_q, t)
    block_k = None if block_k is None else min(block_k, t)
    for blk in (block_q or t, block_k or t):
        if t % blk:
            raise ValueError(f"time {t} not divisible by blocks "
                             f"({block_q},{block_k})")
        if not interpret and blk % 128:
            raise ValueError(
                f"compiled TPU mode needs block sizes that are multiples of "
                f"128 (got block_q={block_q}, block_k={block_k}; time={t} — "
                f"for shorter sequences use dense attention or "
                f"interpret=True)")

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, block_q, block_k,
               interpret)
    return o.reshape(b, h, t, dh).transpose(0, 2, 1, 3)
