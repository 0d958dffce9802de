"""Power retention: linear attention of degree 2 with a gate, in the
three forms a server needs (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239).

For query head ``i`` of the group of KV head ``h`` (``i // (H / KV)``),
with ``G_t = sum_{r <= t} log g_r`` the KV head's cumulative log-gate:

    A[t, s] = (q_t . k_s / sqrt(dh)) ** 2 * exp(G_t - G_s)      s <= t
    y_t     = sum_s A[t, s] v_s / (sum_s A[t, s] + eps)

The recurrent form computes the same quantity from a state that does not
grow with the context.  With ``phi(x)`` the symmetric degree-2 tensor
power of ``x / dh ** (1/4)`` (``D = dh (dh + 1) / 2`` entries, so that
``phi(q) . phi(k) = (q . k / sqrt(dh)) ** 2``)::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      (D, dh)
    z_t = g_t z_{t-1} + phi(k_t)            (D,)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

``phi`` orders the ``D`` products by cyclic offset, the order a kernel
builds them in with lane rotations: for ``o = 0 .. dh/2 - 1`` a segment of
``dh`` entries ``c_o x_a x_{(a + o) mod dh}`` (``c_0 = 1``, else
``sqrt 2``), then ``sqrt 2 x_a x_{a + dh/2}`` for ``a < dh/2``.  Every
unordered pair appears once.

Three forms, each a function of its inputs alone:

  * :func:`retention_reference` — the quadratic form, float32.
  * :func:`chunk_attend` / :func:`chunk_fold` — the chunked form: a chunk's
    rows attend to each other quadratically and to the state the chunk
    starts from through ``phi``; the chunk's rows are then folded into the
    state.  :func:`retention_chunked` runs a whole sequence that way from
    zero state; the serve engine's prefill runs one chunk of a slot.
  * :func:`retention_decode` — one new token a slot: the decayed state plus
    a TAIL of fewer than ``C`` unfolded K/V rows in the quadratic form, the
    tail folded into the state once every ``C`` positions (the caller's
    :func:`chunk_fold`).  ``impl='kernel'`` is ONE Mosaic call
    (``retention_decode``) that reads each slot's state, normaliser and
    tail once and serves a KV head's query heads from one read.

The state is float32 (its products run at ``Precision.HIGHEST``); K and V
enter in the compute type.  Rows whose ``k``, ``v`` and ``log g`` are zero
leave the state as it was: a chunk's padding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
SQRT2 = math.sqrt(2.0)


def features(dh: int) -> int:
    """Entries of ``phi`` for head size ``dh``: ``dh (dh + 1) / 2``."""
    return dh * (dh + 1) // 2


def _pairs(dh: int):
    """The two factors' indices of each of ``phi``'s entries, in the
    cyclic-offset order of the module docstring, as numpy arrays."""
    a, half = np.arange(dh), dh // 2
    first = np.concatenate([a] * half + [a[:half]])
    second = np.concatenate([(a + o) % dh for o in range(half)]
                            + [a[:half] + half])
    return first.astype(np.int32), second.astype(np.int32)


def sym_features(x: jnp.ndarray) -> jnp.ndarray:
    """``phi(x)``: ``(..., dh) -> (..., features(dh))`` in float32, in the
    cyclic-offset order of the module docstring.  Each factor is picked by
    a product with a 0/1 matrix (exact at ``HIGHEST``): one operation a
    factor, where a concatenation of ``dh / 2 + 1`` rotated segments made
    XLA write and rematerialise the whole ``(..., D)`` array once a
    segment."""
    x = x.astype(jnp.float32)
    dh = x.shape[-1]
    x = x * dh ** -0.25
    first, second = _pairs(dh)
    row = lax.broadcasted_iota(jnp.int32, (dh, first.size), 0)

    def pick(idx):  # x[..., idx]
        return jnp.dot(x, (row == idx).astype(jnp.float32), precision=HIGHEST)

    coef = jnp.where(first == second, 1.0, SQRT2).astype(jnp.float32)
    return pick(first) * pick(second) * coef


def _power(scores: jnp.ndarray) -> jnp.ndarray:
    """The quadratic form's weight of a score: its square (the degree)."""
    return scores * scores


def retention_reference(q, k, v, log_g, *, eps: float):
    """The quadratic form: ``q`` ``(B, T, H, dh)``, ``k`` and ``v`` ``(B,
    T, KV, dh)``, ``log_g`` ``(B, T, KV)`` -> ``y`` ``(B, T, H, dh)``
    float32.  A ``(B, KV, H/KV, T, T)`` weight tensor: small sizes."""
    f32 = jnp.float32
    b, t, h, dh = q.shape
    kv = k.shape[2]
    qg = q.astype(f32).reshape(b, t, kv, h // kv, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(f32),
                   precision=HIGHEST) / math.sqrt(dh)
    g = jnp.cumsum(log_g.astype(f32), axis=1).transpose(0, 2, 1)  # (b,kv,t)
    seen = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.exp(jnp.where(seen, g[..., :, None] - g[..., None, :], 0.0))
    w = jnp.where(seen, _power(s) * decay[:, :, None], 0.0)
    num = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(f32),
                     precision=HIGHEST)
    den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)[..., None]
    return (num / (den + eps)).reshape(b, t, h, dh)


def chunk_attend(q, k, v, log_g, S, z, *, eps: float):
    """One chunk's outputs: its rows attend to each other in the quadratic
    form and to the state ``(S, z)`` it starts from, decayed by the chunk's
    cumulative gates.  ``q`` ``(B, c, H, dh)``, ``k``, ``v`` ``(B, c, KV,
    dh)``, ``log_g`` ``(B, c, KV)``, ``S`` ``(B, KV, D, dh)``, ``z`` ``(B,
    KV, D)``.  Returns ``(y, G)``: ``y`` ``(B, c, H, dh)`` float32 and the
    chunk's inclusive cumulative log-gates ``(B, c, KV)``, which
    :func:`chunk_fold` takes.  The state is read a KV head at a time, so
    ``phi(q)`` never exists for every head at once."""
    f32 = jnp.float32
    b, c, h, dh = q.shape
    kv = k.shape[2]
    grp = h // kv
    G = jnp.cumsum(log_g.astype(f32), axis=1)
    qg = q.astype(f32).reshape(b, c, kv, grp, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(f32),
                   precision=HIGHEST) / math.sqrt(dh)
    gt = G.transpose(0, 2, 1)  # (b, kv, c)
    seen = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(seen, gt[..., :, None] - gt[..., None, :], 0.0))
    w = jnp.where(seen, _power(s) * decay[:, :, None], 0.0)
    num = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(f32),
                     precision=HIGHEST)
    den = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)  # (b, c, kv, g)

    def read(args):  # one KV head: (b, c, g, dh), (b, D, dh), (b, D)
        qh, sh, zh = args
        phi = sym_features(qh)
        return (jnp.einsum("bcgD,bDe->bcge", phi, sh.astype(f32),
                           precision=HIGHEST),
                jnp.einsum("bcgD,bD->bcg", phi, zh.astype(f32),
                           precision=HIGHEST))

    num_s, den_s = lax.map(read, (jnp.moveaxis(qg, 2, 0),
                                  jnp.moveaxis(S, 1, 0),
                                  jnp.moveaxis(z, 1, 0)))
    num_s = jnp.moveaxis(num_s, 0, 2)  # (b, c, kv, g, dh)
    den_s = jnp.moveaxis(den_s, 0, 2)
    at = jnp.exp(G)[..., None]  # the state's decay to each row
    y = (at[..., None] * num_s + num) / (at * den_s + den + eps)[..., None]
    return y.reshape(b, c, h, dh), G


def chunk_fold(k, v, G, S, z, upto):
    """Fold rows ``s < upto`` of a chunk into the state it started from::

        S' = exp(G_e) S + sum_{s < upto} exp(G_e - G_s) phi(k_s) v_s^T

    with ``G_e = G_{upto - 1}`` (0 when ``upto`` is 0: the state as it
    was).  ``k``, ``v`` ``(N, c, dh)``, ``G`` ``(N, c)`` inclusive
    cumulative log-gates from the chunk's start, ``S`` ``(N, D, dh)``,
    ``z`` ``(N, D)``, ``upto`` a traced scalar; ``N`` is any set of
    (batch, KV head) pairs.  Returns ``(S', z')`` in the state's type."""
    f32 = jnp.float32
    c = k.shape[1]
    row = jnp.arange(c)
    end = jnp.sum(jnp.where(row == upto - 1, G, 0.0), axis=1)  # (N,)
    w = jnp.where(row < upto, jnp.exp(jnp.where(row < upto,
                                                end[:, None] - G, 0.0)), 0.0)
    phi = sym_features(k) * w[..., None]
    keep = jnp.exp(end)
    s_new = keep[:, None, None] * S.astype(f32) + jnp.einsum(
        "ncD,nce->nDe", phi, v.astype(f32), precision=HIGHEST)
    z_new = keep[:, None] * z.astype(f32) + jnp.sum(phi, axis=1)
    return s_new.astype(S.dtype), z_new.astype(z.dtype)


def retention_chunked(q, k, v, log_g, *, chunk: int, eps: float):
    """The whole sequence in chunks of ``chunk`` from zero state (shapes as
    :func:`retention_reference`; ``T`` need not be a multiple: the last
    chunk is padded with rows that leave the state as it was)."""
    b, t, h, dh = q.shape
    kv = k.shape[2]
    n = -(-t // chunk)
    pad = n * chunk - t

    def cut(x):  # (b, t, ...) -> (n, b, chunk, ...), zero rows at the end
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 1, 0)

    D = features(dh)
    S0 = jnp.zeros((b, kv, D, dh), jnp.float32)
    z0 = jnp.zeros((b, kv, D), jnp.float32)

    def step(carry, xs):
        S, z = carry
        qc, kc, vc, gc = xs
        y, G = chunk_attend(qc, kc, vc, gc, S, z, eps=eps)
        flat = (lambda x: jnp.moveaxis(x, 2, 1).reshape(b * kv, chunk,
                                                        *x.shape[3:]))
        S, z = chunk_fold(flat(kc), flat(vc), flat(G[..., None])[..., 0],
                          S.reshape(b * kv, D, dh), z.reshape(b * kv, D),
                          chunk)
        return (S.reshape(b, kv, D, dh), z.reshape(b, kv, D)), y

    _, ys = lax.scan(step, (S0, z0), (cut(q), cut(k), cut(v), cut(log_g)))
    return jnp.moveaxis(ys, 0, 1).reshape(b, n * chunk, h, dh)[:, :t]


# ------------------------------------------------------------ decode


def _interpret_default() -> bool:
    """Interpret mode is the CPU platform's stand-in for Mosaic, and only
    that (as ``paged_attention._interpret_default``)."""
    return jax.default_backend() == "cpu"


def retention_decode(q, S, z, tail_k, tail_v, tail_g, n, *, eps: float,
                     impl: str = "einsum", layer=None,
                     interpret: bool | None = None):
    """One token a slot.  ``q`` ``(b, KV, H/KV, dh)``; ``S`` ``(b, KV, D,
    dh)``, ``z`` ``(b, KV, D)``, ``tail_k``, ``tail_v`` ``(b, KV, C, dh)``,
    ``tail_g`` ``(b, KV, C)`` (cumulative log-gates from the last fold) —
    or, with ``layer`` given, the whole stacks with a leading layer axis,
    of which the call reads layer ``layer`` (the kernel's BlockSpecs pick
    it: no slice of a stack is ever a value).  ``n`` ``(b,)``: the tail
    rows that count, the new token's included; 0 for a slot that is not
    decoding, whose output is zeros and whose state the kernel does not
    read.  Returns ``(b, KV, H/KV, dh)`` float32.

    ``impl='einsum'`` is XLA's contractions; ``'kernel'`` the Mosaic call
    (interpreted on the CPU platform unless ``interpret`` says
    otherwise)."""
    if impl == "kernel":
        if interpret is None:
            interpret = _interpret_default()
        if layer is None:
            S, z, tail_k, tail_v, tail_g = (
                x[None] for x in (S, z, tail_k, tail_v, tail_g))
            layer = 0
        return _decode_kernel_call(q, S, z, tail_k, tail_v, tail_g, n,
                                   layer=layer, eps=eps, interpret=interpret)
    if impl != "einsum":
        raise ValueError(f"retention impl {impl!r}: 'einsum' or 'kernel'")
    if layer is not None:
        S, z, tail_k, tail_v, tail_g = (
            x[layer] for x in (S, z, tail_k, tail_v, tail_g))
    return _decode_einsum(q, S, z, tail_k, tail_v, tail_g, n, eps=eps)


def _decode_einsum(q, S, z, tail_k, tail_v, tail_g, n, *, eps):
    f32 = jnp.float32
    dh, c = q.shape[-1], tail_k.shape[2]
    q = q.astype(f32)
    phi = sym_features(q)  # (b, kv, g, D)
    num_s = jnp.einsum("bkgD,bkDe->bkge", phi, S.astype(f32),
                       precision=HIGHEST)
    den_s = jnp.einsum("bkgD,bkD->bkg", phi, z.astype(f32), precision=HIGHEST)
    sc = jnp.einsum("bkgd,bkcd->bkgc", q, tail_k.astype(f32),
                    precision=HIGHEST) / math.sqrt(dh)
    row = jnp.arange(c)
    valid = row[None, None, :] < n[:, None, None]  # (b, 1, c)
    cur = jnp.sum(jnp.where(row[None, None, :] == n[:, None, None] - 1,
                            tail_g, 0.0), axis=-1, keepdims=True)  # (b,kv,1)
    decay = jnp.where(valid, jnp.exp(jnp.where(valid, cur - tail_g, 0.0)),
                      0.0)
    w = _power(sc) * decay[:, :, None, :]
    num_t = jnp.einsum("bkgc,bkce->bkge", w, tail_v.astype(f32),
                       precision=HIGHEST)
    den_t = jnp.sum(w, axis=-1)
    at = jnp.exp(cur)[..., None]  # (b, kv, 1, 1)
    y = (at * num_s + num_t) / (at[..., 0] * den_s + den_t + eps)[..., None]
    return jnp.where((n > 0)[:, None, None, None], y, 0.0)


# ------------------------------------------------------- Pallas kernel
#
# Grid ``(slot, KV head)``.  A step holds one slot-head's whole state block
# ``(D, dh)`` float32 (4.2 MB at dh 128), the slot's normalisers and
# tail gates for every KV head (one sublane tile of 8 at the published
# 8 KV heads) and the head's tail K and V.  ``phi(q)`` is built in VMEM a
# segment at a time from lane rotations of the group's queries, so a
# segment's ``(heads, dh) x (dh, dh)`` product reads a contiguous
# ``(dh, dh)`` slab of the state.  A slot that is not decoding maps every
# block to the last one fetched, so its grid steps move no bytes.


def _decode_kernel(n_ref, src_ref, q_ref, s_ref, z_ref, k_ref, v_ref, g_ref,
                   o_ref, *, eps: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    slot, head = pl.program_id(0), pl.program_id(1)
    n = n_ref[slot]

    @pl.when(n == 0)
    def _idle():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n > 0)
    def _run():
        q = q_ref[0, 0].astype(f32)  # (g, dh)
        grp, dh = q.shape
        half = dh // 2
        qs = q * dh ** -0.25

        kv = z_ref.shape[1]
        mine = lax.broadcasted_iota(jnp.int32, (kv, 1), 0) == head

        def pick(block):  # this head's row of a (KV, w) block, (1, w)
            return jnp.sum(jnp.where(mine, block.astype(f32), 0.0), axis=0,
                           keepdims=True)

        def zseg(start, width):  # the head's normaliser entries
            return pick(z_ref[0, :, start:start + width])

        acc = jnp.zeros((grp, dh), f32)
        den = jnp.zeros((grp, 1), f32)
        for o in range(half):
            f = qs * (qs if o == 0 else pltpu.roll(qs, dh - o, 1) * SQRT2)
            seg = s_ref[0, 0, o * dh:(o + 1) * dh, :].astype(f32)
            acc += jnp.dot(f, seg, precision=HIGHEST,
                           preferred_element_type=f32)
            den += jnp.sum(f * zseg(o * dh, dh), axis=1,
                           keepdims=True)
        f = qs[:, :half] * qs[:, half:] * SQRT2
        base = half * dh
        acc += jnp.dot(f, s_ref[0, 0, base:base + half, :].astype(f32),
                       precision=HIGHEST, preferred_element_type=f32)
        den += jnp.sum(f * zseg(base, half), axis=1, keepdims=True)

        kt = k_ref[0, 0].astype(f32)  # (C, dh)
        vt = v_ref[0, 0].astype(f32)
        gt = pick(g_ref[0])  # (1, C)
        sc = lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                             precision=HIGHEST,
                             preferred_element_type=f32) / math.sqrt(dh)
        row = lax.broadcasted_iota(jnp.int32, gt.shape, 1)
        valid = row < n
        cur = jnp.sum(jnp.where(row == n - 1, gt, 0.0), axis=1,
                      keepdims=True)  # (1, 1)
        decay = jnp.where(valid, jnp.exp(jnp.where(valid, cur - gt, 0.0)),
                          0.0)
        w = sc * sc * decay  # (g, C)
        num_t = jnp.dot(w, vt, precision=HIGHEST, preferred_element_type=f32)
        den_t = jnp.sum(w, axis=1, keepdims=True)
        at = jnp.exp(cur)
        y = (at * acc + num_t) / (at * den + den_t + eps)
        o_ref[0, 0] = y.astype(o_ref.dtype)


_VMEM_BUDGET = 48 * 1024 * 1024


def _decode_kernel_call(q, S, z, tail_k, tail_v, tail_g, n, *, layer, eps,
                        interpret):
    """:func:`retention_decode` as ONE Mosaic call over the whole stacks,
    ``name="retention_decode"``.  The slots' ``n`` and the slot whose
    blocks each grid step fetches ride as scalar prefetch."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, kv, grp, dh = q.shape
    D, dv = S.shape[-2:]
    c = tail_k.shape[-2]
    n = jnp.asarray(n, jnp.int32)
    active = n > 0
    idx = jnp.arange(b, dtype=jnp.int32)
    # an idle slot fetches what the step before it fetched: the last
    # decoding slot before it (its last head), or the first one
    before = lax.cummax(jnp.where(active, idx, -1))
    src = jnp.where(active, idx,
                    jnp.where(before >= 0, before,
                              jnp.argmax(active).astype(jnp.int32)))

    def head_map(s, h, n_ref, src_ref):
        return (layer, src_ref[s], jnp.where(n_ref[s] > 0, h, kv - 1), 0, 0)

    def slot_map(s, h, n_ref, src_ref):
        return (layer, src_ref[s], 0, 0)

    def out_map(s, h, n_ref, src_ref):
        return (s, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv),
        in_specs=[
            pl.BlockSpec((1, 1, grp, dh), out_map),
            pl.BlockSpec((None, 1, 1, D, dv), head_map),
            pl.BlockSpec((None, 1, kv, D), slot_map),
            pl.BlockSpec((None, 1, 1, c, dh), head_map),
            pl.BlockSpec((None, 1, 1, c, dv), head_map),
            pl.BlockSpec((None, 1, kv, c), slot_map),
        ],
        out_specs=pl.BlockSpec((1, 1, grp, dv), out_map),
    )
    kw = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_BUDGET)}
    return pl.pallas_call(
        functools.partial(_decode_kernel, eps=eps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, grp, dv), jnp.float32),
        interpret=interpret,
        name="retention_decode",
        **kw,
    )(n, src, q.astype(jnp.float32), S, z, tail_k, tail_v, tail_g)
