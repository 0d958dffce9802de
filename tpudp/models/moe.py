"""Mixture-of-Experts MLPs: :class:`MoeMlp` (capacity-based, with its
all-to-all over an ``expert`` axis; described first) and
:class:`DroplessMoe` (sort-based, no capacity, holds a stated share of the
routed experts; at the end of the file).

Beyond-parity capability (SURVEY.md §2.2 lists EP/MoE as absent from the
reference).  Switch-Transformer-style top-1 routing by default, general
top-k (``top_k>=2``, GShard/Mixtral style: choice-major capacity priority,
renormalized combine weights) with a fixed per-expert capacity, so every
shape is static and the whole layer stays jit/MXU friendly: dispatch and
combine are one-hot einsums, expert FFNs run as one ``vmap``-ed batched
matmul over the expert axis.

Expert parallelism is the TPU-native all-to-all pattern: expert weights are
stacked ``(E, ...)`` and sharded over an ``expert`` mesh axis; inside
``shard_map`` each device routes its local tokens to per-expert capacity
slots, one ``lax.all_to_all`` regroups the slots so each device holds the
tokens bound for *its* experts, the FFNs run locally, and the reverse
``all_to_all`` brings results home for the weighted combine.  Without a
bound expert axis the same module runs dense (all experts local) — init and
single-device tests take that path with identical math, which is the oracle
the EP tests compare against.

Capacity overflow drops tokens (the standard Switch behavior): a dropped
token contributes zero from the MoE layer and rides the transformer block's
residual connection unchanged.  Router balance metrics (per-expert load
fraction and the Switch aux loss ``E * sum(f_e * P_e)``) are sown into the
``intermediates`` collection for a trainer to pull and add to its loss.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tpudp.mesh import axis_is_bound as _axis_is_bound


def collect_moe_aux(intermediates) -> jnp.ndarray | float:
    """Mean of every ``moe_aux`` value sown into an ``intermediates``
    collection (0.0 when none).  The single shared harvest used by BOTH the
    default train path (tpudp.train._loss_and_updates) and the EP rung
    (tpudp.parallel.expert) so their objectives can never diverge."""
    auxes = [v for path, v in
             jax.tree_util.tree_flatten_with_path(intermediates)[0]
             if "moe_aux" in jax.tree_util.keystr(path)]
    if not auxes:
        return 0.0
    return sum(auxes) / len(auxes)


class MoeMlp(nn.Module):
    """Drop-in MLP replacement: ``(..., d) -> (..., d)``.

    Attributes:
      num_experts: global expert count E.
      mlp_ratio: hidden width multiplier (f = mlp_ratio * d).
      capacity_factor: per-expert slots = ceil(cf * local_tokens / E).
      expert_axis: mesh axis to shard experts over (None/unbound = dense).
      dtype: compute dtype (params stay fp32, router runs fp32).
    """

    num_experts: int = 8
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    top_k: int = 1
    expert_axis: str | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        d = x.shape[-1]
        f = self.mlp_ratio * d
        e = self.num_experts
        orig_shape = x.shape
        xt = x.reshape(-1, d)
        t = xt.shape[0]

        gate = self.param("gate", nn.initializers.lecun_normal(), (d, e),
                          jnp.float32)
        # Stacked expert FFNs; the leading E axis is what expert parallelism
        # shards.  Inside shard_map the leaves arrive pre-sharded, so the
        # declared shape is the LOCAL expert count (init always runs
        # unbound -> full (E, ...) shapes).
        ep = self.expert_axis is not None and _axis_is_bound(self.expert_axis)
        n = lax.axis_size(self.expert_axis) if ep else 1
        if e % n:
            raise ValueError(
                f"{e} experts not divisible by expert-axis size {n}")
        e_local = e // n
        w1 = self.param("experts_w1", nn.initializers.lecun_normal(),
                        (e_local, d, f), jnp.float32)
        b1 = self.param("experts_b1", nn.initializers.zeros, (e_local, f),
                        jnp.float32)
        w2 = self.param("experts_w2", nn.initializers.lecun_normal(),
                        (e_local, f, d), jnp.float32)
        b2 = self.param("experts_b2", nn.initializers.zeros, (e_local, d),
                        jnp.float32)

        # --- route (fp32 for a stable softmax/top_k) ---
        k = self.top_k
        if not 1 <= k <= e:
            raise ValueError(f"top_k={k} must be in [1, num_experts={e}]")
        logits = xt.astype(jnp.float32) @ gate
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_idx = lax.top_k(probs, k)  # (T, k), best-first
        # Per-choice combine weights: Switch uses the raw router prob for
        # top-1; for k>=2 renormalize over the chosen experts (Mixtral
        # convention) so the combine is a convex mix of expert outputs.
        weights = top_p / top_p.sum(-1, keepdims=True) if k > 1 else top_p
        onehot_k = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (T, k, E)

        # Capacity slots scale with k (k*T total assignments).  Queue
        # priority is choice-major: every token's FIRST choice claims a slot
        # before any second choice does (GShard ordering), so overflow drops
        # lower-ranked assignments first.
        capacity = max(int(math.ceil(self.capacity_factor * t * k / e)), 1)
        flat = onehot_k.transpose(1, 0, 2).reshape(k * t, e)  # choice-major
        position = jnp.cumsum(flat, axis=0) * flat  # 1-based queue slot
        keep = (position > 0) & (position <= capacity)
        slot = jax.nn.one_hot(
            jnp.clip(position.astype(jnp.int32) - 1, 0, capacity - 1),
            capacity, dtype=jnp.float32)
        disp_k = (slot * keep[..., None].astype(jnp.float32)).reshape(
            k, t, e, capacity)
        # A token occupies at most one slot per (choice, expert): summing
        # over choices keeps dispatch one-hot along (E, C).
        dispatch = disp_k.sum(axis=0)  # (T, E, C)
        combine = (disp_k
                   * weights.T[:, :, None, None]).sum(axis=0)  # (T, E, C)

        # balance metrics for an aux loss (Switch: E * sum(f_e * P_e);
        # f_e = fraction of routing assignments to expert e)
        load_fraction = onehot_k.mean(axis=(0, 1))
        self.sow("intermediates", "moe_load", load_fraction)
        self.sow("intermediates", "moe_aux",
                 e * jnp.sum(load_fraction * probs.mean(axis=0)))

        expert_inputs = jnp.einsum(
            "tec,td->ecd", dispatch, xt.astype(jnp.float32)
        ).astype(self.dtype)  # (E, C, d)

        if ep:
            # slots for my experts, gathered from every peer
            expert_inputs = lax.all_to_all(
                expert_inputs, self.expert_axis, split_axis=0, concat_axis=1,
                tiled=True)  # (E_local, C * n, d)

        def ffn(w1_e, b1_e, w2_e, b2_e, xe):
            h = nn.gelu(xe @ w1_e.astype(self.dtype) + b1_e.astype(self.dtype))
            return h @ w2_e.astype(self.dtype) + b2_e.astype(self.dtype)

        expert_outputs = jax.vmap(ffn)(w1, b1, w2, b2, expert_inputs)

        if ep:
            expert_outputs = lax.all_to_all(
                expert_outputs, self.expert_axis, split_axis=1, concat_axis=0,
                tiled=True)  # back to (E, C, d), my tokens' slots

        y = jnp.einsum("ecd,tec->td", expert_outputs.astype(jnp.float32),
                       combine)
        return y.astype(self.dtype).reshape(orig_shape)


# ------------------------------------------------------ dropless experts


def collect_moe_counts(intermediates) -> tuple:
    """Every ``moe_counts`` vector sown into an ``intermediates``
    collection, in layer order (``()`` when none): per expert layer
    ``[assignments, rows computed, load of held expert 0, 1, ...]``, what
    ``TrainState.obs_moe`` is advanced from (tpudp.train)."""
    return tuple(v for path, v in
                 jax.tree_util.tree_flatten_with_path(intermediates)[0]
                 if "moe_counts" in jax.tree_util.keystr(path))


_SCORE_FNS = {"sigmoid": jax.nn.sigmoid}
ROUTE_NAME = "moe_route"  # the expert choice, for jax.checkpoint policies


# The pieces of the sort-based dispatch, each with its own VJP.  They pass
# ``(M, n)`` row buffers whose rows from ``sum(loads)`` on are UNDEFINED
# (tpudp/ops/expert_rows.py): nothing here, forward or backward, reads a
# row tile past the walk or writes one.


@jax.custom_vjp
def _rows_by_expert(x, token_of, plan):
    """``x[token_of]``: each token's row once per assignment, in sorted
    order, for the rows before the absent group.  Its transpose sums a
    token's held rows back (``plan``: ``expert_rows.combine_plan``), so
    neither direction is a scatter-add."""
    from tpudp.ops import expert_rows as er

    return er.gather_rows(x, token_of, plan[2][-1])


def _rows_by_expert_fwd(x, token_of, plan):
    return _rows_by_expert(x, token_of, plan), (token_of, plan)


def _rows_by_expert_bwd(res, d_rows):
    return _rows_to_tokens(d_rows, *res), None, None


_rows_by_expert.defvjp(_rows_by_expert_fwd, _rows_by_expert_bwd)


@jax.custom_vjp
def _rows_to_tokens(rows, token_of, plan):
    """The transpose of :func:`_rows_by_expert`: ``(M, d) -> (T, d)``."""
    from tpudp.ops import expert_rows as er

    return er.combine_rows(rows, plan)


def _rows_to_tokens_fwd(rows, token_of, plan):
    return _rows_to_tokens(rows, token_of, plan), (token_of, plan)


def _rows_to_tokens_bwd(res, d_tokens):
    return _rows_by_expert(d_tokens, *res), None, None


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


@jax.custom_vjp
def _weights_by_row(weights, flat, order, slot_of, total):
    """``weights.reshape(-1)[order]``, each row's combine weight, by the
    sort that made ``order`` (a stable sort of ``flat``; a gather by index
    over ``T x k`` scalars costs ten times the sort on the v5e).  A row from
    ``total`` on hands back an undefined gradient, which its assignment
    does not take."""
    return lax.sort((flat, weights.reshape(-1)), num_keys=1,
                    is_stable=True)[1]


def _weights_by_row_fwd(weights, flat, order, slot_of, total):
    return (_weights_by_row(weights, flat, order, slot_of, total),
            (order, slot_of, total))


def _weights_by_row_bwd(res, d_rows):
    order, slot_of, total = res
    back = lax.sort((order, d_rows), num_keys=1)[1].reshape(slot_of.shape)
    return jnp.where(slot_of < total, back, 0.0), None, None, None, None


_weights_by_row.defvjp(_weights_by_row_fwd, _weights_by_row_bwd)


@jax.custom_vjp
def _expert_ffn(rows, w_rows, w1, w3, w2, walk, walk_t):
    """Every held expert's SwiGLU on its own rows, times the row's combine
    weight: ``(w * silu(rows @ w1[g]) * (rows @ w3[g])) @ w2[g]``, three
    grouped products and one row kernel under ``walk``; ``walk_t`` is the
    weight gradients' (``tgmm``).  The weight goes in before the down
    projection, where the hidden value is float32 and rounded once anyway:
    the products' rows scale, so this is the weighted sum the layer
    returns, and neither direction needs a pass that only weighs."""
    return _expert_ffn_fwd(rows, w_rows, w1, w3, w2, walk, walk_t)[0]


def _expert_ffn_fwd(rows, w_rows, w1, w3, w2, walk, walk_t):
    from tpudp.ops import expert_rows as er
    from tpudp.ops import grouped_matmul as gm

    h1, h3 = gm.gmm_walk(rows, w1, walk), gm.gmm_walk(rows, w3, walk)
    hdn = er.swiglu(h1, h3, w_rows, walk)
    return gm.gmm_walk(hdn, w2, walk), (rows, w_rows, w1, w3, w2, h1, h3,
                                        hdn, walk, walk_t)


def _expert_ffn_bwd(res, d_out):
    from tpudp.ops import expert_rows as er
    from tpudp.ops import grouped_matmul as gm

    rows, w_rows, w1, w3, w2, h1, h3, hdn, walk, walk_t = res
    d_hdn = gm.gmm_walk(d_out, w2, walk, transpose_rhs=True)
    # float32 parameters take the float32 accumulator, not a rounding of it
    d_w2 = gm.tgmm_walk(hdn, d_out, walk_t, w2.dtype)
    # the rows' weights take their gradient here: how the router learns
    d_h1, d_h3, d_w = er.swiglu_bwd(h1, h3, d_hdn, w_rows, walk)
    d_rows = gm.gmm_walk(
        d_h3, w3, walk, transpose_rhs=True,
        plus=gm.gmm_walk(d_h1, w1, walk, transpose_rhs=True))
    return (d_rows, d_w[:, 0], gm.tgmm_walk(rows, d_h1, walk_t, w1.dtype),
            gm.tgmm_walk(rows, d_h3, walk_t, w3.dtype), d_w2, None, None)


_expert_ffn.defvjp(_expert_ffn_fwd, _expert_ffn_bwd)


def dropless_moe(x, gate, w1, w3, w2, *, top_k: int, first_expert: int = 0,
                 expert_bias=None, score_fn: str = "sigmoid",
                 normalize: bool = True, scaling: float = 1.0,
                 impl: str = "gmm", dtype=jnp.float32, live=None):
    """:class:`DroplessMoe`'s layer as a function of its raw parameters:
    the module and the serve engine's raw-param twin
    (``tpudp.models.pangu.block_paged``) both call it, so there is one
    expert layer.  ``x`` ``(..., d)``; ``gate`` ``(d, routed)``; ``w1``,
    ``w3`` ``(held, d, f)`` and ``w2`` ``(held, f, d)`` in any float type
    (they reach the MXU in ``dtype``; the router runs in float32).

    ``live`` ``(rows,)`` bool marks the rows that are real tokens: a
    serving step also carries the rows of inactive slots and of a chunk's
    padding, and those are sent to the ABSENT group (they cost no expert
    a row, return zeros) and are counted nowhere.  ``None``: every row.

    Returns ``(y, chosen, counts)``: the held experts' part of the layer
    in ``dtype`` and ``x``'s shape, the routed-expert ids ``(rows, k)``
    every row chose, and ``[assignments of live rows, rows computed, load
    of held expert 0, 1, ...]`` as float32 (what the module sows as
    ``moe_counts``)."""
    d, k = x.shape[-1], top_k
    g, f, e = w1.shape[0], w1.shape[2], gate.shape[1]
    if not (0 <= first_expert and first_expert + g <= e and 1 <= k <= e):
        raise ValueError(
            f"experts {first_expert}..{first_expert + g - 1} "
            f"and top_k={k} do not fit {e} routed experts")
    if impl not in ("gmm", "dense"):
        raise ValueError(f"unknown moe impl {impl!r}; choose from "
                         "'gmm', 'dense'")
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    # --- route over all the experts, in float32 at full precision: a
    # bf16 pass would move scores by more than neighbours differ
    scores = _SCORE_FNS[score_fn](jnp.dot(
        xt.astype(jnp.float32), gate.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    biased = scores if expert_bias is None \
        else scores + lax.stop_gradient(expert_bias)
    _, chosen = lax.top_k(biased, k)  # (T, k) routed-expert ids
    # A block under remat recomputes its forward pass inside another
    # XLA program, whose rounding can break a near tie the other way:
    # the backward pass would then run other experts than the forward
    # pass did.  Named, so that a remat policy keeps the choice
    # (models/lfm2.py: REMAT_POLICY).
    chosen = checkpoint_name(chosen, ROUTE_NAME)
    # the chosen experts' own scores, by comparison (the same values:
    # one term a sum): a gather by index over T x k scalars costs the
    # v5e 1.3 ms, twice a layer under remat (PERF.md section 6, PR 30)
    weights = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(e),
                                scores[:, None, :], 0.0), axis=-1)
    if normalize:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    weights = weights * scaling
    local = chosen - first_expert
    local = jnp.where((local >= 0) & (local < g), local, g)  # g: absent
    assignments = t * k
    if live is not None:
        local = jnp.where(live.reshape(t, 1), local, g)
        assignments = jnp.sum(live) * k
    loads = jnp.sum(local[..., None] == jnp.arange(g), axis=(0, 1))

    from tpudp.ops import expert_rows as er
    from tpudp.ops import grouped_matmul as gm

    xb = xt.astype(dtype)
    if impl == "gmm" and gm.supported(t * k, d, f):
        # --- sort the assignments by held expert, absent ones last
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)  # row -> assignment
        slot_of = jnp.argsort(order).reshape(t, k)  # assignment -> row
        # --- the walk, once: every kernel from here to `y` runs over
        # the row tiles it lists and no other, and it is what is sown
        walk = gm.visits(loads, t * k)
        total = walk[3][-1]
        plan = er.combine_plan(slot_of, total)
        token_of = order // k
        w_rows = _weights_by_row(weights, flat, order, slot_of, total)
        out = _expert_ffn(_rows_by_expert(xb, token_of, plan), w_rows,
                          w1, w3, w2, walk,
                          gm.visits(loads, t * k, visit_empty=True))
        y = _rows_to_tokens(out, token_of, plan)
        computed = walk[4] * gm.row_tile(t * k)
    else:
        y = jnp.zeros((t, d), jnp.float32)
        for j in range(g):
            w_j = jnp.sum(jnp.where(local == j, weights, 0.0), axis=-1)
            hdn = nn.silu(xb @ w1[j].astype(dtype)) \
                * (xb @ w3[j].astype(dtype))
            y = y + w_j[:, None] * (hdn @ w2[j].astype(dtype))
        computed = g * t
    counts = jnp.concatenate([
        jnp.stack([jnp.asarray(assignments, jnp.float32),
                   jnp.asarray(computed, jnp.float32)]),
        loads.astype(jnp.float32)])
    return y.astype(dtype).reshape(x.shape), chosen, counts


#: What ``Engine.metrics()["stats"]`` counts of the expert layers, in the
#: order the serving twins return them (``forward_paged``'s ``routed``).
SERVE_MOE_COUNTERS = ("moe_rows", "moe_rows_held", "moe_experts_touched",
                      "moe_layer_runs")


def serve_moe_counts(counts: jnp.ndarray) -> jnp.ndarray:
    """:data:`SERVE_MOE_COUNTERS` of one expert layer's run from
    :func:`dropless_moe`'s ``counts``, as int32."""
    loads = counts[2:]
    return jnp.stack([counts[0], jnp.sum(loads), jnp.sum(loads > 0),
                      jnp.ones((), counts.dtype)]).astype(jnp.int32)


def swiglu(p: dict, u: jnp.ndarray, dtype) -> jnp.ndarray:
    """A SwiGLU of raw parameters ``p`` (``w1``, ``w3``, ``w2``, each a
    ``kernel``): ``W_2 (silu(W_1 u) * W_3 u)`` in ``dtype``."""
    def mm(w, x):
        return x.astype(dtype) @ p[w]["kernel"].astype(dtype)

    return mm("w2", nn.silu(mm("w1", u)) * mm("w3", u))


def routed_and_shared(moe: dict, shared: dict, u: jnp.ndarray, **routing):
    """A serving block's expert layer on raw parameters: the held routed
    experts' part (:func:`dropless_moe` of ``moe``'s ``gate``, ``w1``,
    ``w3``, ``w2`` under ``routing``, sigmoid scores) plus the shared
    SwiGLU expert every token takes.  Returns ``(m, (chosen, counts))``,
    the pair what a paged forward appends to its ``routed``."""
    m, chosen, counts = dropless_moe(
        u, moe["gate"], moe["w1"], moe["w3"], moe["w2"], score_fn="sigmoid",
        **routing)
    return m + swiglu(shared, u, routing["dtype"]), (chosen, counts)


def scaled_init(init, scale: float):
    """``init``'s draw times ``scale`` (``init`` itself at 1)."""
    if scale == 1.0:
        return init
    return lambda key, shape, dtype: (init(key, shape, dtype)
                                      * scale).astype(dtype)


class DroplessMoe(nn.Module):
    """One chip's share of a routed SwiGLU expert layer, no token dropped:
    ``(..., d) -> (..., d)``.

    The router scores all ``num_experts_routed`` experts (``score_fn`` of
    its float32 logits), selects ``top_k`` by score plus an optional
    ``expert_bias`` (a parameter that takes no gradient: load is balanced
    by moving it, not by a loss, so nothing is sown as ``moe_aux``), and
    weights the chosen experts by their unbiased scores, normalised over
    the choice when ``normalize`` and times ``scaling``.  This module HOLDS
    the ``num_experts`` experts from ``first_expert`` on and returns their
    part of the result, ``sum_j w_j * expert_j(x)`` over the chosen experts
    it holds; what the others would add is left out (they live on other
    chips; summed over all the shares the parts give the whole layer,
    tests/test_lfm2.py::test_expert_shares_sum_to_the_whole_layer).
    ``num_experts_routed=None`` holds them all.  No exchange runs here and
    no mesh axis is read: on one chip the share is the layer.

    ``impl='gmm'`` is the sort-based dropless dispatch: the ``T x k``
    assignments are ordered by held-expert id with every assignment to an
    absent expert in one trailing group, the tokens' rows are gathered in
    that order into a ``(T x k, d)`` buffer (the worst case, so nothing
    can overflow), the three products run as grouped matmuls with the
    rows' weights applied between them, and each token sums its
    assignments' rows back.  THE INVARIANT of that path: **rows from
    ``sum(loads)`` on are undefined and nobody reads them.**  The visit
    tables (``grouped_matmul.visits``: the row tiles the held experts' rows
    touch, a traced count) are computed once here from ``loads`` and every
    operation between the sort and ``y`` walks under them, forward and
    backward (tpudp/ops/grouped_matmul.py, tpudp/ops/expert_rows.py), so
    the layer costs what the rows it owns cost: all ``T x k`` of them on
    a chip that holds every expert or when every token picks held experts,
    nothing when no row is owned.  The bound is what ``moe_counts`` sows as
    its second entry.  Only the sort's own ``(T x k,)`` index vectors and
    the ``(T, d)`` token-side tensors are touched whole.
    ``impl='dense'`` is a masked loop over the held experts in plain XLA,
    every expert over every token.  The layer takes it by itself, silently,
    where Mosaic cannot tile the shape (``grouped_matmul.supported``: the
    16-token init trace, tiny CPU sizes), as ops/attention.py falls back
    from flash; the option exists only so that tests and ``chip_smoke.py``
    can run the loop at a shape the kernels take, as their reference.

    The layer itself is :func:`dropless_moe`, a function of the raw
    parameters; this module declares them (in ``param_dtype``; the
    selection bias always float32) and sows what the function returns.
    """

    num_experts: int  # held here
    hidden: int
    top_k: int
    num_experts_routed: int | None = None
    first_expert: int = 0
    score_fn: str = "sigmoid"
    selection_bias: bool = False
    normalize: bool = True
    scaling: float = 1.0
    impl: str = "gmm"  # 'gmm' | 'dense'
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32  # what `init` makes the leaves in
    down_init_scale: float = 1.0  # the experts' `w2` start at this x lecun

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        d, f, g = x.shape[-1], self.hidden, self.num_experts
        e = self.num_experts_routed or g
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        pd = self.param_dtype
        gate = self.param("gate", nn.initializers.lecun_normal(), (d, e), pd)
        w1 = self.param("w1", stacked, (g, d, f), pd)
        w3 = self.param("w3", stacked, (g, d, f), pd)
        w2 = self.param("w2", scaled_init(stacked, self.down_init_scale),
                        (g, f, d), pd)
        bias = self.param("expert_bias", nn.initializers.zeros, (e,),
                          jnp.float32) if self.selection_bias else None
        y, chosen, counts = dropless_moe(
            x, gate, w1, w3, w2, top_k=self.top_k,
            first_expert=self.first_expert, expert_bias=bias,
            score_fn=self.score_fn, normalize=self.normalize,
            scaling=self.scaling, impl=self.impl, dtype=self.dtype)
        self.sow("intermediates", "moe_chosen", chosen)
        self.sow("intermediates", "moe_counts", counts)
        return y
