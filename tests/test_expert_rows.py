"""The expert layer's row operations (tpudp/ops/expert_rows.py) and the
VJPs that compose them (tpudp/models/moe.py) against their plain
``jax.numpy`` forms, in Pallas interpret mode on the CPU.

THE INVARIANT under test: rows from ``sum(loads)`` on are undefined and
nobody reads them.  Interpret mode leaves what a kernel does not write NaN
(``test_what_no_kernel_writes_is_nan_here`` holds it to that), so a result
that is finite and equal to the plain form's has read no such row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models import moe
from tpudp.models.moe import DroplessMoe
from tpudp.ops import expert_rows as er
from tpudp.ops import grouped_matmul as gm

T, K, D, F, G = 256, 4, 128, 256, 4
M = T * K  # 1,024 rows in four tiles of 256
# rows the four held experts own, of 1,024
LOADS = {
    "an_empty_group": [200, 0, 112, 100],
    "a_boundary_inside_a_tile": [250, 12, 50, 100],
    "every_row_owned": [256, 256, 256, 256],
    "no_row_owned": [0, 0, 0, 0],
    "one_row": [0, 0, 1, 0],
}


def _routing(case, seed=0):
    """A routing with these loads: ``(flat held-expert id of every
    assignment (G = absent), order, slot_of (T, K), token_of, walk, plan)``;
    a token's K assignments go to different groups, as top-k's do."""
    loads = LOADS[case]
    rng = np.random.default_rng(seed)
    flat = np.full((T, K), G, np.int32)
    for g, n in enumerate(loads):  # expert g takes n tokens with a free slot
        free = np.flatnonzero((flat == G).any(axis=1) & (flat != g).all(1))
        for t in rng.choice(free, size=n, replace=False):
            flat[t, np.flatnonzero(flat[t] == G)[0]] = g
    for row in flat:
        rng.shuffle(row)
    flat = jnp.asarray(flat.reshape(-1))
    order = jnp.argsort(flat, stable=True)
    slot_of = jnp.argsort(order).reshape(T, K)
    walk = gm.visits(jnp.asarray(loads, jnp.int32), M)
    return (flat, order, slot_of, order // K, walk,
            er.combine_plan(slot_of, walk[3][-1]))


def _nan_tail(a, total):
    return a.at[total:].set(jnp.nan)


def _rand(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def _head(a, total):
    return np.asarray(a)[:total]


def test_what_no_kernel_writes_is_nan_here():
    """The premise of every test below."""
    _, _, _, token_of, walk, _ = _routing("an_empty_group")
    total = int(walk[3][-1])
    tail = 512  # the walk ends in the tile of rows 256..511
    h = gm.gmm_walk(_rand(0, M, D), _rand(1, G, D, F), walk)
    rows = er.gather_rows(_rand(2, T, D), token_of, walk[3][-1], 128)
    act = er.swiglu(h, h, jnp.ones((M,)), walk)
    for a in (h, rows, act):
        assert np.isnan(np.asarray(a)[tail:]).all()
        assert np.isfinite(_head(a, total)).all()


@pytest.mark.parametrize("case", sorted(LOADS))
def test_swiglu_and_its_gradients_match_the_plain_form(case):
    walk = _routing(case)[4]
    total = int(walk[3][-1])
    h1, h3, u = (_nan_tail(_rand(i, M, F), total) for i in range(3))
    w = _nan_tail(jax.random.uniform(jax.random.PRNGKey(3), (M,)), total)

    def plain(a, b, c):
        return c[:, None] * jax.nn.silu(a) * b

    np.testing.assert_allclose(_head(er.swiglu(h1, h3, w, walk), total),
                               _head(plain(h1, h3, w), total), atol=1e-6)
    want = jax.vjp(plain, h1[:total], h3[:total], w[:total])[1](u[:total])
    d_h1, d_h3, d_w = er.swiglu_bwd(h1, h3, u, w, walk)
    for got, ref in zip((d_h1, d_h3, d_w[:, 0]), want):
        np.testing.assert_allclose(_head(got, total), ref, atol=2e-5)


@pytest.mark.parametrize("case", sorted(LOADS))
def test_gmm_walk_adds_its_product_to_plus(case):
    """The second data gradient lands on the first: every group's rows of
    a tile that groups share take ``plus`` as it was before the call."""
    walk = _routing(case)[4]
    total = int(walk[3][-1])
    lhs, plus = (_nan_tail(_rand(i, M, F), total) for i in range(2))
    rhs = _rand(2, G, F, F)
    got = gm.gmm_walk(lhs, rhs, walk, plus=plus)
    np.testing.assert_allclose(
        _head(got, total),
        _head(gm.gmm(lhs, rhs, jnp.asarray(LOADS[case], jnp.int32))
              + plus, total), atol=1e-5)


@pytest.mark.parametrize("chunk", [128, 4096])
@pytest.mark.parametrize("case", sorted(LOADS))
def test_gather_rows_is_the_gather_of_the_owned_rows(case, chunk):
    _, _, _, token_of, walk, _ = _routing(case)
    total = int(walk[3][-1])
    x = _rand(0, T, D)
    got = er.gather_rows(x, token_of, walk[3][-1], chunk)
    np.testing.assert_array_equal(_head(got, total),
                                  _head(x[token_of], total))
    # and it stops at the chunk that holds the last owned row
    written = -(-total // min(chunk, M)) * min(chunk, M)
    assert np.isnan(np.asarray(got)[written:]).all()


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 0.04)])
@pytest.mark.parametrize("case", sorted(LOADS))
def test_combine_rows_sums_each_tokens_owned_rows(case, dtype, atol):
    _, _, slot_of, _, walk, plan = _routing(case)
    total = int(walk[3][-1])
    rows = _nan_tail(_rand(0, M, D).astype(dtype), total)
    held = np.asarray(slot_of) < total
    mine = np.asarray(rows.astype(jnp.float32))[np.where(held, slot_of, 0)]
    want = np.where(held[..., None], mine, 0.0).sum(axis=1)
    got = er.combine_rows(rows, plan)
    assert got.shape == (T, D) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=atol)


def test_combine_rows_when_every_token_owns_all_its_rows():
    """The worst case the buffers are sized for: K rows a token, so a grid
    step's rows are K times its tokens and its window takes several trips."""
    slot_of = jnp.asarray(np.random.default_rng(0).permutation(M),
                          jnp.int32).reshape(T, K)
    rows = _rand(0, M, D)
    got = er.combine_rows(rows, er.combine_plan(slot_of, jnp.int32(M)))
    np.testing.assert_allclose(got, rows[slot_of].sum(axis=1), atol=1e-5)


@pytest.mark.parametrize("case", sorted(LOADS))
def test_the_gather_pair_is_each_others_transpose(case):
    _, _, slot_of, token_of, walk, plan = _routing(case)
    total = int(walk[3][-1])
    x, cot = _rand(0, T, D), _nan_tail(_rand(1, M, D), total)
    live = (jnp.arange(M) < total)[:, None]

    def plain(v):  # autodiff would turn a NaN it selects away into 0 x NaN
        return jnp.sum(v[token_of] * jnp.where(live, cot, 0.0))

    got = jax.grad(lambda v: jnp.sum(jnp.where(
        live, moe._rows_by_expert(v, token_of, plan) * cot, 0.0)))(x)
    np.testing.assert_allclose(got, jax.grad(plain)(x), atol=1e-5)
    # the other way round: the combine's gradient is the gather
    d_rows = jax.grad(lambda r: jnp.sum(
        moe._rows_to_tokens(r, token_of, plan) * x))(cot)
    np.testing.assert_array_equal(_head(d_rows, total),
                                  _head(x[token_of], total))


@pytest.mark.parametrize("case", sorted(LOADS))
def test_expert_ffn_vjp_matches_autodiff_of_a_loop_over_the_groups(case):
    """Output and all five gradients (rows, the rows' weights, w1, w3, w2)
    with every row past ``total`` NaN going in, forward and backward."""
    _, _, _, _, walk, _ = _routing(case)
    loads, total = LOADS[case], int(walk[3][-1])
    walk_t = gm.visits(jnp.asarray(loads, jnp.int32), M, visit_empty=True)
    rows = _nan_tail(_rand(0, M, D), total)
    w_rows = _nan_tail(jax.random.uniform(jax.random.PRNGKey(1), (M,)), total)
    w1, w3, w2 = _rand(2, G, D, F), _rand(3, G, D, F), _rand(4, G, F, D)
    cot = _nan_tail(_rand(5, M, D), total)
    scale = 1.0 / (D * F) ** 0.5

    def loop(r, w, a, b, c):
        out, start = [], 0
        for g, n in enumerate(loads):
            x = r[start:start + n]
            hdn = w[start:start + n, None] * jax.nn.silu(x @ a[g]) * (x @ b[g])
            out.append(hdn @ c[g])
            start += n
        return jnp.concatenate(out) * scale

    def ours(r, w, a, b, c):
        return moe._expert_ffn(r, w, a, b, c, walk, walk_t) * scale

    out, vjp = jax.vjp(ours, rows, w_rows, w1, w3, w2)
    ref, ref_vjp = jax.vjp(loop, rows[:total], w_rows[:total], w1, w3, w2)
    np.testing.assert_allclose(_head(out, total), ref, atol=2e-4)
    got, want = vjp(cot), ref_vjp(cot[:total])
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(_head(a, total), b, atol=2e-4)
    for a, b in zip(got[2:], want[2:]):  # an empty group's matrix: zeros
        np.testing.assert_allclose(a, b, atol=2e-4)


def _layer(impl, held=4, routed=16, first=0):
    return DroplessMoe(num_experts=held, hidden=F, top_k=K,
                       num_experts_routed=routed, first_expert=first,
                       selection_bias=True, impl=impl)


def _layer_grads(layer, params, x, cot):
    return jax.grad(lambda p, v: jnp.sum(
        layer.apply({"params": p}, v) * cot), argnums=(0, 1))(params, x)


@pytest.mark.parametrize("held, routed, first, bias", [
    (4, 16, 4, 0.0),   # a quarter of the rows owned, the rest tail
    (4, 4, 0, 0.0),    # a chip that holds every expert: no tail at all
    (4, 16, 8, -9.0),  # no token picks a held expert: the bound is 0
    (4, 16, 8, 9.0),   # every token picks all four held experts
])
def test_tail_poison_the_layer_equals_the_dense_loop(held, routed, first,
                                                     bias):
    """``DroplessMoe`` through the kernels against ``impl='dense'`` on 512
    tokens (2,048 rows in eight tiles): the output, the input's gradient
    and the gradients of ``gate`` (reached only through the rows' weights),
    ``w1``, ``w3`` and ``w2`` are finite and equal, while every buffer
    between the sort and the combine holds NaN from ``sum(loads)``'s tile
    on (``test_what_no_kernel_writes_is_nan_here``)."""
    x = _rand(0, 512, D)
    cot = _rand(1, 512, D)
    gmm, dense = (_layer(i, held, routed, first) for i in ("gmm", "dense"))
    params = dense.init(jax.random.PRNGKey(2), x)["params"]
    params["expert_bias"] = jnp.zeros((routed,)).at[
        first:first + held].set(bias)
    assert gm.supported(512 * K, D, F)
    y, sown = gmm.apply({"params": params}, x, mutable=["intermediates"])
    counts = np.asarray(sown["intermediates"]["moe_counts"][0])
    owned = counts[2:].sum()
    assert owned == {0.0: owned, -9.0: 0, 9.0: 2048}[bias]
    assert held < routed or owned == 2048
    # rows walked: the owned ones, rounded up to tiles and boundaries
    assert owned <= counts[1] <= owned + 256 * held
    np.testing.assert_allclose(y, dense.apply({"params": params}, x),
                               atol=2e-5)
    got = _layer_grads(gmm, params, x, cot)
    want = _layer_grads(dense, params, x, cot)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=5e-5)
    if owned:
        assert float(jnp.abs(got[0]["gate"]).max()) > 0
