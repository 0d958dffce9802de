"""Grouped matmul kernels (tpudp/ops/grouped_matmul.py) against a per-group
loop: forward, transposed rhs, the weight-gradient kernel and the custom
VJP, in Pallas interpret mode on the CPU.  Interpret mode checks the
kernels' arithmetic and their visit tables; that Mosaic takes them at the
expert layer's real shapes is tests/test_tpu_aot_compile.py's job."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.ops.grouped_matmul import (_VMEM_BUDGET, choose_blocks, gmm,
                                      supported, tgmm, visited_rows,
                                      vmem_bytes)

M, K, N, G, BM = 512, 128, 256, 4, 128
# group sizes over 512 rows in tiles of 128
SIZES = {
    "even": [128, 128, 128, 128],
    "skewed": [300, 12, 50, 100],  # boundaries inside tiles, rows left over
    "an_empty_group": [200, 0, 112, 200],
    "not_a_multiple_of_the_tile": [5, 7, 130, 3],
    "all_to_one": [0, 512, 0, 0],
    "nothing_held": [0, 0, 0, 0],
    "a_tile_three_groups_share": [120, 3, 2, 200],
}


def _operands(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (M, K), dtype),
            jax.random.normal(ks[1], (G, K, N), dtype),
            jax.random.normal(ks[2], (M, N), dtype))


def _loop_gmm(lhs, rhs, sizes, transpose_rhs=False):
    """Each group's rows times its own matrix, rows past the last: zero."""
    out = jnp.zeros((lhs.shape[0], rhs.shape[1 if transpose_rhs else 2]),
                    jnp.float32)
    start = 0
    for g, n in enumerate(sizes):
        w = rhs[g].T if transpose_rhs else rhs[g]
        out = out.at[start:start + n].set(
            lhs[start:start + n].astype(jnp.float32) @ w.astype(jnp.float32))
        start += n
    return out


def _loop_tgmm(lhs, rhs, sizes):
    out, start = [], 0
    for n in sizes:
        out.append(lhs[start:start + n].astype(jnp.float32).T
                   @ rhs[start:start + n].astype(jnp.float32))
        start += n
    return jnp.stack(out)


@pytest.mark.parametrize("case", sorted(SIZES))
def test_gmm_matches_a_per_group_loop(case):
    lhs, rhs, _ = _operands()
    sizes = SIZES[case]
    out = gmm(lhs, rhs, jnp.asarray(sizes, jnp.int32), block_m=BM, block_n=128)
    # float32 operands, one block of K: the same sums in another order
    np.testing.assert_allclose(out, _loop_gmm(lhs, rhs, sizes), atol=2e-5)


@pytest.mark.parametrize("case", sorted(SIZES))
def test_gmm_with_transposed_rhs_matches(case):
    lhs, rhs, _ = _operands(1)
    sizes = SIZES[case]
    out = gmm(lhs, rhs.transpose(0, 2, 1), jnp.asarray(sizes, jnp.int32),
              transpose_rhs=True, block_m=BM)
    np.testing.assert_allclose(out, _loop_gmm(lhs, rhs, sizes), atol=2e-5)


@pytest.mark.parametrize("case", sorted(SIZES))
def test_tgmm_matches_a_per_group_loop(case):
    lhs, _, rhs = _operands(2)
    sizes = SIZES[case]
    out = tgmm(lhs, rhs, jnp.asarray(sizes, jnp.int32), block_m=BM,
               block_k=128, block_n=128)
    # sums of up to 512 float32 products of unit normals
    np.testing.assert_allclose(out, _loop_tgmm(lhs, rhs, sizes), atol=2e-4)


@pytest.mark.parametrize("transpose_rhs", [False, True])
@pytest.mark.parametrize("case", ["skewed", "an_empty_group", "all_to_one"])
def test_vjp_matches_the_loops_gradients(case, transpose_rhs):
    lhs, rhs, cot = _operands(3)
    sizes = SIZES[case]
    gs = jnp.asarray(sizes, jnp.int32)
    stored = rhs.transpose(0, 2, 1) if transpose_rhs else rhs

    def ours(a, b):
        return jnp.sum(gmm(a, b, gs, transpose_rhs=transpose_rhs,
                           block_m=BM) * cot)

    def loop(a, b):
        return jnp.sum(_loop_gmm(a, b, sizes, transpose_rhs) * cot)

    got = jax.grad(ours, argnums=(0, 1))(lhs, stored)
    want = jax.grad(loop, argnums=(0, 1))(lhs, stored)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)
    np.testing.assert_allclose(got[1], want[1], atol=2e-4)
    # rows no group owns take no gradient; an empty group's matrix none
    assert not np.any(np.asarray(got[0])[sum(sizes):])
    for g, n in enumerate(sizes):
        assert n or not np.any(np.asarray(got[1])[g])


def test_rows_past_the_last_group_are_never_read_and_come_back_zero():
    """NaN in every row that belongs to no group, in the last visited tile
    and in the tiles never visited: no output of either kernel moves."""
    lhs, rhs, rhs2 = _operands(4)
    sizes = SIZES["skewed"]  # 462 rows owned, 50 not
    gs = jnp.asarray(sizes, jnp.int32)
    owned = sum(sizes)
    poison = lambda a: a.at[owned:].set(jnp.nan)  # noqa: E731
    out = gmm(poison(lhs), rhs, gs, block_m=BM)
    np.testing.assert_array_equal(out, gmm(lhs, rhs, gs, block_m=BM))
    assert not np.any(np.asarray(out)[owned:])
    np.testing.assert_array_equal(
        tgmm(poison(lhs), poison(rhs2), gs, block_m=BM),
        tgmm(lhs, rhs2, gs, block_m=BM))


def test_bf16_operands_accumulate_in_float32():
    lhs, rhs, rhs2 = _operands(5, jnp.bfloat16)
    sizes = SIZES["skewed"]
    gs = jnp.asarray(sizes, jnp.int32)
    out = gmm(lhs, rhs, gs, block_m=BM)
    assert out.dtype == jnp.bfloat16
    # one bf16 rounding of sums of 128 products of unit normals (|x| up to
    # ~40): half an ulp at 32 is 0.125
    np.testing.assert_allclose(out.astype(jnp.float32),
                               _loop_gmm(lhs, rhs, sizes), atol=0.13)
    # float32 parameters: rhs is rounded to lhs's dtype for the MXU, and
    # its gradient comes back float32 from the float32 accumulator
    rhs32 = rhs.astype(jnp.float32)
    np.testing.assert_array_equal(gmm(lhs, rhs32, gs, block_m=BM), out)
    d_rhs = jax.grad(lambda b: jnp.sum(gmm(lhs, b, gs, block_m=BM)
                                       .astype(jnp.float32)))(rhs32)
    assert d_rhs.dtype == jnp.float32
    want = _loop_tgmm(lhs, jnp.ones((M, N), jnp.bfloat16), sizes)
    np.testing.assert_allclose(d_rhs, want, atol=1e-3)
    out = tgmm(lhs, rhs2, gs, block_m=BM)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, _loop_tgmm(lhs, rhs2, sizes), atol=1e-3)


# every (rows, K, N) the LFM2 expert layer passes: the cell (16,384 tokens
# x top-4; the up, down and both data-gradient products), one sequence of
# it (the correctness check), and the rehearsal's
MODEL_SHAPES = [(65536, 2048, 1792), (65536, 1792, 2048),
                (32768, 2048, 1792), (32768, 1792, 2048),
                (1024, 128, 128)]


@pytest.mark.parametrize("kernel, out_dtype", [("gmm", jnp.bfloat16),
                                               ("tgmm", jnp.float32)])
@pytest.mark.parametrize("m, k, n", MODEL_SHAPES)
def test_chosen_blocks_are_legal_at_the_models_shapes(kernel, out_dtype, m, k,
                                                      n):
    assert supported(m, k, n)
    bm, bk, bn = choose_blocks(kernel, m, k, n, jnp.bfloat16, out_dtype)
    for extent, block in ((m, bm), (k, bk), (n, bn)):
        assert extent % block == 0 and block % 128 == 0, (extent, block)
    assert kernel == "tgmm" or bk == k  # gmm holds K whole
    assert vmem_bytes(kernel, bm, bk, bn, jnp.bfloat16,
                      out_dtype) <= _VMEM_BUDGET


def test_visited_rows_counts_boundary_tiles_twice():
    gs = lambda s: jnp.asarray(s, jnp.int32)  # noqa: E731
    assert int(visited_rows(gs(SIZES["even"]), M, BM)) == 512
    # [0,300) 3 tiles, [300,312) 1, [312,362) 1, [362,462) 2
    assert int(visited_rows(gs(SIZES["skewed"]), M, BM)) == 7 * 128
    assert int(visited_rows(gs(SIZES["nothing_held"]), M, BM)) == 0


def test_bad_shapes_are_refused():
    lhs, rhs, _ = _operands()
    gs = jnp.asarray(SIZES["even"], jnp.int32)
    with pytest.raises(ValueError, match="does not match"):
        gmm(lhs, rhs[:, :64], gs)
    with pytest.raises(ValueError, match="not divisible by block_m"):
        gmm(lhs, rhs, gs, block_m=96)
    with pytest.raises(ValueError, match="multiple of 128"):
        gmm(lhs[:100], rhs, gs, interpret=False)
    assert not supported(100, 128, 128)
