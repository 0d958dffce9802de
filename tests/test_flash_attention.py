"""Pallas flash attention vs dense oracle — forward and gradients.

Runs in Pallas interpret mode on the CPU simulator (the kernel auto-selects
interpret off-TPU). Interpret mode checks the kernel math, not Mosaic
lowering constraints — the small block sizes used here (64) are
interpret-only; compiled TPU mode enforces 128-multiples and is exercised
by benchmarks/flash_attention_bench.py on real hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.ops.flash_attention import (_VMEM_BUDGET, choose_blocks,
                                       flash_attention, vmem_bytes)
from tpudp.parallel.ring_attention import dense_causal_attention


def _dense(q, k, v, causal):
    b, t, h, dh = q.shape
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def kernel_calls(jaxpr) -> dict:
    """How many ``pallas_call`` equations a program holds under each
    kernel ``name=``, sub-programs included (the printed jaxpr shows a
    sub-program that several equations share once)."""
    calls = {}
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            calls[name] = calls.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for name, n in kernel_calls(sub).items():
                calls[name] = calls.get(name, 0) + n
    return calls


def _rand_qkv(key, b=2, t=256, h=2, dh=32):
    ks = jax.random.split(key, 3)
    shape = (b, t, h, dh)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_matches_ring_oracle():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_dense(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=1, t=128, h=2, dh=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        return jnp.sum(o * jnp.cos(o))  # nonlinear reduction

    def loss_dense(q, k, v):
        o = _dense(q, k, v, causal).astype(q.dtype)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_uneven_blocks():
    # block_q != block_k exercises the causal loop-bound arithmetic
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), t=256)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    out2 = flash_attention(q, k, v, causal=True, block_q=64, block_k=128)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_io():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), t=128, dh=64)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def _loss(attn):
    def loss(q, k, v):
        o = attn(q, k, v).astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o))  # nonlinear reduction
    return loss


@pytest.mark.parametrize("t, dh", [(256, 64), (1024, 64), (384, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_default_blocks_match_dense(dtype, causal, t, dh):
    """No block arguments: the chooser's blocks (one whole tile at 256, the
    slab-cut diagonal tile at 1,024, the iota-masked tile at 384), forward
    and all three gradients, in the model's dtype and in float32."""
    q, k, v = (x.astype(dtype) for x in
               _rand_qkv(jax.random.PRNGKey(5), b=1, t=t, h=2, dh=dh))
    up = [x.astype(jnp.float32) for x in (q, k, v)]
    tol = (dict(rtol=0.05, atol=0.05) if dtype == jnp.bfloat16
           else dict(rtol=5e-4, atol=5e-4))
    fwd_tol = tol if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)

    out = flash_attention(q, k, v, causal=causal)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_dense(*up, causal)), **fwd_tol)
    g_flash = jax.grad(_loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(_loss(lambda q, k, v: _dense(q, k, v, causal)),
                       argnums=(0, 1, 2))(*up)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        assert gf.dtype == dtype
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gd), err_msg=name, **tol)


def test_diagonal_slabs_beside_whole_tiles():
    # 512-wide blocks at t=1024: two slab-cut diagonal tiles, one whole tile
    # below the diagonal and one skipped above it, in all three kernels.
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), b=1, t=1024, h=1, dh=32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=512, block_k=512)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(_dense(q, k, v, True)),
                               rtol=2e-5, atol=2e-5)
    g_flash = jax.grad(_loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(_loss(lambda q, k, v: _dense(q, k, v, True)),
                       argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_chooser_blocks_are_legal(kernel):
    for t, dh, dtype, causal in [
            (128, 64, jnp.bfloat16, True), (256, 64, jnp.bfloat16, False),
            (384, 128, jnp.bfloat16, True), (1024, 64, jnp.bfloat16, True),
            (1152, 64, jnp.float32, True), (8192, 64, jnp.bfloat16, True),
            (16384, 128, jnp.bfloat16, True), (16384, 256, jnp.float32, False),
            (200, 32, jnp.float32, True)]:
        bq, bk = choose_blocks(kernel, t, dh, dtype, causal)
        assert t % bq == 0 and t % bk == 0, (t, bq, bk)
        if t % 128 == 0:
            assert bq % 128 == 0 and bk % 128 == 0, (t, bq, bk)
            # bounded at any length: the kernel never stages a sequence
            assert vmem_bytes(kernel, bq, bk, dh, dtype) <= _VMEM_BUDGET
    # the benchmark cell's shape gets more than one 128x128 tile a step
    assert choose_blocks(kernel, 1024, 64, jnp.bfloat16, True) != (128, 128)
    bq, bk = choose_blocks(kernel, 16384, 128, jnp.bfloat16, True)
    assert max(bq, bk) < 16384
    assert vmem_bytes(kernel, bq, bk, 128, jnp.bfloat16) <= _VMEM_BUDGET


def test_explicit_blocks_override_and_are_checked():
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b=1, t=256, h=1, dh=16)
    ref = _dense(q, k, v, True)
    # one explicit side, the other chosen; a block longer than t clamps
    for kw in (dict(block_q=64), dict(block_k=128), dict(block_q=4096)):
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal=True, **kw)),
            np.asarray(ref), rtol=2e-5, atol=2e-5, err_msg=str(kw))
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, block_q=96)
    with pytest.raises(ValueError, match="multiples of 128"):
        flash_attention(q, k, v, block_q=64, interpret=False)


@pytest.mark.parametrize("remat, forward_calls", [(False, 1), (True, 2)])
def test_checkpoint_names_change_no_program_that_lists_none(remat,
                                                            forward_calls):
    """``_flash_fwd`` names its outputs for remat policies (OUT_NAME,
    LSE_NAME; models/lfm2.py lists them).  A name outside a checkpoint is
    an identity, and ``make_train_step(remat=True)`` is a whole-model
    ``jax.checkpoint`` under the default policy, which keeps nothing: a
    GPT-2 train step holds the calls it held before the names, one
    forward kernel a layer without remat and two with it."""
    from tpudp.models.gpt2 import GPT2, GPT2Config
    from tpudp.train import init_state, make_optimizer, make_train_step

    layers = 2
    model = GPT2(GPT2Config(vocab_size=128, max_seq_len=128,
                            num_layers=layers, num_heads=2, d_model=64,
                            attn_impl="flash"))
    tx = make_optimizer()
    state = init_state(model, tx, input_shape=(1, 128))
    tokens = jnp.zeros((2, 128), jnp.int32)
    program = jax.make_jaxpr(make_train_step(
        model, tx, None, "none", donate=False, remat=remat))(
            state, tokens, tokens)
    assert kernel_calls(program) == {
        "flash_fwd": forward_calls * layers, "flash_bwd_dq": layers,
        "flash_bwd_dkv": layers}
