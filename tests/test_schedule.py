"""MPMD 1F1B schedule (tpudp/parallel/schedule.py): the unrolled per-tick
pipeline must reproduce the single-stage trainer's LOSS trajectory
bit-for-bit at equal global batch across PP x DP geometries — the referee
for the ring-transport / liveness-window / shared-grad-assembly math — and
the in-step sharded optimizer must keep that exactness while physically
sharding momentum 1/DP per replica."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.mesh import make_mesh_nd
from tpudp.models.gpt2 import gpt2_small
from tpudp.parallel.schedule import (TRACE_COUNTS, StagePartition,
                                     make_pipeline_eval_step,
                                     make_pipeline_train_step,
                                     stack_partitioned, unstack_partitioned)
from tpudp.parallel.sync import get_sync
from tpudp.train import _loss_and_updates, init_state, make_optimizer

TINY = dict(vocab_size=64, max_seq_len=32, num_layers=4, num_heads=2,
            d_model=32)


def _data(steps=3, batch=8, t=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TINY["vocab_size"],
                        size=(steps, batch, t)).astype(np.int32)
    return [(jnp.asarray(x), jnp.roll(jnp.asarray(x), -1, axis=1))
            for x in toks]


def _run(pp, dp, micro=2, interleave=1, steps=3, shard_optimizer=True):
    """Build + drive one geometry; returns (losses, params, state, traces)."""
    mesh = make_mesh_nd({"data": dp, "pipe": pp},
                        devices=jax.devices()[: dp * pp])
    model = gpt2_small(**TINY)
    tx = make_optimizer(learning_rate=0.01)
    before = TRACE_COUNTS["pp_1f1b"]
    state, step = make_pipeline_train_step(
        model, tx, mesh, init_state(model, tx, input_shape=(1, 8), seed=0),
        n_microbatches=micro, interleave=interleave, donate=False,
        shard_optimizer=shard_optimizer)
    losses = []
    for x, y in _data(steps=steps):
        state, loss = step(state, x, y)
        losses.append(np.asarray(loss))
    part = StagePartition(TINY["num_layers"], pp, interleave)
    params = unstack_partitioned(jax.device_get(state.params), part)
    return np.array(losses), params, state, TRACE_COUNTS["pp_1f1b"] - before


@pytest.fixture(scope="module")
def baseline():
    """PP=1 DP=1: the single-stage trainer every geometry must match."""
    return _run(1, 1)


@pytest.fixture(scope="module")
def geometries(baseline):
    """The tier-1 PP x DP sweep, sharing one compile per geometry."""
    return {(pp, dp): _run(pp, dp) for pp, dp in [(2, 1), (4, 1), (2, 2)]}


# ---- partition unit tests ------------------------------------------------

def test_stage_partition_layout():
    part = StagePartition(8, 2, interleave=2)
    assert part.chunks == 4 and part.layers_per_chunk == 2
    assert part.chunk_layers(1) == (2, 3)
    assert part.chunk_stage(3) == 1
    assert part.stage_chunks(0) == (0, 2)
    assert part.stage_layers(0) == (0, 1, 4, 5)
    # stage-major stacking: pipe-sharding the leading axis in 2 slices
    # hands stage 0 exactly its chunk-major layers
    assert part.layer_order() == (0, 1, 4, 5, 2, 3, 6, 7)
    assert part.ticks(4) == 4 + 2 * 3
    # interleave=1 stacking is the identity (checkpoint compatible)
    assert StagePartition(8, 4).layer_order() == tuple(range(8))


def test_stage_partition_bubble():
    assert StagePartition(8, 1).bubble_fraction(4) == 0.0
    assert StagePartition(8, 4).bubble_fraction(4) == pytest.approx(3 / 7)
    # interleaving shrinks the bubble: (P-1)/(V*M + P-1)
    assert StagePartition(8, 4, 2).bubble_fraction(4) == pytest.approx(3 / 11)


def test_stage_partition_rejects_indivisible():
    with pytest.raises(ValueError, match="not divisible"):
        StagePartition(6, 4)
    with pytest.raises(ValueError, match="not divisible"):
        StagePartition(8, 2, interleave=3)
    with pytest.raises(ValueError, match=">= 1"):
        StagePartition(8, 0)


def test_stack_unstack_roundtrip_interleaved():
    model = gpt2_small(**TINY)
    params = init_state(model, make_optimizer(), input_shape=(1, 8)).params
    part = StagePartition(TINY["num_layers"], 2, interleave=2)
    back = unstack_partitioned(stack_partitioned(params, part), part)
    jax.tree.map(np.testing.assert_array_equal, params, back)


# ---- trajectory parity ---------------------------------------------------

def _assert_loss_parity(losses, baseline_losses, dp):
    """Pure-PP geometries (DP=1) reproduce the single-stage loss
    trajectory BIT-FOR-BIT.  With DP>1 the same numbers are summed in
    another order — the cross-replica mean re-associates the batch mean
    (PP=1/DP=2 already sits 1 ulp from PP=1/DP=1 at step 0 under jax
    0.9.0's XLA:CPU), and the last-ulp parameter freedom
    test_param_trajectory_within_ulp grants reaches the loss by step 3 —
    so those trajectories are held to 2 float32 ulps (1 measured).
    Until jax 0.9.0 the backend's reduction order happened to make them
    coincide."""
    if dp == 1:
        assert np.array_equal(losses, baseline_losses)
    else:
        np.testing.assert_array_max_ulp(losses, baseline_losses, maxulp=2)


def test_baseline_matches_dense_oracle(baseline):
    """PP=1 (all collectives statically elided) tracks the dense trainer
    to float tolerance — anchors the whole parity chain to the oracle."""
    model = gpt2_small(**TINY)
    tx = make_optimizer(learning_rate=0.01)
    state = init_state(model, tx, input_shape=(1, 8), seed=0)

    @jax.jit
    def ref_step(state, x, y):
        return _loss_and_updates(model, tx, state, x, y, get_sync("none"),
                                 None)

    ref = []
    for x, y in _data():
        state, loss = ref_step(state, x, y)
        ref.append(float(loss))
    np.testing.assert_allclose(baseline[0], ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("pp,dp", [(2, 1), (4, 1), (2, 2)])
def test_loss_trajectory_bitexact(baseline, geometries, pp, dp):
    """The acceptance oracle: the single-stage trainer's loss trajectory
    at equal global batch — bit-exact (np.array_equal, no tolerance) for
    every pure-PP geometry, 2 ulps where DP re-associates the sums
    (_assert_loss_parity)."""
    _assert_loss_parity(geometries[(pp, dp)][0], baseline[0], dp)


@pytest.mark.parametrize("pp,dp", [(2, 1), (4, 1), (2, 2)])
def test_param_trajectory_within_ulp(baseline, geometries, pp, dp):
    """Parameters agree to ~1 ulp (see the module docstring of
    tpudp/parallel/schedule.py for why the last ulp belongs to XLA's
    fusion choices, not the schedule)."""
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-7),
        baseline[1], geometries[(pp, dp)][1])


@pytest.mark.slow
@pytest.mark.parametrize("pp,dp,interleave", [(2, 1, 2), (2, 2, 2),
                                              (4, 2, 1)])
def test_interleaved_and_wide_geometries_bitexact(baseline, pp, dp,
                                                  interleave):
    """Virtual stages (interleave=2: chunks wrap the ring) and the full
    PP4xDP2 8-device mesh keep the same bit-exact loss trajectory."""
    losses, params, _, _ = _run(pp, dp, interleave=interleave)
    _assert_loss_parity(losses, baseline[0], dp)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-7),
        baseline[1], params)


@pytest.mark.slow
def test_unsharded_optimizer_matches(baseline):
    """shard_optimizer=False (plain replicated update) is the same
    trajectory — the reduce-scatter/shard-update/allgather round trip is
    numerically invisible."""
    losses, params, _, _ = _run(2, 2, shard_optimizer=False)
    _assert_loss_parity(losses, baseline[0], 2)


# ---- compile-once + sharding layout -------------------------------------

def test_compiles_once_per_geometry(geometries):
    """Three steps at a fixed geometry trace the 1F1B body exactly once
    (TRACE_COUNTS is the train-side analogue of tpudp.serve's counters)."""
    for geo, (_, _, _, traces) in geometries.items():
        assert traces == 1, f"geometry {geo} traced {traces}x"


def test_block_params_sharded_over_pipe(geometries):
    _, _, state, _ = geometries[(4, 1)]
    qkv = state.params["blocks"]["attn"]["qkv"]["kernel"]
    assert qkv.shape[0] == TINY["num_layers"]
    layer_rows = {s.data.shape[0] for s in qkv.addressable_shards}
    assert layer_rows == {TINY["num_layers"] // 4}


def test_optimizer_state_sharded_per_replica(geometries):
    """In-step ZeRO-1: every params-shaped optimizer leaf lives as flat
    1/DP shards — block leaves additionally split over pipe — so no
    device holds more than 1/(PP*DP) of the momentum for blocks."""
    _, _, state, _ = geometries[(2, 2)]
    leaves = jax.tree_util.tree_flatten_with_path(state.opt_state)[0]
    checked_block = checked_shared = 0
    for path, leaf in leaves:
        keys = jax.tree_util.keystr(path)
        if not hasattr(leaf, "addressable_shards") or leaf.ndim != 1:
            continue
        shard_sizes = {s.data.size for s in leaf.addressable_shards}
        if "blocks" in keys:
            assert shard_sizes == {leaf.size // 4}, keys  # pipe x data
            checked_block += 1
        else:
            assert shard_sizes == {leaf.size // 2}, keys  # data only
            checked_shared += 1
    assert checked_block and checked_shared


def test_rejects_non_dense_blocks():
    model = gpt2_small(**TINY, attn_impl="ring")
    mesh = make_mesh_nd({"data": 1, "pipe": 2}, devices=jax.devices()[:2])
    tx = make_optimizer()
    with pytest.raises(ValueError, match="dense"):
        make_pipeline_train_step(
            model, tx, mesh, init_state(model, tx, input_shape=(1, 8)),
            n_microbatches=2)


# ---- eval twin -----------------------------------------------------------

def test_eval_step_matches_dense_forward(geometries):
    """Forward-only MPMD ticks on the trained pp2dp2 state reproduce the
    dense forward's loss/accuracy totals (Trainer eval contract)."""
    _, params, state, _ = geometries[(2, 2)]
    model = gpt2_small(**TINY)
    mesh = make_mesh_nd({"data": 2, "pipe": 2}, devices=jax.devices()[:4])
    eval_step = make_pipeline_eval_step(model, mesh, state,
                                        n_microbatches=2)
    x, y = _data(steps=1, seed=7)[0]
    w = jnp.ones((x.shape[0],), jnp.float32)
    loss_sum, correct, count = eval_step(state, x, y, w)

    from tpudp.models.gpt2 import Block, embed_tokens, lm_head
    import optax
    cfg = model.config
    h = embed_tokens(cfg, params, x)
    for i in range(cfg.num_layers):
        h = Block(cfg).apply({"params": params[f"h_{i}"]}, h)
    logits = lm_head(cfg, params, h)
    per = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    np.testing.assert_allclose(float(loss_sum), float(per.sum()),
                               rtol=1e-5)
    assert int(count) == x.size
    np.testing.assert_allclose(
        int(correct), int((jnp.argmax(logits, -1) == y).sum()), atol=0)


# ---- stage fault + voted rollback ---------------------------------------

class _TokenLoader:
    """Synthetic LM loader with the framework loader contract."""

    def __init__(self, steps=4, seed=0):
        rng = np.random.default_rng(seed)
        toks = rng.integers(0, TINY["vocab_size"],
                            size=(steps, 8, 16)).astype(np.int32)
        self.batches = [
            (jnp.asarray(x), jnp.roll(jnp.asarray(x), -1, axis=1),
             jnp.ones((8,), jnp.float32))
            for x in toks
        ]

    def set_epoch(self, epoch):
        pass

    def __iter__(self):
        return iter(self.batches)

    def __len__(self):
        return len(self.batches)


def _fit_pp_mpmd(tmp_path, tag, hook=None):
    from tpudp.resilience import ResiliencePolicy
    from tpudp.train import Trainer

    mesh = make_mesh_nd({"data": 2, "pipe": 2}, devices=jax.devices()[:4])
    trainer = Trainer(
        gpt2_small(**TINY), mesh, strategy="pp",
        strategy_options={"n_microbatches": 2, "schedule": "1f1b_mpmd"},
        input_shape=(1, 16), learning_rate=0.01, log_every=2,
        log_fn=lambda s: None, seed=0, step_fault_hook=hook)
    pol = ResiliencePolicy(checkpoint_dir=str(tmp_path / tag))
    trainer.fit(_TokenLoader(), epochs=2, resilience=pol)
    part = StagePartition(TINY["num_layers"], 2)
    return trainer, unstack_partitioned(
        jax.device_get(trainer.state.params), part)


@pytest.mark.slow
def test_stage_fault_voted_rollback_bit_exact(tmp_path):
    """A fault raised inside a pipeline step takes the supervisor's
    existing voted recovery path (single-host vote = identity): restore
    the per-stage shards from the global-slice manifest, replay, and land
    bit-identical to the uninterrupted PP run — and within 1 ulp of the
    single-stage trainer (the step-level parity tests pin the rest)."""
    from tpudp.training_faults import RaisingStep

    clean, clean_params = _fit_pp_mpmd(tmp_path, "clean")
    faulted, faulted_params = _fit_pp_mpmd(tmp_path, "fault",
                                           hook=RaisingStep(fail_at={5}))
    assert faulted.stats["step_retries"] == 1
    assert any(e["kind"] == "step_retry" for e in faulted.stats["events"])
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        clean_params, faulted_params)
