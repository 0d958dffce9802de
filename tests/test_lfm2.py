"""LFM2-MoE (tpudp/models/lfm2.py, tpudp.models.moe.DroplessMoe) against
the plain float32 reference in perf/families/lfm2_moe.py, on seeded
weights at small sizes: the two operators, logits and gradients, the two
expert-layer implementations, the expert share, and the counters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perf.harness.cells import load_module
from test_flash_attention import kernel_calls
from tpudp.models.lfm2 import (Lfm2, Lfm2Config, QkNormAttention, ShortConv)
from tpudp.models.moe import DroplessMoe

fam = load_module("families", "lfm2_moe")

# One chip's share (experts 4-7 of 16) of a three-layer cut with every kind
# of block (conv + dense SwiGLU, attention + experts, conv + experts): 64
# tokens x top-4 = 256 rows, all extents multiples of 128, so
# moe_impl='gmm' runs the kernels (interpret mode).
CONFIG = dict(
    vocab_size=512, hidden_size=128, intermediate_size=256,
    moe_intermediate_size=128, num_hidden_layers=3, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv"],
    num_attention_heads=4, num_key_value_heads=2, num_experts=4,
    num_experts_per_tok=4, num_experts_routed=16, first_expert=4,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, rope_theta=1000000,
    compute_dtype="float32", train={"moe_impl": "gmm", "remat": True})


def _setup(seed=0, t=64, attn_impl="dense", **overrides):
    config = {**CONFIG, **overrides}
    model = fam.build_model(config, attn_impl=attn_impl)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, config["vocab_size"], (2, t + 1)))
    x, y = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(seed + 1), x)["params"]
    # a selection bias that is not zero, so that it is seen to act
    last = params[f"h_{config['num_hidden_layers'] - 1}"]
    if "moe" in last:
        last["moe"]["expert_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 2), (config["num_experts_routed"],))
    return config, model, params, x, y


def _ref_losses(config):
    return jax.jit(lambda p, x, y: fam.reference_token_losses(p, x, y,
                                                              config))


def _ref_routed(config):
    return jax.jit(lambda p, x, y, chosen: fam.reference_token_losses_routed(
        p, x, y, config, chosen))


def _sys_routed(model):
    # the losses and the choices; the gradient beside them is not computed
    return jax.jit(lambda p, x, y: fam.system_routed(model, p, x, y)[:2])


def _rel(got, want):
    """Largest per-leaf ``|got - want|_2 / |want|_2`` over two trees."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)
                           / (jnp.linalg.norm(b) + 1e-30)), got, want)))


# ------------------------------------------------------------- operators


def test_short_conv_is_the_three_tap_loop_and_causal():
    cfg = Lfm2Config.from_dict(CONFIG)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 24, cfg.hidden_size))
    op = ShortConv(cfg)
    p = op.init(jax.random.PRNGKey(1), u)["params"]
    got = np.asarray(op.apply({"params": p}, u))
    # the equations, a position at a time
    b_, c_, x_ = np.split(np.asarray(u) @ np.asarray(p["in_proj"]["kernel"]),
                          3, axis=-1)
    z = b_ * x_
    w = np.asarray(p["conv_w"])
    conv = np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(3):
            if t - 2 + j >= 0:
                conv[:, t] += w[:, j] * z[:, t - 2 + j]
    want = (c_ * conv) @ np.asarray(p["out_proj"]["kernel"])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(fam._conv_op(p, u, CONFIG), want, atol=1e-5)
    # causal: a change at position 12 moves nothing before it
    moved = np.asarray(op.apply({"params": p}, u.at[:, 12].add(1.0)))
    np.testing.assert_array_equal(moved[:, :12], got[:, :12])
    assert np.abs(moved[:, 12:15] - got[:, 12:15]).max() > 1e-3


@pytest.mark.parametrize("attn_impl, t", [("dense", 24), ("flash", 128)])
def test_qk_norm_rope_gqa_attention_matches_the_reference(attn_impl, t):
    cfg = Lfm2Config.from_dict(CONFIG, attn_impl=attn_impl)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, t, cfg.hidden_size))
    op = QkNormAttention(cfg)
    p = op.init(jax.random.PRNGKey(1), u, jnp.arange(t))["params"]
    # scales that are not one, so that the norms' scales are seen to act
    p["q_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (32,))
    p["k_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(3), (32,))
    got = op.apply({"params": p}, u, jnp.arange(t))
    # float32 both sides: summation order only
    np.testing.assert_allclose(got, fam._attention_op(p, u, CONFIG),
                               atol=2e-5)


# ----------------------------------------------------------- whole model


def test_float32_free_routing_matches_the_reference():
    """Both sides float32 and each routing by itself: on this seed no
    token's 4th and 5th scores are within rounding of each other, so the
    choices are the same and the losses differ by summation order."""
    config, model, params, x, y = _setup()
    with jax.default_matmul_precision("highest"):
        want = _ref_losses(config)(params, x, y)
        got, chosen = _sys_routed(model)(params, x, y)
        _, scores = _ref_routed(config)(params, x, y, chosen)
    np.testing.assert_allclose(got, want, atol=5e-5)
    for s, c in zip(scores, chosen, strict=True):
        assert float(fam.choice_gap(s, c)) <= 0.0  # the reference's top-4

    def mean_loss(fn):
        return jax.jit(jax.grad(lambda p: jnp.mean(fn(p))))(params)

    with jax.default_matmul_precision("highest"):
        g_ref = mean_loss(lambda p: fam.reference_token_losses(p, x, y,
                                                               config))
        g_sys = mean_loss(lambda p: fam.system_token_losses(model, p, x, y))
    bias = lambda g: [g[f"h_{i}"]["moe"].pop("expert_bias")  # noqa: E731
                      for i in (1, 2)]
    # the selection bias takes no gradient in the system; the reference's
    # top-k passes none either
    assert not any(np.any(np.asarray(b)) for b in bias(g_sys) + bias(g_ref))
    # float32 both sides: 1e-4 of each leaf's norm is summation order
    assert _rel(g_sys, g_ref) < 1e-4


def test_bf16_forced_routing_matches_the_reference():
    """The system in bf16 chooses a few experts differently (its scores
    move by about 1e-2).  Given ITS choices the float32 reference agrees
    with it to bf16 rounding, in the losses and in the gradients, and every
    choice is within a tie's width of the reference's own top-4."""
    config, model, params, x, y = _setup(compute_dtype="bfloat16")
    got, chosen = _sys_routed(model)(params, x, y)
    with jax.default_matmul_precision("highest"):
        want, scores = _ref_routed(config)(params, x, y, chosen)
        free = _ref_losses(config)(params, x, y)
    gap = float(jnp.max(jnp.abs(got - want)))
    # bf16 activations through five blocks and a 512-way softmax: 0.04-0.07
    # over seeds 0-3 here; float32 reads 5e-6, and a dropped expert or a
    # wrong weight moves a token's loss by 0.3 and more
    assert gap < 0.15, gap
    # a flipped choice swaps a quarter of a layer's output: free routing
    # reads 0.1-0.3 on the same tokens, which is why the bound above is
    # taken with the choices forced
    assert float(jnp.max(jnp.abs(got - free))) > gap
    # sigmoid scores move by ~0.25 x the logit's bf16 error (~0.02)
    for s, c in zip(scores, chosen, strict=True):
        assert float(fam.choice_gap(s, c)) < 0.03

    g_sys = jax.jit(jax.grad(lambda p: jnp.mean(fam.system_token_losses(
        model, p, x, y))))(params)
    with jax.default_matmul_precision("highest"):
        g_ref = jax.jit(jax.grad(lambda p: jnp.mean(
            fam.reference_token_losses_routed(p, x, y, config, chosen)[0])))(
                params)
    # bf16 products with float32 accumulation.  The whole gradient is
    # within 2-2.5% of the float32 one's norm over seeds 0-2.  Leaf by leaf
    # most read 1-4%; the router's gate, whose gradient is a hundredth of
    # the whole (weights normalised over the choice leave it only what
    # differs between the chosen experts), reads up to 31%.  A leaf that is
    # missing a term or has a wrong sign reads 100% or more.
    sq = lambda t: sum(float(jnp.sum(a * a))  # noqa: E731
                       for a in jax.tree.leaves(t))
    diff = jax.tree.map(lambda a, b: a - b, g_sys, g_ref)
    assert (sq(diff) / sq(g_ref)) ** 0.5 < 0.05
    assert _rel(g_sys, g_ref) < 0.6


def test_gmm_and_dense_expert_layers_agree():
    config, model, params, x, y = _setup(1)
    dense = fam.build_model({**config, "train": {"moe_impl": "dense"}})

    def loss(m):
        return lambda p: jnp.mean(fam.system_token_losses(m, p, x, y))

    np.testing.assert_allclose(model.apply({"params": params}, x),
                               dense.apply({"params": params}, x), atol=2e-5)
    assert _rel(jax.jit(jax.grad(loss(model)))(params),
                jax.jit(jax.grad(loss(dense)))(params)) < 1e-5


def test_a_shape_mosaic_cannot_tile_takes_the_dense_path():
    """15 tokens x top-4 is no multiple of 128 rows: the layer runs its
    plain loop (the kernel would refuse), with the same result as asked
    for by name."""
    config, model, params, x, _ = _setup(1)
    dense = fam.build_model({**config, "train": {"moe_impl": "dense"}})
    np.testing.assert_array_equal(
        model.apply({"params": params}, x[:1, :15]),
        dense.apply({"params": params}, x[:1, :15]))


# ------------------------------------------------------- the expert share


def _layer(first, held, impl="gmm", routed=16):
    return DroplessMoe(num_experts=held, hidden=128, top_k=4,
                       num_experts_routed=routed, first_expert=first,
                       selection_bias=True, impl=impl)


def _whole_layer_params(seed=0, routed=16):
    whole = _layer(0, routed)
    u = jax.random.normal(jax.random.PRNGKey(seed), (64, 128))
    p = whole.init(jax.random.PRNGKey(seed + 1), u)["params"]
    p["expert_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                               (routed,))
    return u, p


@pytest.mark.parametrize("impl", ["gmm", "dense"])
def test_expert_shares_sum_to_the_whole_layer(impl):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one 16-expert
    layer.  Each routes over all 16 and computes its own experts' part; the
    parts add up to what the uncut reference layer gives."""
    u, p = _whole_layer_params()
    cut = {**CONFIG, "num_experts": 16, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        want, _ = fam._expert_ffn(p, u, cut, None)
    total = jnp.zeros_like(u)
    for first in (0, 4, 8, 12):
        share = {k: (v[first:first + 4] if k in ("w1", "w2", "w3") else v)
                 for k, v in p.items()}
        part = _layer(first, 4, impl).apply({"params": share}, u)
        ref_part, _ = fam._expert_ffn(share, u, {**CONFIG,
                                                 "first_expert": first}, None)
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, atol=5e-5)


@pytest.mark.parametrize("impl", ["gmm", "dense"])
def test_no_token_is_dropped_when_every_token_picks_one_expert(impl):
    """A selection bias that sends every token to expert 5 (and 6, 7, 8):
    expert 5 gets all 64 tokens, four times an even load, and the layer
    still equals the reference, which has no capacity to overflow."""
    u, p = _whole_layer_params(3)
    p["expert_bias"] = jnp.zeros((16,)).at[jnp.arange(5, 9)].set(
        jnp.asarray([8.0, 6.0, 4.0, 2.0]))
    share = {k: (v[4:8] if k in ("w1", "w2", "w3") else v)
             for k, v in p.items()}
    got, sown = _layer(4, 4, impl).apply({"params": share}, u,
                                         mutable=["intermediates"])
    want, _ = fam._expert_ffn(share, u, {**CONFIG, "first_expert": 4}, None)
    np.testing.assert_allclose(got, want, atol=2e-5)
    counts = np.asarray(sown["intermediates"]["moe_counts"][0])
    # [assignments, rows computed, loads of experts 4, 5, 6, 7]
    np.testing.assert_array_equal(counts[[0, 2, 3, 4, 5]],
                                  [256, 0, 64, 64, 64])
    # and its gradient reaches every token's row through expert 5
    d_u = jax.grad(lambda v: jnp.sum(_layer(4, 4, impl).apply(
        {"params": share}, v) ** 2))(u)
    assert np.all(np.abs(np.asarray(d_u)).max(axis=-1) > 0)


def test_remat_keeps_the_expert_choice_of_the_forward_pass():
    """A block under remat recomputes its forward pass for the backward
    one, but not the expert choice: recomputed in another XLA program, a
    near tie can break the other way, and the backward pass would run
    other experts than the forward pass did (on the chip, bf16, 8,192
    tokens: the last router's gradient 20% off the reference, 4% with the
    choice kept; PERF.md section 6).  So a gradient's program holds one
    ``top_k`` per expert layer, and two with a plain ``nn.remat``."""
    config, model, params, x, y = _setup(compute_dtype="bfloat16")
    assert model.config.remat

    def top_ks(m):
        return str(jax.make_jaxpr(jax.grad(lambda p: jnp.mean(
            fam.system_token_losses(m, p, x, y))))(params)).count("top_k[")

    assert top_ks(model) == 2  # the two expert layers, forward only
    plain = dataclasses.replace(model.config, remat=False)
    assert top_ks(Lfm2(plain)) == 2
    from tpudp.models import lfm2

    kept, lfm2.REMAT_POLICY = lfm2.REMAT_POLICY, None  # nn.remat's default
    try:
        assert top_ks(Lfm2(model.config)) == 4
    finally:
        lfm2.REMAT_POLICY = kept


def _mean_loss(model, x, y):
    return lambda p: jnp.mean(fam.system_token_losses(model, p, x, y))


def _kept_by_block(grad_jaxpr):
    """What each block under remat hands its backward pass, read off the
    gradient's program: for every backward ``remat2`` equation, in the
    order of the backward pass, ``(name, shape)`` of each operand that is
    no parameter; ``name`` is what ``checkpoint_name`` gave it
    (``reduce_precision`` is what jax wraps a kept value in that the
    forward pass reads again), None for an unnamed one."""
    jaxpr = grad_jaxpr.jaxpr
    leaves, names, blocks = set(jaxpr.invars), {}, []
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "name":
            names[eqn.outvars[0]] = eqn.params["name"]
        elif prim == "reduce_precision" and eqn.invars[0] in names:
            names[eqn.outvars[0]] = names[eqn.invars[0]]
        elif prim == "remat2":
            assert eqn.params["differentiated"]
            blocks.append([(names.get(v), v.aval.shape)
                           for v in eqn.invars if v not in leaves])
    return blocks


@pytest.mark.parametrize("layer_types, dense_layers", [
    (("conv", "full_attention", "conv"), 1),
    (("full_attention", "conv"), 0),
    (("conv", "conv"), 1),
    (("conv", "conv"), 0)])
def test_a_block_under_remat_keeps_its_input_and_the_named_values(
        layer_types, dense_layers, monkeypatch):
    """Beside its input, a block keeps the expert choice, flash's output
    and row statistics, the convolution's in-projection and what its
    operator adds to the residual stream, and nothing else: no value of
    the expert layer's ``T x k``-row buffers.  With ``o`` and ``lse``
    kept, the backward pass has no use for a second forward flash call:
    one ``flash_fwd`` an attention layer in the gradient's program, where
    everything-recomputed holds two."""
    from tpudp.models import lfm2
    from tpudp.models.moe import ROUTE_NAME
    from tpudp.ops.flash_attention import LSE_NAME, OUT_NAME

    config, model, params, x, y = _setup(
        t=128, attn_impl="flash", layer_types=list(layer_types),
        num_hidden_layers=len(layer_types), num_dense_layers=dense_layers)
    assert model.config.remat
    b, t = x.shape
    rows = b * t * config["num_experts_per_tok"]

    def program():
        return jax.make_jaxpr(jax.grad(_mean_loss(
            Lfm2(model.config), x, y)))(params)

    policy = program()
    blocks = _kept_by_block(policy)[::-1]  # the backward pass runs last first
    assert len(blocks) == len(layer_types)
    for i, (kind, kept) in enumerate(zip(layer_types, blocks)):
        want = [lfm2.OP_NAME] + (
            [lfm2.IN_PROJ_NAME] if kind == "conv" else [OUT_NAME, LSE_NAME])
        if i >= dense_layers:
            want.append(ROUTE_NAME)
        assert sorted(n for n, _ in kept if n) == sorted(want), (i, kept)
        # unnamed: the block's input and the cotangent of its output;
        # RoPE's positions
        assert sorted(shape for n, shape in kept if not n) == sorted(
            [(b, t, config["hidden_size"])] * 2
            + [(t,)] * (kind == "full_attention")), (i, kept)
        assert all(shape[:1] != (rows,) for _, shape in kept), (i, kept)
    attention = layer_types.count("full_attention")
    calls = kernel_calls(policy)
    assert [calls.get(k, 0) for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")] == [attention] * 3
    monkeypatch.setattr(lfm2, "REMAT_POLICY", None)  # nn.remat's default
    assert kernel_calls(program()).get("flash_fwd", 0) == 2 * attention


@pytest.mark.parametrize("attn_impl, t", [("dense", 64), ("flash", 128)])
def test_remat_changes_no_gradient(attn_impl, t):
    """A kept value is the value its recomputation would have produced:
    the loss and every parameter's gradient with ``remat`` are those
    without it."""
    _, model, params, x, y = _setup(t=t, attn_impl=attn_impl)
    assert model.config.remat
    plain = Lfm2(dataclasses.replace(model.config, remat=False))
    loss, grads = jax.jit(jax.value_and_grad(_mean_loss(model, x, y)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(_mean_loss(plain, x, y)))(
        params)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert _rel(grads, want) <= 1e-6


def test_unnormalised_scores_and_all_experts_held():
    """The layer's other settings: no selection bias, no normalisation, a
    scaling factor, every routed expert held."""
    layer = DroplessMoe(num_experts=4, hidden=128, top_k=2,
                        normalize=False, scaling=2.5)
    u = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    p = layer.init(jax.random.PRNGKey(1), u)["params"]
    assert "expert_bias" not in p
    s = jax.nn.sigmoid(u @ p["gate"])
    w, chosen = jax.lax.top_k(s, 2)
    want = jnp.zeros_like(u)
    for j in range(4):
        w_j = jnp.sum(jnp.where(chosen == j, w, 0.0), -1) * 2.5
        want += w_j[:, None] * ((jax.nn.silu(u @ p["w1"][j])
                                 * (u @ p["w3"][j])) @ p["w2"][j])
    np.testing.assert_allclose(layer.apply({"params": p}, u), want, atol=2e-5)
    with pytest.raises(ValueError, match="do not fit"):
        _layer(14, 4).init(jax.random.PRNGKey(0), u)
    with pytest.raises(ValueError, match="unknown moe impl"):
        _layer(0, 4, "sparse").init(jax.random.PRNGKey(0), u)


def test_config_checks_and_from_dict():
    cfg = Lfm2Config.from_dict({**CONFIG, "model_type": "lfm2_moe",
                                "train": {}}, remat=True)
    assert cfg.layer_types == tuple(CONFIG["layer_types"]) and cfg.remat
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(cfg, num_hidden_layers=5)
    with pytest.raises(ValueError, match="unknown layer types"):
        dataclasses.replace(cfg, layer_types=("conv", "conv", "window"))
    with pytest.raises(ValueError, match="attn_impl"):
        dataclasses.replace(cfg, attn_impl="ring")


# ---------------------------------------------------------- the counters


@pytest.mark.parametrize("devices, grad_accum", [(1, 1), (2, 1), (2, 2)])
def test_obs_moe_counts_through_the_train_step(devices, grad_accum):
    """``init_state(track_moe=True)``: two steps of the ordinary train
    step advance the accumulator by what the two expert layers routed,
    loads summed over the data axis; without it the state has no such
    leaf and the model trains the same."""
    from jax.sharding import Mesh

    from tpudp.train import (init_state, make_optimizer, make_train_step,
                             moe_metrics)

    config, model, _, _, _ = _setup()
    tx = make_optimizer(learning_rate=1e-3, weight_decay=0.0,
                        optimizer="adamw")
    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("data",))
    step = make_train_step(model, tx, mesh, "allreduce", donate=False,
                           grad_accum=grad_accum)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, config["vocab_size"], (4, 65)))
    state = init_state(model, tx, input_shape=(1, 16), track_moe=True)
    plain = init_state(model, tx, input_shape=(1, 16))
    assert plain.obs_moe is None
    assert len(jax.tree.leaves(state)) == len(jax.tree.leaves(plain)) + 1
    for _ in range(2):
        state, loss = step(state, tokens[:, :-1], tokens[:, 1:])
    if devices == 1:
        for _ in range(2):
            plain, loss_plain = step(plain, tokens[:, :-1], tokens[:, 1:])
        assert float(loss) == float(loss_plain)
    total, held, as_if_largest, rows = np.asarray(state.obs_moe)
    assert total == 2 * 2 * 4 * 64 * 4  # steps x layers x tokens x top-4
    assert 0 < held <= as_if_largest and held < total and rows >= held
    out = moe_metrics(state.obs_moe)
    assert out["moe_held_share"] == held / total
    # 4 of 16 experts held and a near-uniform router at initialisation
    assert 0.15 < out["moe_held_share"] < 0.35
    assert 1.0 <= out["moe_load_max_over_mean"] < 2.0
    assert out["moe_rows_over_held"] >= 1.0


def test_trainer_reports_the_moe_counters():
    from tpudp.train import Trainer

    config, model, _, _, _ = _setup()
    trainer = Trainer(model, None, input_shape=(1, 16), learning_rate=1e-3,
                      track_moe=True, log_fn=lambda _: None)
    assert "moe_held_share" not in trainer.metrics()  # no step yet
    assert trainer.state.obs_moe.shape == (4,)
    assert Trainer(model, None, input_shape=(1, 16),
                   log_fn=lambda _: None).state.obs_moe is None
