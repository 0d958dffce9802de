"""The latent-attention expert family (tpudp/models/pangu.py, the latent
page type and the absorbed paged attention, the engine's two programs for
it) against the plain float32 reference in perf/families/pangu_moe.py, on
seeded weights at small sizes: the module, absorbed = expanded attention,
the engine's prefill and decode through pages, the expert shares, the
counters, the page write, the ``latent_attn`` kernel (interpreted) against
the XLA loop, and every refusal."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.harness.cells import load_module
from tpudp.models.moe import dropless_moe
from tpudp.models.pangu import (PanguConfig, absorbed_queries, forward_paged,
                                latent_pad)
from tpudp.ops.paged_attention import latent_paged_attention
from tpudp.serve import Engine

fam = load_module("families", "pangu_moe")
# (`tpudp.models.generate` the attribute is the function of that name)
gen = importlib.import_module("tpudp.models.generate")

# One chip's share (experts 4-7 of 16) of a three-layer cut: one dense
# layer and two expert layers, every width small and none a multiple of
# 128, so the expert layer runs its plain loop.
CONFIG = dict(
    vocab_size=256, hidden_size=96, intermediate_size=160,
    moe_intermediate_size=64, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
    n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
    num_experts_routed=16, first_expert=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000,
    max_position_embeddings=128, compute_dtype="float32",
    serve={"weight_dtype": "float32"})


def _setup(seed=0, **overrides):
    config = {**CONFIG, **overrides}
    model = fam.build_model(config)
    params = model.init(jax.random.PRNGKey(seed + 1),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    return config, model, params


def _reference(config, params, tokens, routing=None):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, x, c: fam.reference_forward(
            p, x, config, c))(params, tokens, routing)


def _chosen(model, params, tokens):
    """The module's logits and, in layer order, its expert choices."""
    logits, sown = model.apply({"params": params}, tokens,
                               mutable=["intermediates"])
    layers = sown["intermediates"]
    return logits, [layers[n]["moe"]["moe_chosen"][0] for n in
                    sorted(layers, key=lambda n: int(n.split("_")[1]))]


def test_the_module_matches_the_reference_with_the_choices_forced():
    config, model, params = _setup()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 40)))
    got, chosen = _chosen(model, params, tokens)
    want, scores = _reference(config, params, tokens, chosen)
    assert got.shape == (2, 40, 256) and len(chosen) == 2
    np.testing.assert_allclose(got, want, atol=2e-4)
    # float32 on both sides: the module's choice IS the reference's top-k
    for s, c in zip(scores, chosen):
        assert float(fam.choice_gap(s, c)) <= 1e-6
    free, _ = _reference(config, params, tokens)
    np.testing.assert_allclose(got, free, atol=2e-4)


@pytest.mark.parametrize("start, even", [("module", True), ("ones", False)])
def test_the_untrained_router_is_near_even(start, even):
    """Where the module starts (``pangu.POST_NORM_SCALE``, ``EMBED_STD``) a
    token's stream is mostly its own embedding and the 64 experts of a
    layer are chosen nearly alike; with the closing norms at one and
    flax's embedding every token carries the same sublayer outputs and an
    eighth of the experts take most of the choices (what made a held
    share's load, and the serving cell's step time, follow the seed)."""
    config, model, params = _setup(
        hidden_size=128, num_hidden_layers=4, n_routed_experts=8,
        num_experts_routed=64, first_expert=0, num_experts_per_tok=4)
    if start == "ones":
        params = dict(params)
        params["wte"] = {"embedding": params["wte"]["embedding"] / 128 ** .5}
        for i in range(4):
            params[f"h_{i}"] = {
                **params[f"h_{i}"],
                **{n: {"scale": jnp.ones_like(
                    params[f"h_{i}"][n]["scale"])}
                   for n in ("rms_post_attn", "rms_post_mlp")}}
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 256, (1, 128)))
    _, scores = _reference(config, params, tokens)
    shares = []
    for s in scores:
        chosen = np.argsort(-np.asarray(s).reshape(128, 64), -1)[:, :4]
        counts = np.sort(np.bincount(chosen.ravel(), minlength=64))
        shares.append(counts[-8:].sum() / counts.sum())  # even: 0.125
    assert (max(shares) < 0.3) == even, shares
    assert (min(shares) > 0.4) == (not even), shares


def test_parameters_are_made_in_param_dtype():
    config, model, _ = _setup(serve={"weight_dtype": "bfloat16"},
                              compute_dtype="bfloat16")
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 16), jnp.int32))["params"], jax.random.PRNGKey(0))
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert shapes["h_1"]["moe"]["w1"].shape == (4, 96, 64)
    assert shapes["lm_head"]["kernel"].shape == (96, 256)
    assert "moe" not in shapes["h_0"] and "shared" in shapes["h_2"]


def _paged_setup(t_page=8, pages=12, seed=0):
    config, model, params = _setup(seed)
    cfg = model.config
    pool = gen.page_type(cfg).zeros(cfg, pages + 1, t_page)
    return config, model, cfg, params, pool


def test_absorbed_attention_through_pages_equals_the_expanded_module():
    """One attention layer: the module's expanded form on a whole sequence
    against the absorbed form, fed a window at a time through scattered
    pages."""
    from tpudp.models.pangu import LatentAttention

    _, _, cfg, params, pool = _paged_setup()
    p = params["h_1"]["attn"]
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 96))
    want = LatentAttention(cfg).apply({"params": p}, u, jnp.arange(24))
    table = jnp.asarray([[7, 2, 9, -1]], jnp.int32)
    pages, outs = tuple(pool), []
    c, h = cfg.kv_lora_rank, cfg.num_attention_heads
    w_v = p["wkv_b"]["kernel"].reshape(c, h, -1)[..., cfg.qk_nope_head_dim:]
    for start in range(0, 24, 8):
        positions = start + jnp.arange(8)[None]
        q_lat, q_rope, c_kv, k_rope = absorbed_queries(
            cfg, p, u[:, start:start + 8], positions)
        assert k_rope.shape[-1] == latent_pad(cfg) == 128
        pages = gen.write_token_pages(pages, c_kv, k_rope, table,
                                      jnp.int32(start), jnp.ones((1,), bool),
                                      layer=1)
        o_lat = latent_paged_attention(
            q_lat, q_rope, pages, table, jnp.int32(start),
            scale=cfg.score_scale, dtype=jnp.float32, layer=1)
        outs.append(jnp.einsum("bqhc,chv->bqhv", o_lat, w_v).reshape(
            1, 8, -1) @ p["wo"]["kernel"])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=2e-5)


def test_latent_attention_masks_by_row_depth():
    """Slots at different depths in one call: each row sees its own
    ``pos + 1`` tokens, whatever lies in the pages behind them."""
    _, _, cfg, _, pool = _paged_setup()
    rng = jax.random.PRNGKey(5)
    c_pages = jax.random.normal(rng, pool.c.shape)
    r_pages = jax.random.normal(jax.random.fold_in(rng, 1), pool.r.shape)
    table = jnp.asarray([[3, 1, -1], [0, 5, 8]], jnp.int32)
    pos = jnp.asarray([9, 20], jnp.int32)
    q_lat = jax.random.normal(jax.random.fold_in(rng, 2), (2, 1, 4, 32))
    q_rope = jax.random.normal(jax.random.fold_in(rng, 3), (2, 1, 4, 128))
    got = latent_paged_attention(q_lat, q_rope, (c_pages, r_pages), table,
                                 pos, scale=0.2, dtype=jnp.float32, layer=2)
    for b in range(2):
        n = int(pos[b]) + 1
        ids = [int(i) for i in table[b] if i >= 0]
        ck = jnp.concatenate([c_pages[2, i] for i in ids])[:n]
        rk = jnp.concatenate([r_pages[2, i] for i in ids])[:n]
        s = (jnp.einsum("hc,tc->ht", q_lat[b, 0], ck)
             + jnp.einsum("hr,tr->ht", q_rope[b, 0], rk)) * 0.2
        want = jax.nn.softmax(s, axis=-1) @ ck
        np.testing.assert_allclose(got[b, 0], want, atol=2e-5)


# (table rows, pos: a scalar is a prefill window, cur, heads)
KERNEL_CASES = {
    # the window sits in its third page; the entries behind its reach are
    # unmapped and the grid still walks them
    "a_chunk_across_pages": ([[7, 2, 9, -1, -1]], 16, 8, 4),
    "slots_at_different_depths": ([[3, 1, -1], [0, 5, 8]], [9, 20], 1, 4),
    "a_slot_with_no_page": ([[3, 1, -1], [-1, -1, -1]], [9, 0], 1, 4),
    # 16 tokens x 128 heads are two row blocks of 1,024 (eight tokens):
    # tokens 5..12 straddle the first page boundary and 13..20 the second,
    # and the last block sees three pages where the first sees two
    "a_row_block_off_the_page_boundary": ([[4, 11, 6, -1]], 5, 16, 128),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_latent_kernel_equals_the_loop_on_the_same_pages(case):
    """``impl='kernel'`` (the ``latent_attn`` Mosaic call, interpreted
    here) against ``impl='einsum'`` (the XLA loop the two tests above
    hold to the expanded form) on the same random pages; a slot whose
    table row is all ``-1`` comes back as zeros from the kernel."""
    rows, pos, cur, heads = KERNEL_CASES[case]
    _, _, cfg, _, pool = _paged_setup()
    rng = jax.random.PRNGKey(11)
    pages = (jax.random.normal(rng, pool.c.shape),
             jax.random.normal(jax.random.fold_in(rng, 1), pool.r.shape))
    table = jnp.asarray(rows, jnp.int32)
    b = table.shape[0]
    q_lat = jax.random.normal(jax.random.fold_in(rng, 2),
                              (b, cur, heads, 32))
    q_rope = jax.random.normal(jax.random.fold_in(rng, 3),
                               (b, cur, heads, 128))
    kw = dict(scale=0.2, dtype=jnp.float32, layer=1)
    want = latent_paged_attention(q_lat, q_rope, pages, table,
                                  jnp.asarray(pos, jnp.int32), **kw)
    got = latent_paged_attention(q_lat, q_rope, pages, table,
                                 jnp.asarray(pos, jnp.int32), impl="kernel",
                                 **kw)
    assert got.shape == want.shape and got.dtype == want.dtype
    mapped = np.asarray(table[:, 0] >= 0)
    np.testing.assert_allclose(np.asarray(got)[mapped],
                               np.asarray(want)[mapped], atol=2e-5)
    assert np.all(np.asarray(got)[~mapped] == 0)
    assert np.isfinite(np.asarray(got)).all()


def test_the_latent_op_refuses_an_impl_it_does_not_have():
    _, _, _, _, pool = _paged_setup()
    q = jnp.zeros((1, 8, 4, 32))
    with pytest.raises(ValueError, match="unknown latent paged-attention"):
        latent_paged_attention(q, jnp.zeros((1, 8, 4, 128)), tuple(pool),
                               jnp.zeros((1, 3), jnp.int32), jnp.int32(0),
                               scale=0.2, dtype=jnp.float32, layer=0,
                               impl="gather")


def test_the_latent_page_write_leaves_other_pages_alone():
    _, _, cfg, _, pool = _paged_setup(pages=5)
    pages = tuple(jnp.full(b.shape, 7.0) for b in pool)
    table = jnp.asarray([[4, 1], [2, -1], [0, 3]], jnp.int32)
    c_new = jnp.arange(3 * 32, dtype=jnp.float32).reshape(3, 1, 32)
    r_new = -jnp.ones((3, 1, 128))
    # slot 0 writes token 9 (page 1, row 1), slot 1 is inactive, slot 2
    # writes token 3 (page 0, row 3); layer 1 only
    out = gen.write_token_pages(pages, c_new, r_new, table,
                                jnp.asarray([9, 2, 3]),
                                jnp.asarray([True, False, True]), layer=1)
    want_c = np.full(pool.c.shape, 7.0, np.float32)
    want_c[1, 1, 1], want_c[1, 0, 3] = c_new[0, 0], c_new[2, 0]
    want_c[1, 5, 2] = c_new[1, 0]  # the inactive slot's row: the scratch page
    np.testing.assert_array_equal(out[0], want_c)
    want_r = np.full(pool.r.shape, 7.0, np.float32)
    want_r[1, 1, 1] = want_r[1, 0, 3] = want_r[1, 5, 2] = -1.0
    np.testing.assert_array_equal(out[1], want_r)


def test_a_whole_chunk_commits_as_one_page():
    _, _, cfg, _, pool = _paged_setup(pages=4)
    pages = tuple(jnp.full(b.shape, 7.0) for b in pool)
    c_new = jnp.ones((1, 8, 32))
    out = gen.write_token_pages(pages, c_new, jnp.ones((1, 8, 128)),
                                jnp.asarray([[3, 1]], jnp.int32),
                                jnp.int32(8), jnp.ones((1,), bool), layer=0)
    changed = np.argwhere(np.asarray(out[0]) != 7.0)
    assert {(int(a), int(b)) for a, b, *_ in changed} == {(0, 1)}
    assert len(changed) == 8 * 32


def _engine(model, params, **kw):
    kw = {"num_slots": 3, "max_len": 64, "prefill_chunk": 8, "kv_pages": 24,
          **kw}
    return Engine(model, params, **kw)


def test_the_engine_serves_prefill_and_decode_through_latent_pages():
    """Several chunks, several pages, slots at different depths, a slot
    that stays empty: every greedy token the engine emits is the
    reference's argmax given the system's choices (float32: to rounding),
    and the pool is the latent page type."""
    config, model, params = _setup()
    eng = _engine(model, params, num_slots=4)
    assert isinstance(eng.page_pool.pages, gen.LatentPages)
    assert eng.page_pool.pages.c.shape == (3, 25, 8, 32)
    assert eng.page_pool.pages.r.shape == (3, 25, 8, 128)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n, dtype=np.int32)
               for n in (5, 19, 30)]
    handles = [eng.submit(p, 7) for p in prompts]
    eng.run_until_complete()
    m = eng.metrics()
    assert m["paged_attn"]["resolved"] == "einsum"
    assert m["paged_attn"]["requested"] is None
    assert set(m["paged_attn"]["dispatch"]) == {"decode_paged",
                                                "prefill_paged"}
    for p, h in zip(prompts, handles):
        assert h.ok and len(h.tokens) == 7
        seq = jnp.asarray(np.concatenate([p, h.tokens]))[None]
        got, chosen = _chosen(model, params, seq)
        want, _ = _reference(config, params, seq, chosen)
        rows = np.asarray(want)[0, p.size - 1:p.size + 6]
        gap = rows.max(-1) - rows[np.arange(7), h.tokens]
        assert gap.max() <= 2e-4, gap
    eng.close()


def test_the_engine_serves_through_the_latent_kernel():
    """``paged_attn='kernel'`` is accepted for the family and shown in
    ``metrics()``: both programs trace the ``latent_attn`` call (their own
    TRACE_COUNTS keys), a slot stays empty throughout, and every greedy
    token is still the reference's argmax given the system's choices."""
    from tpudp.serve.engine import TRACE_COUNTS

    config, model, params = _setup()
    before = dict(TRACE_COUNTS)
    eng = _engine(model, params, num_slots=4, paged_attn="kernel")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=n, dtype=np.int32)
               for n in (5, 19, 30)]
    handles = [eng.submit(p, 5) for p in prompts]
    eng.run_until_complete()
    attn = eng.metrics()["paged_attn"]
    assert attn["requested"] == attn["resolved"] == "kernel"
    assert attn["dispatch"] == {"decode_paged": "kernel",
                                "prefill_paged": "kernel"}
    assert not attn.get("fallbacks")
    for key in ("decode_paged_latent_kernel", "prefill_paged_latent_kernel"):
        assert TRACE_COUNTS[key] > 0
    for key in ("decode_paged_latent", "prefill_paged_latent"):
        assert TRACE_COUNTS[key] == before.get(key, 0)
    for p, h in zip(prompts, handles):
        assert h.ok and len(h.tokens) == 5
        seq = jnp.asarray(np.concatenate([p, h.tokens]))[None]
        got, chosen = _chosen(model, params, seq)
        want, _ = _reference(config, params, seq, chosen)
        rows = np.asarray(want)[0, p.size - 1:p.size + 4]
        gap = rows.max(-1) - rows[np.arange(5), h.tokens]
        assert gap.max() <= 2e-4, gap
    eng.close()


def test_forward_paged_takes_the_backend_and_unset_is_einsum_on_the_cpu():
    """``generate._forward_paged``'s ``impl`` reaches the latent family:
    unset traces the XLA loop on the CPU platform (the benchmark's routed
    check calls it so; on an accelerator that is the kernel), ``'kernel'``
    the Mosaic call, and the two agree."""
    _, _, cfg, params, pool = _paged_setup()
    table = jnp.asarray([[2, 5, -1], [7, 0, 3]], jnp.int32)
    tokens = jnp.asarray([[3], [9]], jnp.int32)
    args = (tokens, pool, table, jnp.asarray([4, 17], jnp.int32),
            jnp.ones((2,), bool))

    def text(impl):
        return str(jax.make_jaxpr(lambda p: gen._forward_paged(
            cfg, p, *args, impl))(params))

    assert "pallas_call" not in text(None) and text(None) == text("einsum")
    assert "pallas_call" in text("kernel")
    want, _ = gen._forward_paged(cfg, params, *args)
    got, _ = gen._forward_paged(cfg, params, *args, "kernel")
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_forward_paged_logits_match_the_reference_position_by_position():
    """The library's paged forward, chunk by chunk and then token by
    token at the engine's decode shape with two slots idle: every
    position's logits against the reference's, the system's choices
    forced."""
    config, _, cfg, params, pool = _paged_setup()
    seq = np.random.default_rng(1).integers(0, 256, 29).astype(np.int32)
    table = np.full((3, 8), -1, np.int32)
    table[1, :4] = [5, 0, 7, 2]
    logits, chosen = [], [[], []]
    for start in range(0, 24, 8):
        routed = []
        lg, pool = forward_paged(cfg, params, seq[None, start:start + 8],
                                 pool, jnp.asarray(table[1:2]),
                                 jnp.int32(start), jnp.ones((1,), bool),
                                 routed=routed)
        logits.append(lg[0])
        for rows, (c, _) in zip(chosen, routed):
            rows.append(c)
    active = jnp.asarray([False, True, False])
    for j in range(24, 29):
        routed = []
        toks = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(int(seq[j]))
        lens = jnp.zeros((3,), jnp.int32).at[1].set(j)
        lg, pool = forward_paged(cfg, params, toks, pool, jnp.asarray(table),
                                 lens, active, routed=routed)
        logits.append(lg[1])
        for rows, (c, _) in zip(chosen, routed):
            rows.append(c[1:2])
    want, _ = _reference(config, params, jnp.asarray(seq)[None],
                         [jnp.concatenate(rows) for rows in chosen])
    np.testing.assert_allclose(jnp.concatenate(logits), want[0], atol=2e-4)


def test_inactive_and_padding_rows_move_no_counter():
    """Engine counters: a prompt of 11 tokens in chunks of 8 has 5 rows of
    padding in its second chunk, two of three slots stay idle in every
    decode run; the counters see 11 + 3 rows x top-2 x 2 layers, no more."""
    _, model, params = _setup()
    eng = _engine(model, params)
    h = eng.submit(np.arange(11, dtype=np.int32), 4)
    eng.run_until_complete()
    st = eng.metrics()["stats"]
    assert h.ok and st["prefill_chunks"] == 2 and st["decode_steps"] == 3
    assert st["moe_layer_runs"] == (2 + 3) * 2
    assert st["moe_rows"] == (11 + 3) * 2 * 2
    assert 0 <= st["moe_rows_held"] <= st["moe_rows"]
    assert st["moe_experts_touched"] <= min(st["moe_rows_held"],
                                            st["moe_layer_runs"] * 4)
    assert (st["moe_rows_held"] > 0) == (st["moe_experts_touched"] > 0)
    eng.close()


@pytest.mark.parametrize("impl", ["dense", "gmm"])
def test_rows_that_are_not_live_reach_no_expert(impl):
    """``dropless_moe(live=)``: a row that is not live returns zeros, is in
    no load and no count, and leaves the live rows' results as they were.
    128 rows x top-2 at widths of 128: the kernels run (interpret mode)."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (128, 128))
    gate = jax.random.normal(jax.random.fold_in(key, 1), (128, 8))
    w1, w3 = (0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                      (4, 128, 128)) for i in (2, 3))
    w2 = 0.1 * jax.random.normal(jax.random.fold_in(key, 4), (4, 128, 128))
    live = jnp.arange(128) % 3 != 0
    kw = dict(top_k=2, first_expert=2, scaling=2.5, impl=impl)
    y, chosen, counts = dropless_moe(x, gate, w1, w3, w2, live=live, **kw)
    y_all, chosen_all, counts_all = dropless_moe(x, gate, w1, w3, w2, **kw)
    np.testing.assert_array_equal(chosen, chosen_all)
    np.testing.assert_array_equal(y[~live], 0.0)
    np.testing.assert_allclose(y[live], y_all[live], atol=1e-5)
    held = (chosen >= 2) & (chosen < 6)
    assert counts[0] == 2 * int(live.sum()) and counts_all[0] == 256
    assert counts[2:].sum() == int((held & live[:, None]).sum())
    assert counts_all[2:].sum() == int(held.sum())


def test_the_shares_and_the_shared_expert_once_sum_to_the_whole_layer():
    """8 routed experts as 4 shares of 2: the shares' routed parts plus
    the shared expert counted once are the uncut reference's whole layer
    (all 8 held, one chip)."""
    config, _, params = _setup(n_routed_experts=8, num_experts_routed=8,
                               first_expert=0)
    blk = params["h_1"]
    u = jax.random.normal(jax.random.PRNGKey(7), (40, 96))
    with jax.default_matmul_precision("highest"):
        whole, _ = fam._expert_ffn(blk["moe"], u, config, None)
        whole = whole + fam._swiglu(blk["shared"], u)
    moe = blk["moe"]
    parts = sum(dropless_moe(
        u, moe["gate"], moe["w1"][i:i + 2], moe["w3"][i:i + 2],
        moe["w2"][i:i + 2], top_k=2, first_expert=i, scaling=2.5,
        impl="dense")[0] for i in range(0, 8, 2))
    np.testing.assert_allclose(parts + fam._swiglu(blk["shared"], u), whole,
                               atol=2e-5)


REFUSED = [
    ("kv_pages", dict(kv_pages=0)),
    ("kv_dtype", dict(kv_dtype="int8")),
    ("speculate_k", dict(speculate_k=2)),
    ("speculate_tree", dict(speculate_k=2, speculate_tree="binary2")),
    ("decode_fuse", dict(decode_fuse=4)),
    ("paged_attn", dict(paged_attn="gather")),
]


@pytest.mark.parametrize("option, kw", REFUSED, ids=[o for o, _ in REFUSED])
def test_the_engine_refuses_what_the_family_does_not_serve(option, kw):
    _, model, params = _setup()
    with pytest.raises(ValueError, match=rf"Engine\({option}=\.\.\.\) is not "
                       "served for the latent-attention family"):
        _engine(model, params, **kw)


def test_the_engine_refuses_co_residence():
    from tpudp.serve.tenancy import TenantClass

    _, model, params = _setup()
    with pytest.raises(ValueError, match=r"Engine\(models=\.\.\.\)"):
        _engine(model, params, tenants={"a": TenantClass()},
                models={"other": (model, params)})


@pytest.mark.parametrize("method", ["export_ticket", "admit_ticket"])
def test_the_engine_refuses_migration_tickets(method):
    _, model, params = _setup()
    eng = _engine(model, params)
    with pytest.raises(ValueError, match=rf"Engine\.{method}\(\) is not "
                       "served"):
        getattr(eng, method)(None)
    eng.close()


@pytest.mark.parametrize("entry", ["generate", "beam_search"])
def test_generate_and_beam_search_refuse_the_config(entry):
    _, model, params = _setup()
    with pytest.raises(ValueError, match="no dense-cache twin"):
        getattr(gen, entry)(model, params, jnp.zeros((1, 4), jnp.int32), 2)


def test_page_types_and_their_geometry():
    from tpudp.models.gpt2 import GPT2Config

    cfg = PanguConfig()
    assert gen.page_type(cfg) is gen.LatentPages
    assert gen.LatentPages.geometry(cfg) == ("latent", 3, 32, 128, "float32")
    with pytest.raises(ValueError, match="kv_dtype"):
        gen.page_type(cfg, "int8")
    g = GPT2Config(vocab_size=64, max_seq_len=32, num_layers=2, num_heads=2,
                   d_model=16)
    assert gen.page_type(g) is gen.KVPages  # the arena's type is KVCache
    assert gen.page_type(g, "int8") is gen.Int8Pages
    assert gen.KVCache.geometry(g) == gen.KVPages.geometry(g) \
        == gen.Int8Pages.geometry(g) == (2, 2, 8, str(g.dtype))
    # a heads page is a token's row of kv_heads x head_dim values, fp and
    # int8 alike; the int8 scales stay one a head
    assert gen.KVPages.zeros(g, 5, 4).k.shape == (2, 5, 4, 16)
    q8 = gen.Int8Pages.zeros(g, 5, 4)
    assert q8.v.shape == (2, 5, 4, 16) and q8.v_scale.shape == (2, 5, 4, 2)
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        dataclasses.replace(cfg, first_k_dense_replace=9)
