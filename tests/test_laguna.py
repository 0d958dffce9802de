"""The window-and-full-attention expert family (tpudp/models/laguna.py, the
two-pool page type, the window mask of the paged attention paths, the
engine's window table and its two programs) against the plain float32
reference in perf/families/laguna_moe.py, on seeded weights at small
sizes: the module, paged prefill then decode through both page sizes
(smaller than the window, equal to it; the masked attention paths alone
are tests/test_paged_window.py's), the window pool's bound and recycling,
YaRN's frequencies, the untrained router, the planted faults (window
halved, RoPE kinds swapped, a page released early) and every refusal."""

import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.harness.cells import load_module
from tpudp.models.laguna import (FULL, SLIDING, Laguna, LagunaConfig,
                                 RopeKind, forward_paged, rope_inv_freq)
from tpudp.serve import Engine

fam = load_module("families", "laguna_moe")
# (`tpudp.models.generate` the attribute is the function of that name)
gen = importlib.import_module("tpudp.models.generate")

ROPES = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 64, "beta_slow": 1,
           "beta_fast": 8, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1}}
# The published pattern's first five layers at small widths, none a
# multiple of 128, so the expert layer runs its plain loop: a dense layer
# with full attention, three sliding expert layers, a full expert layer;
# 6 / 8 query heads over 2 KV heads; a window of 16.
WINDOW = 16
CONFIG = dict(
    vocab_size=256, hidden_size=96, intermediate_size=160,
    moe_intermediate_size=48, shared_expert_intermediate_size=48,
    num_hidden_layers=5, layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[6, 8, 8, 8, 6], num_key_value_heads=2,
    head_dim=16, sliding_window=WINDOW, rope_parameters=ROPES,
    num_experts=16, num_experts_per_tok=2, moe_routed_scaling_factor=2.5,
    gating=True, rms_norm_eps=1e-6, max_position_embeddings=128,
    compute_dtype="float32", serve={"weight_dtype": "float32"})
ATOL = 2e-4  # float32 on both sides


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


def _gaps(config, model, params, prompt, tokens):
    """How far each greedy token lies under the reference's argmax, the
    system's choices forced."""
    seq = np.zeros((1, 96), np.int32)  # one length: one compilation
    seq[0, :prompt.size + len(tokens)] = [*prompt, *tokens]
    _, chosen = _chosen(model, params, jnp.asarray(seq))
    want, _ = _reference(config, params, jnp.asarray(seq), chosen)
    rows = np.asarray(want)[0, prompt.size - 1:prompt.size - 1 + len(tokens)]
    return rows.max(-1) - rows[np.arange(len(tokens)), tokens]


@pytest.mark.parametrize("routing", ["forced", "free"])
def test_the_module_matches_the_reference(routing):
    config, model, params = _setup()
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 40)))
    got, chosen = _chosen(model, params, tokens)
    assert got.shape == (2, 40, 256) and len(chosen) == 4
    want, scores = _reference(config, params, tokens,
                              chosen if routing == "forced" else None)
    np.testing.assert_allclose(got, want, atol=ATOL)
    # float32 on both sides: the module's choice IS the reference's top-k
    for s, c in zip(scores, chosen):
        assert float(fam.choice_gap(s, c)) <= 1e-6


def test_parameters_are_made_in_param_dtype():
    _, model, _ = _setup(serve={"weight_dtype": "bfloat16"},
                         compute_dtype="bfloat16")
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 16), jnp.int32))["params"], jax.random.PRNGKey(0))
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert shapes["h_0"]["attn"]["wq"]["kernel"].shape == (96, 6 * 16)
    assert shapes["h_1"]["attn"]["wq"]["kernel"].shape == (96, 8 * 16)
    assert shapes["h_1"]["attn"]["wg"]["kernel"].shape == (96, 8)
    assert shapes["h_1"]["attn"]["wk"]["kernel"].shape == (96, 2 * 16)
    assert shapes["h_1"]["moe"]["w1"].shape == (16, 96, 48)
    assert "moe" not in shapes["h_0"] and "shared" in shapes["h_4"]
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        fam.parameters_held({**CONFIG})


# ---------------------------------------------------------------- YaRN


def test_yarn_frequencies_are_the_closed_form():
    """The published full-attention setting: 32 pairs over the first 64 of
    128 dimensions, theta 500,000, factor 64 from 4,096, beta 64 / 1.  The
    ramp runs from pair floor(corr(64)) = 5 to ceil(corr(1)) = 16: pairs
    up to 5 keep their trained frequency, pairs from 16 on turn 64 times
    slower, the system's table and the reference's agree."""
    rope = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}
    got = rope_inv_freq(RopeKind.from_dict(rope), 128)
    assert got.shape == (32,) and got.dtype == np.float32
    base = 500000.0 ** (-np.arange(32) / 32.0)

    def corr(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (
            2 * math.log(500000.0))

    low, high = math.floor(corr(64)), math.ceil(corr(1))
    assert (low, high) == (5, 16)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, base * (1 - ramp) + base / 64 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(got[:6], base[:6], rtol=1e-6)
    np.testing.assert_allclose(got[16:], base[16:] / 64, rtol=1e-6)
    ref, factor = fam.inv_freq(rope, 128)
    np.testing.assert_allclose(got, ref, rtol=2e-6)
    assert factor == pytest.approx(0.1 * math.log(64) + 1)
    plain, one = fam.inv_freq(ROPES[SLIDING], 128)
    np.testing.assert_allclose(
        rope_inv_freq(RopeKind.from_dict(ROPES[SLIDING]), 128), plain,
        rtol=2e-6)
    assert plain.shape == (64,) and one == 1.0


def test_yarn_frequencies_are_transformers():
    """Against ``transformers``' own function, imported by this test only
    (the library and the reference write the formula out)."""
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    pytest.importorskip("torch")
    from types import SimpleNamespace

    scaling = {"rope_type": "yarn", "factor": 64.0, "beta_fast": 64.0,
               "beta_slow": 1.0, "attention_factor": 1.4158883083359672,
               "original_max_position_embeddings": 4096,
               "rope_theta": 500000.0, "partial_rotary_factor": 0.5}
    config = SimpleNamespace(
        rope_theta=500000.0, partial_rotary_factor=0.5, head_dim=128,
        hidden_size=2048, num_attention_heads=48,
        max_position_embeddings=262144, rope_scaling=scaling,
        rope_parameters=scaling)
    try:
        want, factor = rope_utils._compute_yarn_parameters(config, "cpu")
    except Exception as exc:  # noqa: BLE001 — another release's signature
        pytest.skip(f"transformers' function takes another config: {exc!r}")
    got = rope_inv_freq(RopeKind.from_dict(scaling), 128)
    np.testing.assert_allclose(got, want.numpy(), rtol=2e-6)
    assert factor == pytest.approx(1.4158883083359672)


# ------------------------------------- the library forward through pages


@functools.lru_cache(maxsize=None)
def _library_pass(t_page, impl):
    """The library's paged forward, chunk by chunk and then token by token
    at the engine's decode shape with two slots idle, through ONE table
    with every page mapped (what the benchmark's driver passes): the
    sequence, every position's logits, every expert layer's choices."""
    _, model, params = _setup()
    cfg = model.config
    n_prompt, n_all = 3 * t_page, 3 * t_page + 7
    seq = np.random.default_rng(1).integers(0, 256, n_all).astype(np.int32)
    pool = gen.page_type(cfg).zeros(cfg, 9, t_page)
    assert isinstance(pool, gen.WindowedPages)
    assert pool.full.k.shape == (2, 9, t_page, 32)
    assert pool.window.v.shape == (3, 9, t_page, 32)
    table = np.full((3, 8), -1, np.int32)
    table[1, :4] = [5, 0, 7, 2]
    logits, chosen = [], [[] for _ in range(4)]
    for start in range(0, n_prompt, t_page):
        routed = []
        lg, pool = gen._forward_paged(
            cfg, params, seq[None, start:start + t_page], pool,
            jnp.asarray(table[1:2]), jnp.int32(start), jnp.ones((1,), bool),
            impl, routed=routed)
        logits.append(lg[0])
        for rows, (c, _) in zip(chosen, routed):
            rows.append(c)
    active = jnp.asarray([False, True, False])
    for j in range(n_prompt, n_all):
        routed = []
        toks = jnp.zeros((3, 1), jnp.int32).at[1, 0].set(int(seq[j]))
        lens = jnp.zeros((3,), jnp.int32).at[1].set(j)
        lg, pool = forward_paged(cfg, params, toks, pool, jnp.asarray(table),
                                 lens, active, impl, routed=routed)
        logits.append(lg[1])
        for rows, (c, _) in zip(chosen, routed):
            rows.append(c[1:2])
    return (seq, jnp.concatenate(logits),
            [jnp.concatenate(rows) for rows in chosen])


@pytest.mark.parametrize("routing", ["forced", "free"])
@pytest.mark.parametrize("impl", ["einsum", "kernel"])
@pytest.mark.parametrize("t_page", [8, WINDOW],
                         ids=["page<window", "page=window"])
def test_forward_paged_matches_the_reference_position_by_position(
        t_page, impl, routing):
    """Every position's logits of :func:`_library_pass` against the
    reference's, across page and window boundaries, the system's choices
    forced or the reference routing itself."""
    config, _, params = _setup()
    seq, logits, chosen = _library_pass(t_page, impl)
    want, _ = _reference(config, params, jnp.asarray(seq)[None],
                         chosen if routing == "forced" else None)
    np.testing.assert_allclose(logits, want[0], atol=ATOL)


# ---------------------------------------------- the engine's two tables


def _engine(model, params, t_page=8, **kw):
    slots = kw.pop("num_slots", 3)
    kw = {"num_slots": slots, "max_len": 96, "prefill_chunk": t_page,
          "kv_pages": slots * (96 // t_page), **kw}
    return Engine(model, params, **kw)


@pytest.mark.parametrize("impl", ["einsum", "kernel"])
@pytest.mark.parametrize("t_page", [8, WINDOW],
                         ids=["page<window", "page=window"])
def test_the_engine_serves_prefill_and_decode_through_two_pools(t_page,
                                                                impl):
    """Several chunks, several pages, slots at different depths, more
    requests than slots: every greedy token the engine emits is the
    reference's argmax given the system's choices, while the window pool
    is the size the window asks for and its pages go round: freed by one
    slot, taken by another."""
    config, model, params = _setup()
    eng = _engine(model, params, t_page, paged_attn=impl)
    live = -(-WINDOW // t_page) + 1  # pages a slot's window can overlap
    pool = eng.page_pool
    assert isinstance(pool.pages, gen.WindowedPages)
    assert pool.window_pages == 3 * live
    assert pool.pages.window.k.shape == (3, 3 * live + 1, t_page, 32)
    assert pool.pages.full.k.shape == (2, 3 * (96 // t_page) + 1, t_page, 32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n, dtype=np.int32)
               for n in (5, 19, 50, 33, 70)]
    handles = [eng.submit(p, 20) for p in prompts]
    holders: dict[int, set] = {}  # window page -> the slots that held it
    ms = eng._mstates[None]
    while eng.queue_depth or eng.slots_in_use:
        eng.step()
        eng.check_paged()
        for s in range(3):
            row = ms.wtable[s][ms.wtable[s] >= 0]
            assert len(row) <= live, (s, ms.wtable[s])
            for page in row:
                holders.setdefault(int(page), set()).add(s)
    m = eng.metrics()
    assert m["paged_attn"]["resolved"] == impl
    assert m["paged_attn"]["window"] == WINDOW
    assert set(m["paged_attn"]["dispatch"]) == {"decode_paged",
                                                "prefill_paged"}
    assert m["page_pools"][0]["window_pages"] == 3 * live
    assert m["page_pools"][0]["window_used_pages"] == 0  # all retired
    assert m["stats"]["window_pages_freed"] > 0
    assert any(len(slots) > 1 for slots in holders.values()), holders
    assert "prefix_lookups" not in m["stats"]  # skipped for this family
    for p, h in zip(prompts, handles):
        assert h.ok and len(h.tokens) == 20
        gap = _gaps(config, model, params, p, np.asarray(h.tokens))
        assert gap.max() <= ATOL, gap
    eng.close()


def test_the_window_counters_are_the_hand_count():
    """One request of 11 prompt tokens in chunks of 8 and 3 decode steps
    (positions 11, 12, 13), a window of 16, three sliding layers: nothing
    is behind the window yet, so a call reads every row so far."""
    _, model, params = _setup()
    eng = _engine(model, params)
    h = eng.submit(np.arange(11, dtype=np.int32), 4)
    eng.run_until_complete()
    st = eng.metrics()["stats"]
    assert h.ok and st["prefill_chunks"] == 2 and st["decode_steps"] == 3
    # rows: the chunks read 8 and 11, the decode runs 12, 13, 14
    assert st["window_rows_read"] == 3 * (8 + 11 + 12 + 13 + 14)
    # pairs: a query at q sees q + 1 keys (q < 16): 1..11, then 12, 13, 14
    assert st["window_pairs"] == 3 * (sum(range(1, 12)) + 12 + 13 + 14)
    assert st["window_pages_live"] == 2 * 3  # two pages at each decode run
    assert st.get("window_pages_freed", 0) == 0
    assert st["moe_layer_runs"] == (2 + 3) * 4
    assert st["moe_rows"] == st["moe_rows_held"] == (11 + 3) * 2 * 4
    eng.close()


def test_a_long_request_frees_the_pages_behind_its_window():
    """76 positions through a window of 16 on 8-token pages: the slot never
    holds more than three window pages, frees seven, and the counters
    price a call at the window, not at the depth."""
    _, model, params = _setup()
    eng = _engine(model, params, num_slots=1)
    h = eng.submit(np.arange(40, dtype=np.int32) % 256, 37)
    ms = eng._mstates[None]
    while eng.slots_in_use or eng.queue_depth:
        eng.step()
        mapped = np.nonzero(ms.wtable[0] >= 0)[0]
        assert len(mapped) <= 3
        if len(mapped):  # contiguous, and it ends at the page being written
            assert mapped[-1] - mapped[0] == len(mapped) - 1
    st = eng.metrics()["stats"]
    assert h.ok and st["window_pages_freed"] == 7
    assert st["window_pages_live"] <= 3 * st["decode_steps"]
    # a decode run at depth >= 15 reads 16 rows a layer and no more
    assert st["window_rows_read"] < 3 * (st["decode_steps"] * 16
                                         + 5 * (8 + 15))
    eng.close()


FAULTS = {
    # the sliding layers masked at half their window
    "window_halved": lambda cfg: dataclasses.replace(
        cfg, sliding_window=cfg.sliding_window // 2),
    # the full layers' RoPE applied on the sliding layers
    "rope_swapped": lambda cfg: dataclasses.replace(
        cfg, rope_sliding=cfg.rope_full)}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_leaves_the_reference(fault):
    """The system under a damaged configuration, the reference under the
    published one: the engine's tokens and the library's logits leave the
    reference by far more than rounding."""
    config, model, params = _setup()
    bad = Laguna(FAULTS[fault](model.config))
    prompt = np.random.default_rng(2).integers(0, 256, 50).astype(np.int32)
    tokens = jnp.asarray(prompt)[None]
    got = bad.apply({"params": params}, tokens)
    want, _ = _reference(config, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * ATOL
    eng = _engine(bad, params)
    h = eng.submit(prompt, 20)
    eng.run_until_complete()
    gap = _gaps(config, model, params, prompt, np.asarray(h.tokens))
    assert h.ok and gap.max() > 100 * ATOL, gap
    eng.close()


def test_a_window_page_released_early_is_caught(monkeypatch):
    """The third fault: the host frees a window page one page too soon,
    so queries attend the scratch page where their window's oldest keys
    were.  The same request through a sound engine stays on the
    reference; through the faulty one it leaves it."""
    config, model, params = _setup()
    prompt = np.random.default_rng(3).integers(0, 256, 50).astype(np.int32)

    def served():
        eng = _engine(model, params, t_page=WINDOW, num_slots=1)
        h = eng.submit(prompt, 24)
        eng.run_until_complete()
        eng.close()
        assert h.ok
        return np.asarray(h.tokens)

    sound = served()
    assert _gaps(config, model, params, prompt, sound).max() <= ATOL
    dead = Engine._dead_window_pages
    monkeypatch.setattr(Engine, "_dead_window_pages", staticmethod(
        lambda start, window, page: dead(start, window, page) + 1))
    early = served()
    assert _gaps(config, model, params, prompt, early).max() > 100 * ATOL
    assert (early != sound).any()


# ------------------------------------------------------------ the router


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_untrained_router_is_near_even(seed):
    """Where the module starts (``laguna.EMBED_STD``) a token's stream is
    mostly its own embedding and the 64 experts of a layer are chosen
    nearly alike over seeds; with flax's 1/sqrt(d) embedding every token
    carries the first attention layer's common output and a few experts
    take most of the choices."""
    config, _, params = _setup(
        seed, hidden_size=128, num_experts=64, num_experts_per_tok=4)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 256, (1, 128)))

    def top_share(p):
        _, scores = _reference(config, p, tokens)
        shares = []
        for s in scores:
            chosen = np.argsort(-np.asarray(s), -1)[:, :4]
            counts = np.sort(np.bincount(chosen.ravel(), minlength=64))
            shares.append(counts[-8:].sum() / counts.sum())  # even: 0.125
        return max(shares)

    assert top_share(params) < 0.3
    small = dict(params)
    small["wte"] = {"embedding": params["wte"]["embedding"] / 128 ** .5}
    assert top_share(small) > top_share(params)


# -------------------------------------------------------------- refusals


REFUSED = [
    ("kv_pages", {"kv_pages": 0}),
    ("kv_pages", {"kv_pages": 35}),  # under 3 slots' full reservation
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("speculate_k", {"speculate_k": 2}),
    ("speculate_tree", {"speculate_k": 2, "speculate_tree": "fork2x2"}),
    ("decode_fuse", {"decode_fuse": 4}),
    ("paged_attn", {"paged_attn": "gather"})]


@pytest.mark.parametrize("option, kw", REFUSED,
                         ids=[f"{o}={list(k.values())[-1]}"
                              for o, k in REFUSED])
def test_the_engine_refuses_what_the_family_does_not_serve(option, kw):
    _, model, params = _setup()
    with pytest.raises(ValueError, match=rf"Engine\({option}=\.\.\.\) is "
                       "not served for the window-and-full-attention"):
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
    cfg = LagunaConfig()
    assert gen.page_layout(cfg) == "windowed"
    assert gen.page_type(cfg) is gen.WindowedPages
    assert gen.WindowedPages.geometry(cfg) == (
        "windowed", (False, True, True, True, False), 16, 2, 16, "float32")
    with pytest.raises(ValueError, match="kv_dtype"):
        gen.page_type(cfg, "int8")
    both = gen.WindowedPages.zeros(cfg, 5, 4)
    assert both.full.k.shape == (2, 5, 4, 32) == both.window.k.shape[:0] + (
        2, 5, 4, 32)
    assert both.window.k.shape == (3, 5, 4, 32)
    sized = gen.WindowedPages.zeros(cfg, 5, 4, 3)
    assert sized.full.v.shape == (2, 5, 4, 32)
    assert sized.window.v.shape == (3, 3, 4, 32)
    assert cfg.pool_layer(0) == ("full", 0) and cfg.pool_layer(4) == (
        "full", 1)
    assert [cfg.pool_layer(i) for i in (1, 2, 3)] == [
        ("window", 0), ("window", 1), ("window", 2)]
    with pytest.raises(ValueError, match="entries for 5 layers"):
        LagunaConfig(layer_types=(FULL, SLIDING))
