"""The power-retention family (tpudp/ops/retention.py, tpudp/models/
brumby.py, the engine's slot arena of states): the chunked and recurrent
forms against the quadratic reference, the Mosaic decode kernel
(interpreted) against its XLA twin, the library forward and the engine
against the flax module across folds and prefill chunks, padding that
leaves a slot's state bit for bit, a used slot that starts fresh, two
slots that do not interfere, the published layer's parameter count, and
each refused option by name."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.brumby import (Brumby, BrumbyConfig, parameters_per_layer,
                                 state_bytes)
from tpudp.models.generate import RetentionState, _forward_state, generate
from tpudp.ops import retention as ret
from tpudp.serve import Engine
from tpudp.serve.engine import RETENTION_STATS, TRACE_COUNTS

EPS = 1e-6


def _inputs(t, h=4, kv=2, dh=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, t, h, dh))
    k = jax.random.normal(ks[1], (2, t, kv, dh))
    v = jax.random.normal(ks[2], (2, t, kv, dh))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (2, t, kv)) + 3.0)
    return q, k, v, log_g


def test_the_feature_map_is_the_squared_product():
    """``phi(q) . phi(k) = (q . k / sqrt(dh))^2`` with ``dh (dh + 1) / 2``
    entries: every unordered pair once, in the cyclic-offset order."""
    q, k, _, _ = _inputs(5, dh=16)
    f = ret.sym_features(q[:, :, :2]) * ret.sym_features(k)
    want = jnp.einsum("btkd,btkd->btk", q[:, :, :2], k) ** 2 / 16
    assert f.shape[-1] == ret.features(16) == 136
    np.testing.assert_allclose(f.sum(-1), want, rtol=1e-5, atol=1e-5)
    assert ret.features(128) == 8256


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
def test_the_chunked_form_is_the_quadratic_form(chunk, seed):
    """37 positions: the last chunk ends mid-way (padding rows leave the
    state as it was), and at 64 the whole sequence is one chunk."""
    q, k, v, log_g = _inputs(37, seed=seed)
    want = ret.retention_reference(q, k, v, log_g, eps=EPS)
    got = ret.retention_chunked(q, k, v, log_g, chunk=chunk, eps=EPS)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _recurrent(q, k, v, log_g, c, impl):
    """Token by token through a tail of ``c`` rows, folded when full (a
    ``lax.scan``: the form the engine's decode steps make, in order)."""
    b, t, h, dh = q.shape
    kv = k.shape[2]
    D = ret.features(dh)

    def step(carry, xs):
        S, z, tk, tv, tg = carry
        i, qi, ki, vi, gi = xs
        at = i % c
        tk, tv = tk.at[:, :, at].set(ki), tv.at[:, :, at].set(vi)
        before = jnp.where(at > 0, tg[:, :, jnp.maximum(at - 1, 0)], 0.0)
        tg = tg.at[:, :, at].set(before + gi)
        y = ret.retention_decode(qi.reshape(b, kv, h // kv, dh), S, z, tk, tv,
                                 tg, jnp.full((b,), at + 1), eps=EPS,
                                 impl=impl)
        s2, z2 = ret.chunk_fold(tk.reshape(b * kv, c, dh),
                                tv.reshape(b * kv, c, dh),
                                tg.reshape(b * kv, c),
                                S.reshape(b * kv, D, dh),
                                z.reshape(b * kv, D), c)
        full = at == c - 1
        S = jnp.where(full, s2.reshape(S.shape), S)
        z = jnp.where(full, z2.reshape(z.shape), z)
        return (S, z, tk, tv, tg), y.reshape(b, h, dh)

    init = (jnp.zeros((b, kv, D, dh)), jnp.zeros((b, kv, D)),
            jnp.zeros((b, kv, c, dh)), jnp.zeros((b, kv, c, dh)),
            jnp.zeros((b, kv, c)))
    _, ys = jax.lax.scan(step, init, (
        jnp.arange(t), *(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_g))))
    return jnp.moveaxis(ys, 0, 1)


@pytest.mark.parametrize("impl", ["einsum", "kernel"])
def test_the_recurrent_form_is_the_quadratic_form(impl):
    """Three folds and a partial tail; the kernel in interpret mode."""
    q, k, v, log_g = _inputs(29)
    want = ret.retention_reference(q, k, v, log_g, eps=EPS)
    np.testing.assert_allclose(_recurrent(q, k, v, log_g, 8, impl), want,
                               rtol=2e-5, atol=2e-5)


def test_the_kernel_is_its_einsum_twin_on_a_whole_stack():
    """One Mosaic call (interpreted) against the XLA form on random
    state, normalisers and tails of a layer of a stacked arena, slots at
    every tail length and one idle slot (zeros, its state not read)."""
    rng = np.random.default_rng(0)
    L, b, kv, g, dh, c = 3, 5, 2, 3, 16, 8
    D = ret.features(dh)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    S, z = arr(L, b, kv, D, dh), jnp.abs(arr(L, b, kv, D)) * 10
    tk, tv = arr(L, b, kv, c, dh), arr(L, b, kv, c, dh)
    tg = -jnp.cumsum(jnp.abs(arr(L, b, kv, c, scale=0.1)), -1)
    q, n = arr(b, kv, g, dh), jnp.asarray([1, 8, 0, 3, 5], jnp.int32)
    args = (q, S, z, tk.astype(jnp.bfloat16), tv.astype(jnp.bfloat16), tg, n)
    got = ret.retention_decode(*args, eps=EPS, impl="kernel", layer=1)
    want = ret.retention_decode(*args, eps=EPS, impl="einsum", layer=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got[2]))


CFG = BrumbyConfig(fold_tokens=8)


@pytest.fixture(scope="module")
def model():
    m = Brumby(CFG)
    return m, m.init(jax.random.PRNGKey(0),
                     jnp.zeros((1, 4), jnp.int32))["params"]


@jax.jit
def _chunk(params, state, buf, start, slot, last):
    return _forward_state(CFG, params, buf, state, start,
                          jnp.ones((1,), bool), slot=slot, last=last,
                          every_row=True)


def _prefill(params, state, slot, tokens, chunk, pad=0):
    logits = []
    for start in range(0, tokens.size, chunk):
        n = min(chunk, tokens.size - start)
        buf = np.full((1, chunk), pad, np.int32)
        buf[0, :n] = tokens[start:start + n]
        lg, state = _chunk(params, state, buf, np.int32(start),
                           np.int32(slot), np.int32(n - 1))
        logits.append(np.asarray(lg[0, :n]))
    return np.concatenate(logits), state


@pytest.mark.parametrize("impl", ["einsum", "kernel"])
def test_the_library_forward_is_the_module(model, impl):
    """A 37-token prompt in three 16-token chunks (two folds inside a
    chunk, four in all, the last chunk padded), then 12 decoded positions
    across two more folds, in slot 1 of three: every position's logits
    against the flax module's (float32)."""
    m, params = model
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (49,), 0,
                                         CFG.vocab_size), np.int32)
    want = np.asarray(m.apply({"params": params}, toks[None]))[0]
    got, state = _prefill(params, RetentionState.zeros(CFG, 3), 1,
                          toks[:37], 16, pad=7)
    decode = jax.jit(lambda p, st, tk, pos, act: _forward_state(
        CFG, p, tk, st, pos, act, impl))
    rows = [got]
    for t in range(37, 49):
        tk = np.zeros((3, 1), np.int32)
        tk[1] = toks[t]
        lg, state = decode(params, state, tk, np.asarray([5, t, 0], np.int32),
                           np.asarray([False, True, False]))
        rows.append(np.asarray(lg[1]))
    np.testing.assert_allclose(np.concatenate(rows), want, atol=2e-5)


def test_padding_rows_leave_the_state_bit_identical(model):
    """A prompt ending mid-chunk: whatever the padding rows hold, the
    slot's state, normaliser and tail come out bit for bit the same, and
    the other slots' not at all touched."""
    _, params = model
    toks = np.arange(3, 24, dtype=np.int32)  # 21: a partial fold block
    base = RetentionState.zeros(CFG, 2)
    _, a = _prefill(params, base, 0, toks, 16, pad=0)
    _, b = _prefill(params, base, 0, toks, 16, pad=201)
    for x, y, zero in zip(a, b, base):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(x[:, 1]),
                                      np.asarray(zero[:, 1]))


def _engine(params, model, **kw):
    return Engine(model, params, **{"num_slots": 2, "max_len": 96,
                                    "prefill_chunk": 16, **kw})


def _slot_state(engine, s):
    return [np.asarray(x[:, s]) for x in engine._mstates[None].cache]


def test_a_used_slot_starts_from_zero_state(model):
    """One slot: request B admitted after A served there gives the tokens
    and leaves the state it does on a fresh engine (the chunk at position
    0 starts the slot at zero: no dispatch of its own)."""
    m, params = model
    a = np.arange(40, dtype=np.int32) % CFG.vocab_size
    b = (np.arange(29, dtype=np.int32) * 7) % CFG.vocab_size
    used = _engine(params, m, num_slots=1)
    used.submit(a, 10)
    hb = used.submit(b, 10)
    used.run_until_complete()
    fresh = _engine(params, m, num_slots=1)
    hf = fresh.submit(b, 10)
    fresh.run_until_complete()
    assert hb.tokens == hf.tokens and len(hb.tokens) == 10
    for x, y in zip(_slot_state(used, 0), _slot_state(fresh, 0)):
        np.testing.assert_array_equal(x, y)


def test_two_slots_do_not_interfere(model):
    """Served together, each request's tokens and final state are what it
    gives alone; the greedy tokens are the module's argmax."""
    m, params = model
    prompts = [np.arange(5, 42, dtype=np.int32),
               np.arange(60, 79, dtype=np.int32)]
    both = _engine(params, m)
    hs = [both.submit(p, 14) for p in prompts]
    both.run_until_complete()
    for i, p in enumerate(prompts):
        alone = _engine(params, m)
        if i:  # a one-token request takes slot 0: p is in slot 1 again
            alone.submit(p[:3], 1)
        h = alone.submit(p, 14)
        alone.run_until_complete()
        assert h.tokens == hs[i].tokens
        seq = np.concatenate([p, np.asarray(h.tokens[:-1])])
        ref = np.asarray(m.apply({"params": params}, seq[None]))[0]
        assert list(ref[p.size - 1:].argmax(-1)) == h.tokens
    st = both.metrics()
    assert set(RETENTION_STATS) <= set(st["stats"])
    assert st["stats"]["retention_prefill_tokens"] == sum(p.size for p in
                                                          prompts)
    assert st["stats"]["retention_folds"] > 0
    assert st["retention_state_bytes"] == state_bytes(CFG, 2)


def test_the_engine_runs_the_two_programs_once(model):
    m, params = model
    before = dict(TRACE_COUNTS)
    eng = _engine(params, m, paged_attn="kernel")
    for n in (9, 33, 17):
        eng.submit(np.arange(n, dtype=np.int32), 5)
    eng.run_until_complete()
    grew = {k: TRACE_COUNTS[k] - before.get(k, 0) for k in TRACE_COUNTS
            if TRACE_COUNTS[k] != before.get(k, 0)}
    assert set(grew) <= {"decode_retention_kernel", "prefill_retention"}
    assert all(v <= 1 for v in grew.values())
    assert eng.metrics()["retention"]["resolved"] == "kernel"


@pytest.mark.parametrize("option, value", [
    ("kv_pages", 8), ("kv_dtype", "int8"), ("prefix_cache_blocks", 4),
    ("speculate_k", 2), ("speculate_tree", "chain2"), ("decode_fuse", 2),
    ("paged_attn", "gather"), ("prefill_chunk", 12)])
def test_each_refused_option_names_itself(model, option, value):
    m, params = model
    with pytest.raises(ValueError, match=rf"Engine\({option}=\.\.\.\)"):
        _engine(params, m, **{option: value})


def test_models_tickets_and_generate_are_refused(model):
    m, params = model
    with pytest.raises(ValueError, match=r"Engine\(models=\.\.\.\)"):
        _engine(params, m, models={"x": (m, params)})
    eng = _engine(params, m)
    r = eng.submit(np.arange(5, dtype=np.int32), 3)
    with pytest.raises(ValueError, match="not a slot's state"):
        eng.export_ticket(r)
    with pytest.raises(ValueError, match="power-retention"):
        generate(m, params, jnp.zeros((1, 4), jnp.int32), 2)


def test_the_published_layer_has_its_parameter_count():
    """Brumby-14B-Base's widths: a layer is 330,352,896 parameters by the
    hand count and by the module's own tree (shapes only)."""
    cfg = BrumbyConfig(vocab_size=151936, hidden_size=5120,
                       intermediate_size=17408, num_hidden_layers=1,
                       num_attention_heads=40, num_key_value_heads=8,
                       head_dim=128, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16)
    tree = jax.eval_shape(lambda k: Brumby(cfg).init(
        k, jnp.zeros((1, 4), jnp.int32))["params"], jax.random.PRNGKey(0))
    layer = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree["h_0"]))
    assert layer == parameters_per_layer(cfg) == 330_352_896
    # 48 slots of four layers: 6.54 GB of float32 state, 0.10 of tails
    four = dataclasses.replace(cfg, num_hidden_layers=4)
    assert state_bytes(four, 48) == pytest.approx(6.642e9, rel=1e-3)


# every serve program of the lockfile before the power-retention family
# came, by the start of its fingerprint: the GPT-2, LLaMA, latent and
# windowed families' programs trace as they did (the audit test holds the
# tree to the lockfile); the two paged-decode kernel programs as they do
# since the kernel's grid became its visit list
PARENT_LOCK = {
    "serve.decode_paged@s2m32p6": "d2d61685bec1c5e8",
    "serve.decode_paged_kernel@s2m32p6": "a465f65edb911bf9",
    "serve.decode_paged_latent@s2m32p6": "6c5086a9a37c29b9",
    "serve.decode_paged_latent_kernel@s2m32p6": "6f67a2220118f3e4",
    "serve.decode_paged_windowed@s2m32p6": "67c337fd1254e9ea",
    "serve.decode_step@s2m32": "92869d8c11129d0b",
    "serve.draft_model@ctx16k3": "c6afc03209fb53b7",
    "serve.fused_decode@s2m32n4": "8dad0f5d988a4e21",
    "serve.fused_decode_paged@s2m32p6n4": "f3430b804c0d2658",
    "serve.fused_decode_paged_kernel@s2m32p6n4": "f6323f0f8608ed96",
    "serve.fused_decode_paged_stream@s2m32p6n4": "e9b36befc45ec9ab",
    "serve.fused_decode_stream@s2m32n4": "3ff1fab74a1a9be1",
    "serve.fused_spec_decode@s2m32k3n4": "a581c1c962a8229c",
    "serve.fused_spec_decode_stream@s2m32k3n4": "8e5ab1d0f33468b6",
    "serve.fused_spec_paged@s2m32p6k3n4": "3bb1731d7d7b32ba",
    "serve.fused_spec_paged_kernel@s2m32p6k3n4": "b11d40ca11fb38b7",
    "serve.fused_spec_paged_stream@s2m32p6k3n4": "185dc941af8c6bdd",
    "serve.prefill_chunk@s2m32c8": "a23afd46b282e8c4",
    "serve.prefill_paged@s2m32p6c8": "74dc2f514dfb93bc",
    "serve.prefill_paged_kernel@s2m32p6c8": "e7e554c0883b4c62",
    "serve.prefill_paged_latent@s2m32p6c8": "b3d1810e1e2d13c7",
    "serve.prefill_paged_latent_kernel@s2m32p6c8": "e753b91c5df97458",
    "serve.prefill_paged_windowed@s2m32p6c8": "de8a80bc663992c9",
    "serve.tree_verify@s2m32t5": "36c25b31cbcef227",
    "serve.tree_verify_paged@s2m32p6t5": "1ebb4b7fa399d603",
    "serve.tree_verify_paged_kernel@s2m32p6t5": "1598f12ec2b89e9a",
    "serve.verify_paged@s2m32p6k3": "2d06489eb381a58b",
    "serve.verify_paged_kernel@s2m32p6k3": "175e06f932497d8e",
    "serve.verify_step@s2m32k3": "347fcf398d352cd0",
}


def test_the_other_families_programs_trace_unchanged():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tools", "trace_lock.json")) as f:
        programs = json.load(f)["programs"]
    for name, start in PARENT_LOCK.items():
        assert programs[name]["fingerprint"].startswith(start), name
    serve = {n for n in programs if n.startswith("serve.")}
    assert serve - set(PARENT_LOCK) == {
        "serve.decode_retention@s2m32", "serve.decode_retention_kernel@s2m32",
        "serve.prefill_retention@s2m32c8"}
