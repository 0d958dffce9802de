"""tpudp.serve: the continuous-batching engine's contract.

The two properties everything else rests on:

  1. GREEDY PARITY — every request's tokens from the engine are
     bit-identical to a standalone ``generate()`` with the same params,
     regardless of admission order, prompt-length mix, co-resident
     requests, or slot reuse after retirement (the slot-masked decode
     must be exactly the per-request math, just batched).
  2. STATIC SHAPES — the jitted decode step compiles exactly once per
     (config, num_slots, max_len); admission/retirement churn never
     recompiles (TRACE_COUNTS observes trace-time side effects).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import generate
from tpudp.models.gpt2 import gpt2_small
from tpudp.serve import Engine, TRACE_COUNTS
from tpudp.train import init_state, make_optimizer

TINY = dict(vocab_size=61, max_seq_len=64, num_layers=2, num_heads=2,
            d_model=32)


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt2_small(**TINY)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


def _reference(model, params, prompt, n):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]), n))


def test_greedy_parity_staggered_admissions(model_and_params):
    """Five requests with mixed prompt lengths (several longer than the
    prefill chunk) staggered through a 2-slot engine: every output must
    equal its standalone generate(), and 5 > 2 slots forces retirement +
    slot reuse along the way."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY["vocab_size"], size=n)
               .astype(np.int32) for n in (5, 19, 3, 9, 24)]
    max_new = [6, 4, 8, 5, 7]

    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8)
    handles = [eng.submit(prompts[0], max_new[0])]
    eng.step()
    eng.step()  # request 0 mid-flight before anyone else arrives
    handles.append(eng.submit(prompts[1], max_new[1]))
    handles.append(eng.submit(prompts[2], max_new[2]))
    eng.step()
    handles.append(eng.submit(prompts[3], max_new[3]))
    handles.append(eng.submit(prompts[4], max_new[4]))
    eng.run_until_complete()

    for p, n, h in zip(prompts, max_new, handles):
        ref = _reference(model, params, p, n)
        got = np.concatenate([p, np.asarray(h.tokens, np.int32)])
        np.testing.assert_array_equal(ref[0], got)
    assert eng.stats["completed"] == 5


def test_decode_step_compiles_once_across_churn(model_and_params):
    """The static-shape invariant: a fresh engine geometry compiles the
    decode step exactly once, and admitting/retiring many requests with
    different prompt lengths, sampling params, and slot assignments
    never triggers a recompile."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    # A geometry no other test uses, so the module-level jit cache cannot
    # have compiled it already.
    eng = Engine(model, params, num_slots=3, max_len=40, prefill_chunk=8)
    h = eng.submit(rng.integers(0, 61, size=4).astype(np.int32), 3)
    while not h.done:
        eng.step()
    base_decode = TRACE_COUNTS["decode_step"]
    base_prefill = TRACE_COUNTS["prefill_chunk"]

    for i in range(6):  # 6 requests through 3 slots: reuse + churn
        eng.submit(rng.integers(0, 61, size=3 + 5 * (i % 3))
                   .astype(np.int32), 2 + i,
                   temperature=0.5 * (i % 2), top_k=4 if i % 2 else None,
                   seed=i)
    eng.run_until_complete()
    assert TRACE_COUNTS["decode_step"] == base_decode
    assert TRACE_COUNTS["prefill_chunk"] == base_prefill


def test_parity_after_masked_garbage_accumulation(model_and_params):
    """The overwrite-before-visible invariant, adversarially: while slot 0
    decodes alone, every masked decode step writes garbage KV into slot
    1's row at its current depth; a long prompt (3 chunks, padded final
    chunk) then admitted into slot 1 must still decode bit-identically —
    every position its queries can see was rewritten by its own
    prefill/decode before becoming visible."""
    model, params = model_and_params
    rng = np.random.default_rng(9)
    p0 = rng.integers(0, 61, size=4).astype(np.int32)
    p1 = rng.integers(0, 61, size=21).astype(np.int32)

    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8)
    h0 = eng.submit(p0, 20)
    for _ in range(9):  # slot 0 solo; slot 1's row accumulates garbage
        eng.step()
    h1 = eng.submit(p1, 12)
    eng.run_until_complete()
    np.testing.assert_array_equal(
        _reference(model, params, p0, 20)[0, 4:], np.asarray(h0.tokens))
    np.testing.assert_array_equal(
        _reference(model, params, p1, 12)[0, 21:], np.asarray(h1.tokens))


def test_generate_many_matches_generate(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (4, 12, 7)]
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    outs = eng.generate_many(prompts, 5)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(_reference(model, params, p, 5)[0], o)


def test_streaming_iterator_and_token_order(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(3)
    p = rng.integers(0, 61, size=6).astype(np.int32)
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    h = eng.submit(p, 6)
    streamed = list(h)  # iteration drives the engine
    assert h.done
    assert streamed == h.tokens
    np.testing.assert_array_equal(
        _reference(model, params, p, 6)[0, 6:], np.asarray(streamed))


def test_eos_retirement_and_slot_recycling(model_and_params):
    """A sampled EOS retires the request early (eos included, trailing
    budget unused) and frees its slot for the queued request."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    p = rng.integers(0, 61, size=5).astype(np.int32)
    ref = _reference(model, params, p, 8)[0, 5:]
    eos = int(ref[3])
    first_hit = int(np.nonzero(ref == eos)[0][0])

    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8)
    h = eng.submit(p, 8, eos_id=eos)
    q = eng.submit(rng.integers(0, 61, size=4).astype(np.int32), 3)
    eng.run_until_complete()
    assert h.tokens == ref[:first_hit + 1].tolist()  # stops AT the eos
    assert h.done and q.done and len(q.tokens) == 3
    assert eng.stats["completed"] == 2


def test_sampled_requests_reproducible_and_coresident_independent(
        model_and_params):
    """Per-slot key chains: a sampled request's tokens depend only on its
    own seed/params — not on admission order or which other requests
    share the arena (each slot's chain advances once per OWN token)."""
    model, params = model_and_params
    rng = np.random.default_rng(5)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    def tokens_of(crowded):
        eng = Engine(model, params, num_slots=3, max_len=32,
                     prefill_chunk=8)
        if crowded:
            eng.submit(rng.integers(0, 61, size=7).astype(np.int32), 9,
                       temperature=1.3, seed=99)
        h = eng.submit(p, 8, temperature=0.9, top_k=12, top_p=0.9, seed=7)
        if crowded:
            eng.submit(rng.integers(0, 61, size=3).astype(np.int32), 4)
        eng.run_until_complete()
        return list(h.tokens)

    alone = tokens_of(False)
    assert tokens_of(False) == alone      # same seed -> same draws
    assert tokens_of(True) == alone       # co-residents don't perturb
    assert all(0 <= t < TINY["vocab_size"] for t in alone)


def test_submit_validation(model_and_params):
    model, params = model_and_params
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    p = np.zeros(30, np.int32)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(p, 10)  # 40 > 32
    with pytest.raises(ValueError, match="top_k/top_p"):
        eng.submit(p[:4], 2, top_k=5)
    with pytest.raises(ValueError, match="temperature"):
        eng.submit(p[:4], 2, temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(p[:4], 2, temperature=1.0, top_p=1.5)
    with pytest.raises(ValueError, match="eos_id"):
        eng.submit(p[:4], 2, eos_id=61)
    with pytest.raises(ValueError, match="prompt"):
        eng.submit(np.asarray([], np.int32), 2)
    moe = gpt2_small(**{**TINY, "mlp_impl": "moe", "num_experts": 2,
                        "capacity_factor": 4.0})
    with pytest.raises(ValueError, match="dense"):
        Engine(moe, params, num_slots=2)
    flash = gpt2_small(**{**TINY, "attn_impl": "flash"})
    with pytest.raises(ValueError, match="dense"):
        Engine(flash, params, num_slots=2)


@pytest.mark.slow
def test_llama_family_greedy_parity():
    """The engine serves the other decoder lineage too: RoPE positions
    per slot depth, GQA-width arena rows."""
    from tpudp.models.llama import llama_small

    model = llama_small(vocab_size=61, max_seq_len=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, d_model=32)
    params = init_state(model, make_optimizer(),
                        input_shape=(1, 8)).params
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (4, 11, 17)]
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    outs = eng.generate_many(prompts, 6)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(_reference(model, params, p, 6)[0], o)


def test_sample_tokens_masks():
    """The masked-sampling op row-wise: greedy rows ignore the key;
    top_k=1 collapses to greedy; a tiny nucleus keeps only the argmax;
    disabled rows (k=0, p=1) sample the full vocab in range."""
    import jax

    from tpudp.ops.sampling import sample_tokens

    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(4, 33)), jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4, dtype=jnp.uint32))
    greedy = np.asarray(jnp.argmax(logits, -1))

    toks = np.asarray(sample_tokens(
        logits,
        jnp.asarray([0.0, 2.0, 2.0, 1.0], jnp.float32),
        jnp.asarray([0, 1, 0, 0], jnp.int32),       # row1: top_k=1
        jnp.asarray([1.0, 1.0, 1e-6, 1.0], jnp.float32),  # row2: tiny p
        keys))
    assert toks[0] == greedy[0]   # temperature 0 -> argmax
    assert toks[1] == greedy[1]   # top_k=1 -> argmax at any temperature
    assert toks[2] == greedy[2]   # nucleus always keeps the argmax
    assert 0 <= toks[3] < 33

    # all-greedy batch takes the argmax-only branch (the lax.cond fast
    # path) and must still match row-wise argmax exactly
    all_greedy = np.asarray(sample_tokens(
        logits, jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4), keys))
    np.testing.assert_array_equal(all_greedy, greedy)


# Demoted to slow (PR 20 durations audit): the combined top_k+top_p
# sampling semantics are covered fast by
# tests/test_generate.py::test_top_k_and_top_p_sampling.
@pytest.mark.slow
def test_combined_top_k_top_p_composes_like_truncate_logits():
    """top_k THEN nucleus-over-the-renormalized-distribution — the same
    composition as generate()'s _truncate_logits.  Pinned with the case
    that separates the orders: probs (0.4, 0.35, 0.25), k=2, p=0.5 keeps
    ONLY the argmax (renormalized preceding mass of token 1 is 0.533 >=
    0.5); a full-vocab nucleus would wrongly keep {0, 1}.  With k=2
    keeping {0, 1} the sampler can only ever emit token 0."""
    import jax

    from tpudp.ops.sampling import sample_tokens

    logits = jnp.log(jnp.asarray([[0.4, 0.35, 0.25]], jnp.float32))
    for seed in range(20):
        tok = np.asarray(sample_tokens(
            logits, jnp.asarray([1.0], jnp.float32),
            jnp.asarray([2], jnp.int32), jnp.asarray([0.5], jnp.float32),
            jax.random.PRNGKey(seed)[None]))
        assert tok[0] == 0, (seed, tok)


