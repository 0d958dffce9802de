"""Speculative decoding (tpudp.serve.speculate + the engine's verify
step): the contract is the serve engine's, extended.

  1. GREEDY PARITY — speculative output is bit-identical to standalone
     ``generate()`` AND to a non-speculative ``Engine`` for EVERY
     drafter and every k: drafts are hints, never correctness inputs
     (an adversarial drafter proposing garbage must change nothing but
     the speedup).  The per-position vmapped attention in the decode
     twins makes the k+1-token verify window bitwise-equal to k+1
     single-token steps, so this parity is structural, not a tolerance.
  2. DISTRIBUTION PRESERVATION — sampled rows use rejection sampling
     against the truncated target distribution (point-mass proposals),
     so the per-token output distribution is exactly the non-speculative
     one, and a seed fully reproduces a request's draws.
  3. STATIC SHAPES — the verify step compiles once per
     (config, num_slots, max_len, k); admission/retirement/cancellation
     churn never recompiles (TRACE_COUNTS observes this).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import generate
from tpudp.models.gpt2 import gpt2_small
from tpudp.serve import DraftModelDrafter, Engine, NgramDrafter, TRACE_COUNTS
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


class GarbageDrafter:
    """Adversarial drafter: always proposes k copies of an out-of-range
    id.  The robustness layer QUARANTINES it on first sight (out-of-
    vocab proposals are a drafter-contract violation) and the engine
    falls back to plain decode — output must be bit-identical anyway,
    with the garbage proposal charged as proposed-and-rejected."""

    def propose(self, context, k):
        return np.full(k, 10 ** 9, np.int64)


# -- drafters ----------------------------------------------------------


def test_ngram_drafter_repetitive_sequences():
    d = NgramDrafter(max_ngram=3)
    # Suffix [1, 2, 3] last occurred at the start; continuation is 4, 1, 2.
    ctx = np.array([1, 2, 3, 4, 1, 2, 3], np.int32)
    np.testing.assert_array_equal(d.propose(ctx, 3), [4, 1, 2])
    # k clamps to what the context holds after the match.
    np.testing.assert_array_equal(d.propose(ctx, 99), [4, 1, 2, 3])
    # Longest match wins: suffix [2, 9] beats the shorter [9] match.
    ctx = np.array([2, 9, 7, 9, 8, 2, 9], np.int32)
    np.testing.assert_array_equal(d.propose(ctx, 1), [7])
    # MOST RECENT match wins within one n.
    ctx = np.array([5, 1, 5, 2, 5], np.int32)
    np.testing.assert_array_equal(d.propose(ctx, 1), [2])
    # No repeated suffix -> no proposal; short contexts -> no proposal.
    assert d.propose(np.array([1, 2, 3], np.int32), 3).size == 0
    assert d.propose(np.array([7], np.int32), 3).size == 0
    assert d.propose(np.array([7, 7, 7], np.int32), 2).size == 2


def test_ngram_drafter_validation():
    with pytest.raises(ValueError, match="min_ngram"):
        NgramDrafter(min_ngram=0)
    with pytest.raises(ValueError, match="max_ngram"):
        NgramDrafter(max_ngram=1, min_ngram=2)


def test_draft_model_drafter_buckets_compile_once(model_and_params):
    """Context lengths sharing a power-of-two bucket share one compiled
    drafting program; a new bucket (or k) compiles exactly once."""
    model, params = model_and_params
    d = DraftModelDrafter(model, params)
    rng = np.random.default_rng(0)
    base = TRACE_COUNTS["draft_model"]
    for n in (5, 6, 7, 8):  # all bucket 8
        out = d.propose(rng.integers(0, 61, size=n).astype(np.int32), 3)
        assert out.shape == (3,) and out.dtype == np.int32
    assert TRACE_COUNTS["draft_model"] == base + 1
    d.propose(rng.integers(0, 61, size=9).astype(np.int32), 3)  # bucket 16
    assert TRACE_COUNTS["draft_model"] == base + 2


def test_drafter_vocab_mismatch_rejected(model_and_params):
    model, params = model_and_params
    other = gpt2_small(**{**TINY, "vocab_size": 17})
    other_params = init_state(other, make_optimizer(),
                              input_shape=(1, 8)).params
    with pytest.raises(ValueError, match="vocab"):
        Engine(model, params, num_slots=2, speculate_k=2,
               drafter=DraftModelDrafter(other, other_params))


# -- greedy parity -----------------------------------------------------


@pytest.mark.parametrize("k,drafter", [
    (1, "ngram"), (4, "ngram"), (3, "model"), (4, "garbage")])
def test_greedy_parity_speculative_staggered(model_and_params, k, drafter):
    """The serve suite's adversarial schedule — mixed prompt lengths,
    staggered admissions, retirement + slot reuse through 2 slots — with
    speculation on: every output bit-identical to generate() and to the
    non-speculative engine, for a useful drafter, a same-model drafter
    (acceptance 1), and a garbage drafter (acceptance 0)."""
    model, params = model_and_params
    drafter = {"ngram": None,
               "model": lambda: DraftModelDrafter(model, params),
               "garbage": GarbageDrafter}[drafter]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, TINY["vocab_size"], size=n)
               .astype(np.int32) for n in (5, 19, 3, 9, 24)]
    max_new = [6, 4, 8, 5, 7]

    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 speculate_k=k, drafter=drafter() if drafter else None)
    handles = [eng.submit(prompts[0], max_new[0])]
    eng.step()
    eng.step()
    handles.append(eng.submit(prompts[1], max_new[1]))
    handles.append(eng.submit(prompts[2], max_new[2]))
    eng.step()
    handles.append(eng.submit(prompts[3], max_new[3]))
    handles.append(eng.submit(prompts[4], max_new[4]))
    eng.run_until_complete()

    plain = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8)
    plain_handles = [plain.submit(p, n) for p, n in zip(prompts, max_new)]
    plain.run_until_complete()
    for p, n, h, ph in zip(prompts, max_new, handles, plain_handles):
        ref = _reference(model, params, p, n)
        got = np.concatenate([p, np.asarray(h.tokens, np.int32)])
        np.testing.assert_array_equal(ref[0], got)   # vs generate()
        assert h.tokens == ph.tokens                 # vs plain Engine
    assert eng.stats["completed"] == 5


def test_greedy_parity_eos_mid_window(model_and_params):
    """An accepted EOS mid-window retires the request AT the eos; the
    window's remaining emitted tokens are dropped (sequential decode
    would never have produced them) and the freed slot serves the queue."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    p = rng.integers(0, 61, size=5).astype(np.int32)
    ref = _reference(model, params, p, 8)[0, 5:]
    eos = int(ref[3])
    first_hit = int(np.nonzero(ref == eos)[0][0])

    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 speculate_k=4)
    h = eng.submit(p, 8, eos_id=eos)
    q = eng.submit(rng.integers(0, 61, size=4).astype(np.int32), 3)
    eng.run_until_complete()
    assert h.tokens == ref[:first_hit + 1].tolist()
    assert h.done and q.done and len(q.tokens) == 3


# Demoted to slow (PR 20 durations audit): the budget-clamp edge is
# exercised fast by the remaining speculate parity tests and
# tests/test_spec_fused.py at the same k>budget geometry.
@pytest.mark.slow
def test_greedy_parity_k_longer_than_budget(model_and_params):
    """speculate_k larger than a request's whole budget: emitted tokens
    beyond max_new_tokens are dropped, the rest match exactly."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (4, 12, 7)]
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 speculate_k=6)
    outs = eng.generate_many(prompts, 2)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(_reference(model, params, p, 2)[0], o)


def test_submit_bound_reserves_window_scratch(model_and_params):
    """The arena reserves speculate_k positions per slot: a request that
    fits a plain engine can overflow a speculative one (the window's
    rejected tail must never wrap past max_len)."""
    model, params = model_and_params
    p = np.zeros(20, np.int32)
    Engine(model, params, num_slots=1, max_len=32,
           prefill_chunk=8).submit(p, 12)  # exactly fits
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 speculate_k=4)
    with pytest.raises(ValueError, match="speculate_k"):
        eng.submit(p, 12)
    eng.submit(p, 8)  # 20 + 8 + 4 = 32 fits
    with pytest.raises(ValueError, match="speculate_k"):
        Engine(model, params, num_slots=1, max_len=8, prefill_chunk=8,
               speculate_k=8)
    with pytest.raises(ValueError, match="drafter requires"):
        Engine(model, params, num_slots=1, drafter=NgramDrafter())


# -- sampling ----------------------------------------------------------


def test_sampled_speculation_reproducible_and_independent(model_and_params):
    """Same seed -> same draws with speculation on, regardless of
    co-residents (per-slot key chains advance once per OWN verify
    window, drafts depend only on own context)."""
    model, params = model_and_params
    rng = np.random.default_rng(5)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    def tokens_of(crowded):
        eng = Engine(model, params, num_slots=3, max_len=32,
                     prefill_chunk=8, speculate_k=3)
        if crowded:
            eng.submit(rng.integers(0, 61, size=7).astype(np.int32), 9,
                       temperature=1.3, seed=99)
        h = eng.submit(p, 8, temperature=0.9, top_k=12, top_p=0.9, seed=7)
        if crowded:
            eng.submit(rng.integers(0, 61, size=3).astype(np.int32), 4)
        eng.run_until_complete()
        return list(h.tokens)

    alone = tokens_of(False)
    assert len(alone) == 8
    assert tokens_of(False) == alone
    assert tokens_of(True) == alone
    assert all(0 <= t < TINY["vocab_size"] for t in alone)


def test_verify_tokens_greedy_rule():
    """The acceptance rule directly: longest draft prefix matching the
    target argmax, plus the free correction/bonus token."""
    from tpudp.ops.sampling import verify_tokens

    v = 7
    # Row 0: targets [3, 4, 5, 6]; drafts [3, 4, 9%v] -> accept 2, emit
    # [3, 4, 5].  Row 1: n_draft=0 -> plain decode, emit [2].
    # Row 2: all 3 drafts accepted -> emit 4 incl. the bonus target.
    logits = np.full((3, 4, v), -10.0, np.float32)
    for j, t in enumerate([3, 4, 5, 6]):
        logits[0, j, t] = 0.0
    logits[1, 0, 2] = 0.0
    for j, t in enumerate([1, 2, 3, 4]):
        logits[2, j, t] = 0.0
    draft = np.array([[3, 4, 2], [0, 0, 0], [1, 2, 3]], np.int32)
    n_draft = np.array([3, 0, 3], np.int32)
    zeros = jnp.zeros(3)
    keys = jnp.zeros((3, 2), jnp.uint32)
    toks, n_emit = verify_tokens(
        jnp.asarray(logits), jnp.asarray(draft), jnp.asarray(n_draft),
        zeros, jnp.zeros(3, jnp.int32), jnp.ones(3), keys)
    toks, n_emit = np.asarray(toks), np.asarray(n_emit)
    assert n_emit.tolist() == [3, 1, 4]
    assert toks[0, :3].tolist() == [3, 4, 5]
    assert toks[1, :1].tolist() == [2]
    assert toks[2].tolist() == [1, 2, 3, 4]


def test_verify_tokens_rejection_preserves_distribution():
    """Rejection sampling with a point-mass proposal: the first emitted
    token's distribution must equal plain sampling from the target
    softmax NO MATTER what the draft proposes (here: always token 0,
    which has low probability).  Empirical check over many keys."""
    from tpudp.ops.sampling import verify_tokens

    logits = jnp.asarray(
        np.log(np.array([0.05, 0.5, 0.25, 0.15, 0.05], np.float32)))
    n = 4000
    lg = jnp.broadcast_to(logits[None, None, :], (n, 2, 5))
    draft = jnp.zeros((n, 1), jnp.int32)  # always propose token 0
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n, dtype=jnp.uint32))
    toks, _ = verify_tokens(lg, draft, jnp.ones(n, jnp.int32),
                            jnp.ones(n), jnp.zeros(n, jnp.int32),
                            jnp.ones(n), keys)
    first = np.asarray(toks)[:, 0]
    freq = np.bincount(first, minlength=5) / n
    np.testing.assert_allclose(freq, [0.05, 0.5, 0.25, 0.15, 0.05],
                               atol=0.03)


def test_truncation_static_and_dynamic_paths_agree():
    """The dedupe satellite's referee: generate()'s static
    ``_truncate_logits`` wrapper and the serve path's traced
    ``truncate_logits`` produce bitwise-identical masks for every
    (top_k, top_p) combination — one implementation, zero drift."""
    from tpudp.models.generate import _truncate_logits
    from tpudp.ops.sampling import truncate_logits

    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(5, 33)), jnp.float32)
    for top_k, top_p in [(None, 0.7), (4, None), (4, 0.7), (1, 0.01),
                         (40, 1.0), (None, None)]:
        static = _truncate_logits(logits, top_k, top_p)
        dyn = truncate_logits(
            logits, jnp.full((5,), top_k or 0, jnp.int32),
            jnp.full((5,), 1.0 if top_p is None else top_p, jnp.float32))
        np.testing.assert_array_equal(np.asarray(static), np.asarray(dyn))


# -- static shapes -----------------------------------------------------


def test_verify_step_compiles_once_across_churn(model_and_params):
    """The static-shape invariant, speculation edition: one verify-step
    compile per engine geometry; admission, retirement, cancellation,
    and draft-length churn (0..k drafts per row) never recompile."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    # A geometry no other test uses (the module-level jit cache is shared).
    eng = Engine(model, params, num_slots=3, max_len=40, prefill_chunk=8,
                 speculate_k=2)
    pre_verify = TRACE_COUNTS["verify_step"]

    def churn(seed0):
        for i in range(6):
            eng.submit(rng.integers(0, 61, size=3 + 5 * (i % 3))
                       .astype(np.int32), 2 + i,
                       temperature=0.5 * (i % 2),
                       top_k=4 if i % 2 else None, seed=seed0 + i)
        eng.step()
        victim = next(r for r in eng._slots if r is not None)
        eng.cancel(victim)
        eng.run_until_complete()

    # First batch is the warmup: it exercises drafted steps (verify
    # program), no-draft steps (the fall-through decode program), both
    # sampling modes, and a cancellation — everything the engine can
    # dispatch to.  A repetitive extra prompt forces at least one
    # drafted window even if the random outputs never repeat.
    eng.submit(np.array([7, 7, 7, 7], np.int32), 4).result()
    churn(0)
    base_verify = TRACE_COUNTS["verify_step"]
    base_decode = TRACE_COUNTS["decode_step"]
    base_prefill = TRACE_COUNTS["prefill_chunk"]
    assert base_verify > pre_verify  # the repetitive prompt did speculate

    # Second batch: identical churn, zero new traces allowed.
    churn(6)
    assert TRACE_COUNTS["verify_step"] == base_verify
    assert TRACE_COUNTS["decode_step"] == base_decode
    assert TRACE_COUNTS["prefill_chunk"] == base_prefill
    assert eng.stats["cancelled"] == 2


# -- cancellation ------------------------------------------------------


def test_cancel_frees_slot_and_reuse_is_clean(model_and_params):
    """Cancelling an in-flight request frees its slot immediately; the
    next request reuses the slot with clean KV (bit-parity referee)."""
    model, params = model_and_params
    rng = np.random.default_rng(8)
    p1 = rng.integers(0, 61, size=5).astype(np.int32)
    p2 = rng.integers(0, 61, size=9).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8)
    h1 = eng.submit(p1, 20)
    for _ in range(4):
        eng.step()
    assert not h1.done and eng.slots_in_use == 1
    emitted_before = list(h1.tokens)
    assert eng.cancel(h1) is True
    assert h1.done and h1.cancelled and eng.slots_in_use == 0
    assert h1.tokens == emitted_before  # nothing appended after cancel
    assert eng.cancel(h1) is False  # idempotent
    h2 = eng.submit(p2, 6)
    eng.run_until_complete()
    np.testing.assert_array_equal(
        _reference(model, params, p2, 6)[0, 9:], np.asarray(h2.tokens))
    # result() on a cancelled request raises (finish_reason contract);
    # the partial tokens stay on the handle.
    from tpudp.serve import FinishReason, RequestFailed

    with pytest.raises(RequestFailed, match="cancelled"):
        h1.result()
    assert h1.finish_reason is FinishReason.CANCELLED
    assert h1.tokens == emitted_before
    assert eng.stats["cancelled"] == 1 and eng.stats["completed"] == 1


def test_cancel_queued_request_never_occupies_a_slot(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(3)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8)
    h1 = eng.submit(p, 3)
    h2 = eng.submit(p, 3)
    h3 = eng.submit(p, 3)
    assert h2.cancel() is True and h2.done and h2.cancelled
    eng.run_until_complete()
    assert h1.done and h3.done and not h1.cancelled and not h3.cancelled
    assert len(h1.tokens) == 3 and len(h3.tokens) == 3 and h2.tokens == []
    assert eng.stats["admitted"] == 2  # h2 never took a slot


def test_cancel_mid_stream_iteration_terminates(model_and_params):
    """A consumer streaming a handle sees iteration end promptly after a
    cancel (no hang waiting for tokens that will never come)."""
    model, params = model_and_params
    rng = np.random.default_rng(6)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8)
    h = eng.submit(p, 10)
    got = []
    for tok in h:
        got.append(tok)
        if len(got) == 2:
            h.cancel()
    assert h.done and h.cancelled and got == h.tokens


# -- acceptance stats --------------------------------------------------


def test_acceptance_rate_stats(model_and_params):
    """Per-request and engine-wide acceptance accounting: a same-model
    drafter accepts everything, a garbage drafter nothing, and the
    engine aggregates across requests."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 speculate_k=2, drafter=DraftModelDrafter(model, params))
    h = eng.submit(p, 6)
    eng.run_until_complete()
    assert h.acceptance_rate == 1.0 and eng.acceptance_rate == 1.0
    assert h.draft_proposed > 0

    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 speculate_k=2, drafter=GarbageDrafter())
    h = eng.submit(p, 6)
    eng.run_until_complete()
    assert h.acceptance_rate == 0.0 and eng.acceptance_rate == 0.0

    plain = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    assert plain.acceptance_rate is None


# -- llama family ------------------------------------------------------


@pytest.mark.slow
def test_llama_family_speculative_greedy_parity():
    """The verify window's per-position attention holds for the RoPE/GQA
    lineage too: speculative llama output equals standalone generate()."""
    from tpudp.models.llama import llama_small

    model = llama_small(vocab_size=61, max_seq_len=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, d_model=32)
    params = init_state(model, make_optimizer(),
                        input_shape=(1, 8)).params
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (4, 11, 17)]
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 speculate_k=3)
    outs = eng.generate_many(prompts, 6)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(_reference(model, params, p, 6)[0], o)


# -- tooling gate ------------------------------------------------------


