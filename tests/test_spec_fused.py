"""On-device speculation (ISSUE 16): the fused draft→verify→accept
window and the speculative token tree.

Two new speculative execution modes and their contracts:

  * ``Engine(speculate_k=k, decode_fuse=N, drafter=DraftModelDrafter)``
    fuses up to N draft→verify→accept windows into ONE device program
    (``fused_spec_decode``): the draft model's weights are frozen into
    the program and it drafts in-carry, so the per-window host draft
    gather AND verify fetch disappear.  The referee is the host-drafted
    engine: same drafter weights, ``bucket=max_len`` (the device
    drafter's exact prefill geometry), ``decode_fuse=1`` — outputs must
    be BIT-EXACT, greedy and sampled, along with the acceptance
    accounting.
  * ``Engine(speculate_tree=shape)`` verifies a static TREE of
    candidate branches in one tree-masked forward
    (``verify_tree_tokens``): a chain-shaped tree is bit-identical to
    the sequence draft, a branched shape rescues windows the chain's
    first token loses, and only the accepted root-to-leaf path's KV
    commits — on the paged engine, rejected branches write ZERO real
    pool bytes (the byte-diff pin below).

Both modes keep the standing serve invariants: compile-once per
(geometry, k, N / tree shape), quarantine falls back to the plain
FUSED path bit-exactly, preemption and step-failure containment resume
bit-exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import generate
from tpudp.models.gpt2 import gpt2_small
from tpudp.ops.sampling import verify_tokens, verify_tree_tokens
from tpudp.serve import (TRACE_COUNTS, DraftModelDrafter, Engine,
                         FinishReason, NgramDrafter, TenantClass)
from tpudp.train import init_state, make_optimizer

TINY = dict(vocab_size=61, max_seq_len=64, num_layers=2, num_heads=2,
            d_model=32)
# The draft model: smaller in every dimension, but covering
# max_len + speculate_k positions (the fusability bound).
DRAFT = dict(vocab_size=61, max_seq_len=64, num_layers=1, num_heads=2,
             d_model=16)
MAX_LEN = 48
K = 2
FUSE = 4


@pytest.fixture(scope="module")
def target():
    model = gpt2_small(**TINY)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


@pytest.fixture(scope="module")
def draft():
    model = gpt2_small(**DRAFT)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


def _reference(model, params, prompt, n):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                               n))[0]


def _spec_engine(target, draft, *, fuse=FUSE, bucket=None, **kw):
    model, params = target
    dmodel, dparams = draft
    return Engine(model, params, num_slots=2, max_len=MAX_LEN,
                  prefill_chunk=8, speculate_k=K, decode_fuse=fuse,
                  drafter=DraftModelDrafter(dmodel, dparams,
                                            bucket=bucket), **kw)


# -- fused speculative window: parity, accounting, compile-once --------


def test_fused_spec_greedy_parity_and_accounting(target, draft):
    """Greedy fused-spec outputs equal standalone generate() token for
    token (drafts are hints), the fused windows actually engaged, and
    acceptance accounting rides the handles."""
    model, params = target
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (5, 11, 3)]
    eng = _spec_engine(target, draft)
    assert eng._spec_fusable
    handles = [eng.submit(p, 10) for p in prompts]
    eng.run_until_complete()
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(
            _reference(model, params, p, 10)[p.size:],
            np.asarray(h.tokens))
        assert h.draft_proposed > 0
        assert 0 <= h.draft_accepted <= h.draft_proposed
    assert eng.stats["fused_spec_windows"] > 0
    assert eng.stats["draft_tokens"] > 0
    assert eng.stats["draft_accepted"] == sum(
        h.draft_accepted for h in handles)


def test_fused_spec_sampled_parity_vs_host_drafted(target, draft):
    """Sampled fused-spec streams are BIT-EXACT vs the host-drafted
    engine (same draft weights, bucket pinned to max_len — the device
    drafter's prefill geometry — decode_fuse=1): same windows, same
    acceptance, same per-slot PRNG schedule, same accounting."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (5, 9, 13)]

    def run(fused):
        eng = (_spec_engine(target, draft) if fused
               else _spec_engine(target, draft, fuse=1, bucket=MAX_LEN))
        assert eng._spec_fusable is fused
        hs = [eng.submit(p, 11, temperature=0.9, top_k=12, top_p=0.9,
                         seed=5 + i) for i, p in enumerate(prompts)]
        eng.run_until_complete()
        return ([h.tokens for h in hs],
                [(h.draft_proposed, h.draft_accepted) for h in hs])

    toks_f, acc_f = run(True)
    toks_h, acc_h = run(False)
    assert toks_f == toks_h
    assert acc_f == acc_h


def test_fused_spec_paged_parity(target, draft):
    """The paged fused-spec twin (kv_pages) emits the same sampled
    streams as the dense fused-spec engine, with the paged trace key."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (6, 10)]
    dense = _spec_engine(target, draft)
    paged = _spec_engine(target, draft, kv_pages=40)
    outs = []
    for eng in (dense, paged):
        hs = [eng.submit(p, 9, temperature=0.8, top_p=0.95, seed=3 + i)
              for i, p in enumerate(prompts)]
        eng.run_until_complete()
        outs.append([h.tokens for h in hs])
        assert eng.stats["fused_spec_windows"] > 0
    assert outs[0] == outs[1]
    assert TRACE_COUNTS["fused_spec_paged"] >= 1


def test_fused_spec_compiles_once_across_churn(target, draft):
    """One fused_spec_decode trace per (geometry, k, N) no matter how
    many requests churn through — a fresh geometry no other test uses,
    so the count is exact."""
    model, params = target
    dmodel, dparams = draft
    rng = np.random.default_rng(3)
    eng = Engine(model, params, num_slots=3, max_len=40, prefill_chunk=8,
                 speculate_k=K, decode_fuse=5,
                 drafter=DraftModelDrafter(dmodel, dparams))
    h = eng.submit(rng.integers(0, 61, size=4).astype(np.int32), 6)
    eng.run_until_complete()
    assert h.done
    base = TRACE_COUNTS["fused_spec_decode"]
    for i in range(4):
        eng.submit(rng.integers(0, 61, size=3 + 2 * (i % 3))
                   .astype(np.int32), 4 + i,
                   temperature=0.5 * (i % 2), top_k=4 if i % 2 else None,
                   seed=i)
        eng.run_until_complete()
    assert TRACE_COUNTS["fused_spec_decode"] == base
    assert eng.stats["fused_spec_windows"] > 0


def test_fused_spec_eligibility_gates(target, draft):
    """Anything outside the fusable envelope keeps the host-drafted
    path byte-for-byte: an ngram drafter (no weights to freeze), a
    draft model too short for max_len + k, and decode_fuse=1."""
    model, params = target
    dmodel, dparams = draft
    eng = Engine(model, params, num_slots=2, max_len=MAX_LEN,
                 prefill_chunk=8, speculate_k=K, decode_fuse=FUSE,
                 drafter=NgramDrafter())
    assert not eng._spec_fusable
    short = gpt2_small(**dict(DRAFT, max_seq_len=32))
    sparams = init_state(short, make_optimizer(),
                         input_shape=(1, 8)).params
    eng = Engine(model, params, num_slots=2, max_len=MAX_LEN,
                 prefill_chunk=8, speculate_k=K, decode_fuse=FUSE,
                 drafter=DraftModelDrafter(short, sparams))
    assert not eng._spec_fusable  # 32 < 48 + 2
    assert not _spec_engine(target, draft, fuse=1)._spec_fusable
    # The ineligible engine still serves correctly (host-drafted path).
    rng = np.random.default_rng(4)
    p = rng.integers(0, 61, size=5).astype(np.int32)
    h = eng.submit(p, 6)
    eng.run_until_complete()
    np.testing.assert_array_equal(
        _reference(model, params, p, 6)[5:], np.asarray(h.tokens))
    assert eng.stats.get("fused_spec_windows", 0) == 0


def test_quarantine_falls_back_to_fused_decode(target, draft):
    """Satellite 4: a drafter quarantined MID-STREAM demotes the engine
    from fused_spec_decode to the plain FUSED window — not single-step
    decode — and the in-flight sampled request continues bit-exactly
    with no new program traced beyond the two already warm."""
    rng = np.random.default_rng(5)
    p = rng.integers(0, 61, size=5).astype(np.int32)
    eng = _spec_engine(target, draft)
    h = eng.submit(p, 16, temperature=0.9, top_k=10, seed=13)
    eng.step()
    eng.step()
    assert eng.stats["fused_spec_windows"] > 0 and not h.done
    spec_base = TRACE_COUNTS["fused_spec_decode"]
    fused_base = TRACE_COUNTS["fused_decode"]
    decode_before = eng.stats["decode_steps"]
    # The injected mid-stream quarantine (an operator kill / fleet
    # config push — the host-side seams cannot fire organically here:
    # the fused program never calls the host drafter).
    eng._quarantine_drafter("injected: operator quarantine mid-stream")
    eng.run_until_complete()
    assert h.finish_reason is FinishReason.COMPLETE
    assert eng.drafter_quarantined
    # Demotion target is the FUSED window, not the single-step path.
    assert eng.stats["fused_windows"] > 0
    assert eng.stats["decode_steps"] == decode_before
    # No recompiles: each program traced at most once for this
    # geometry, and the speculative program never re-traced.
    assert TRACE_COUNTS["fused_spec_decode"] == spec_base
    assert TRACE_COUNTS["fused_decode"] <= fused_base + 1
    # Bit-exact continuation: the whole stream equals an uninterrupted
    # host-drafted run up to the quarantine point... which is exactly
    # the fused-spec stream, which equals the plain sampled stream only
    # in greedy — so referee against the same engine config replayed
    # with the quarantine armed from the same step.
    ref = _spec_engine(target, draft)
    g = ref.submit(p, 16, temperature=0.9, top_k=10, seed=13)
    ref.step()
    ref.step()
    ref._quarantine_drafter("injected: operator quarantine mid-stream")
    ref.run_until_complete()
    assert h.tokens == g.tokens
    # And the pre-quarantine prefix matches the never-quarantined run.
    full = _spec_engine(target, draft)
    f = full.submit(p, 16, temperature=0.9, top_k=10, seed=13)
    full.run_until_complete()
    assert h.tokens[:len(h.tokens) // 2] == \
        f.tokens[:len(h.tokens) // 2]


def test_fused_spec_preemption_resumes_bit_exactly(target, draft):
    """Tenancy + fused speculation: a high-priority submit between
    windows preempts the speculating slot at the next host touch; the
    preempted SAMPLED request resumes (tokens + PRNG chain + draft
    accounting carried) bit-identically to the HOST-DRAFTED engine
    preempted at the same window boundary — the vacate state (tokens,
    per-window key chain) is the same in both, so the resumes agree.
    (Solo-vs-preempted parity is a per-token-chain property of the
    plain paths; speculative chains advance per WINDOW, so the
    preemption oracle is host-drafted parity, and greedy solo parity.)
    """
    model, params = target
    dmodel, dparams = draft
    rng = np.random.default_rng(6)
    p_low = rng.integers(0, 61, size=5).astype(np.int32)
    p_hi = rng.integers(0, 61, size=7).astype(np.int32)
    tenants = lambda: {"low": TenantClass(priority=0),
                       "high": TenantClass(priority=1)}

    def make(fused, tn):
        return Engine(model, params, num_slots=1, max_len=MAX_LEN,
                      prefill_chunk=8, speculate_k=K,
                      decode_fuse=FUSE if fused else 1,
                      drafter=DraftModelDrafter(
                          dmodel, dparams,
                          bucket=None if fused else MAX_LEN),
                      tenants=tn)

    eng = make(True, tenants())
    h_low = eng.submit(p_low, 12, temperature=0.8, top_p=0.95, seed=11,
                       tenant="low")
    eng.step()
    eng.step()
    assert eng.stats["fused_spec_windows"] > 0
    h_hi = eng.submit(p_hi, 4, tenant="high")
    eng.step()
    assert eng.stats["preempted"] == 1 and h_low.preemptions == 1
    m = len(h_low.tokens)  # committed at the vacate (window boundary)
    assert 0 < m < 12
    eng.run_until_complete()
    assert h_low.finish_reason is FinishReason.COMPLETE
    np.testing.assert_array_equal(
        _reference(model, params, p_hi, 4)[7:], np.asarray(h_hi.tokens))
    # Host-drafted referee, preempted at the SAME window boundary: the
    # per-window chain means both vacate with identical (tokens, key).
    ref = make(False, tenants())
    g_low = ref.submit(p_low, 12, temperature=0.8, top_p=0.95, seed=11,
                       tenant="low")
    while len(g_low.tokens) < m:
        ref.step()
    assert len(g_low.tokens) == m  # window boundaries line up exactly
    ref.submit(p_hi, 4, tenant="high")
    ref.run_until_complete()
    assert g_low.preemptions == 1
    assert h_low.tokens == g_low.tokens
    assert (h_low.draft_proposed, h_low.draft_accepted) == \
        (g_low.draft_proposed, g_low.draft_accepted)
    # And the schedule-independent pin: GREEDY preempted == greedy solo.
    # The budget exceeds what the first two steps can emit (1 prefill
    # sample + one fused program of at most FUSE windows x K+1 tokens),
    # so the request is still in flight when the high tier arrives
    # whatever the draft acceptance is — under jax 0.9.0 this model's
    # greedy stream repeats enough that a 12-token budget finished
    # inside the first fused program and nothing was left to preempt.
    n_greedy = 1 + FUSE * (K + 1) + 7
    eng = make(True, tenants())
    h = eng.submit(p_low, n_greedy, tenant="low")
    eng.step()
    eng.step()
    eng.submit(p_hi, 3, tenant="high")
    eng.run_until_complete()
    assert h.preemptions == 1
    np.testing.assert_array_equal(
        _reference(model, params, p_low, n_greedy)[5:],
        np.asarray(h.tokens))


def test_fused_spec_step_failure_contained(target, draft):
    """An exception escaping the fused_spec device call is contained
    like every step failure: arena rebuilt, the request requeued once
    with tokens + PRNG + acceptance accounting carried, the retry
    finishing bit-identically."""
    rng = np.random.default_rng(7)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    class FailNthSpec:
        def __init__(self, nth):
            self.nth, self.seen = nth, 0

        def __call__(self, kind, idx):
            if kind == "fused_spec":
                self.seen += 1
                if self.seen == self.nth:
                    raise RuntimeError("injected fused_spec fault")

    eng = _spec_engine(target, draft, step_fault_hook=FailNthSpec(2))
    h = eng.submit(p, 12, temperature=0.7, seed=5)
    eng.run_until_complete()
    assert eng.stats["step_failures"] == 1 and eng.stats["requeued"] == 1
    assert h.finish_reason is FinishReason.COMPLETE
    solo = _spec_engine(target, draft)
    ref = solo.submit(p, 12, temperature=0.7, seed=5)
    solo.run_until_complete()
    assert h.tokens == ref.tokens


# -- the speculative token tree ----------------------------------------


def test_verify_tree_tokens_chain_equals_verify_tokens():
    """Op-level: on a chain-shaped tree, verify_tree_tokens is
    bit-identical to verify_tokens — emitted tokens and counts — for a
    mix of greedy, sampled, truncated, and no-draft rows."""
    n, k, v = 6, 2, 23
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (n, k + 1, v), jnp.float32) * 3.0
    drafts = jax.random.randint(jax.random.PRNGKey(1), (n, k), 0, v,
                                jnp.int32)
    # Make some drafts agree with the argmax so accepts happen.
    drafts = drafts.at[0].set(jnp.argmax(logits[0, :k], -1))
    drafts = drafts.at[3, 0].set(jnp.argmax(logits[3, 0], -1))
    n_draft = jnp.array([2, 2, 0, 1, 2, 0], jnp.int32)
    temps = jnp.array([0.0, 0.9, 0.0, 1.2, 0.7, 1.0], jnp.float32)
    top_k = jnp.array([0, 5, 0, 0, 8, 0], jnp.int32)
    top_p = jnp.array([1.0, 0.9, 1.0, 1.0, 1.0, 0.8], jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(n, dtype=jnp.uint32))
    out_seq, n_seq = verify_tokens(logits, drafts, n_draft, temps,
                                   top_k, top_p, keys)
    out_tree, n_tree, path = verify_tree_tokens(
        logits, drafts, (-1, 0, 1), n_draft, temps, top_k, top_p, keys)
    np.testing.assert_array_equal(np.asarray(n_seq), np.asarray(n_tree))
    # Columns past n_emitted are padding the replay never reads.
    live = np.arange(k + 1)[None, :] < np.asarray(n_seq)[:, None]
    np.testing.assert_array_equal(np.where(live, np.asarray(out_seq), 0),
                                  np.where(live, np.asarray(out_tree), 0))
    # The accepted path on a chain is the node prefix 0,1,2.
    np.testing.assert_array_equal(
        np.asarray(path[0]), np.arange(3))


def test_tree_chain_engine_equals_sequence_engine(target):
    """Engine-level chain parity: speculate_tree='chain2' emits the
    exact sampled streams of the k=2 sequence-draft engine — same
    drafter, same seeds, same acceptance accounting."""
    model, params = target
    rng = np.random.default_rng(8)
    rep = np.tile(rng.integers(0, 61, size=4), 5)[:14].astype(np.int32)

    def run(tree):
        eng = Engine(model, params, num_slots=1, max_len=MAX_LEN,
                     prefill_chunk=8, speculate_k=2,
                     speculate_tree="chain2" if tree else None,
                     drafter=NgramDrafter(max_ngram=3, min_ngram=2))
        h = eng.submit(rep, 10, temperature=0.9, top_k=12, seed=9)
        eng.run_until_complete()
        return h.tokens, h.draft_accepted

    assert run(True) == run(False)


def test_tree_fork_greedy_parity_and_stats(target):
    """A branched shape (fork2x2) stays bit-exact greedy (drafts are
    hints) while the tree stats record the windows and accepts."""
    model, params = target
    rng = np.random.default_rng(9)
    rep = np.tile(rng.integers(0, 61, size=3), 6)[:15].astype(np.int32)
    eng = Engine(model, params, num_slots=2, max_len=MAX_LEN,
                 prefill_chunk=8, speculate_k=2, speculate_tree="fork2x2",
                 drafter=NgramDrafter(max_ngram=3, min_ngram=2))
    hs = [eng.submit(rep, 9), eng.submit(rep[:10], 7)]
    eng.run_until_complete()
    np.testing.assert_array_equal(
        _reference(model, params, rep, 9)[rep.size:],
        np.asarray(hs[0].tokens))
    np.testing.assert_array_equal(
        _reference(model, params, rep[:10], 7)[10:],
        np.asarray(hs[1].tokens))
    assert eng.stats["tree_verify_steps"] > 0
    assert eng.stats["draft_tokens"] > 0
    assert TRACE_COUNTS["tree_verify"] >= 1


class _HedgingDrafter:
    """The ambiguity a branched tree exists to hedge, handcrafted: the
    SEQUENCE proposal leads with a wrong token every window, while the
    tree proposal spends the same candidate count on two branches —
    the same wrong guess plus the true greedy continuation."""

    def __init__(self, full, vocab):
        self.full = np.asarray(full, np.int32)  # prompt + greedy tokens
        self.vocab = vocab

    def _truth(self, context):
        length = np.asarray(context).size
        return [int(self.full[length + d]) for d in range(2)]

    def propose(self, context, k):
        t0 = self._truth(context)[0]
        return np.full(k, (t0 + 1) % self.vocab, np.int32)

    def propose_tree(self, context, shape):
        t0, t1 = self._truth(context)
        tokens = np.zeros(shape.num_candidates, np.int32)
        # fork2x2 paths: (1, 2) and (3, 4).  Path 0 = the wrong guess
        # (exactly what propose() leads with), path 1 = the truth.
        tokens[0] = (t0 + 1) % self.vocab
        tokens[1] = (t1 + 1) % self.vocab
        tokens[2] = t0
        tokens[3] = t1
        return tokens


def test_tree_branch_win_over_sequence(target):
    """The tentpole's acceptance bar: at EQUAL candidate count (4) on a
    workload whose first guess always loses, the branched tree strictly
    beats the sequence draft on accepted tokens AND tokens per verify
    window — the sequence draft accepts nothing, the tree commits its
    hedged branch every window."""
    model, params = target
    rng = np.random.default_rng(10)
    p = rng.integers(0, 61, size=6).astype(np.int32)
    full = _reference(model, params, p, 20)
    drafter = _HedgingDrafter(full, 61)

    seq = Engine(model, params, num_slots=1, max_len=MAX_LEN,
                 prefill_chunk=8, speculate_k=4, drafter=drafter)
    hs = seq.submit(p, 10)
    seq.run_until_complete()
    tree = Engine(model, params, num_slots=1, max_len=MAX_LEN,
                  prefill_chunk=8, speculate_k=2,
                  speculate_tree="fork2x2", drafter=drafter)
    ht = tree.submit(p, 10)
    tree.run_until_complete()
    # Greedy output integrity first — hints never change tokens.
    np.testing.assert_array_equal(full[6:16], np.asarray(hs.tokens))
    np.testing.assert_array_equal(full[6:16], np.asarray(ht.tokens))
    # The wrong-first sequence accepts nothing; the tree's hedged
    # branch lands both tokens every window.
    assert hs.draft_accepted == 0
    assert ht.draft_accepted > 0
    seq_rate = (len(hs.tokens) - 1) / seq.stats["verify_steps"]
    tree_rate = (len(ht.tokens) - 1) / tree.stats["tree_verify_steps"]
    assert tree_rate > seq_rate
    assert tree_rate >= 2.0  # 2 accepts + bonus per window, minus tail


class _AllWrongDrafter:
    """Every candidate wrong — both root children — so every tree
    window rejects every branch and emits only the bonus token."""

    def __init__(self, full, vocab):
        self.full = np.asarray(full, np.int32)
        self.vocab = vocab

    def propose_tree(self, context, shape):
        length = np.asarray(context).size
        t = [int(self.full[length + d]) for d in range(2)]
        tokens = np.zeros(shape.num_candidates, np.int32)
        tokens[0] = (t[0] + 1) % self.vocab   # node 1: wrong
        tokens[1] = (t[1] + 1) % self.vocab   # node 2: wrong
        tokens[2] = (t[0] + 2) % self.vocab   # node 3: wrong, != node 1
        tokens[3] = (t[1] + 2) % self.vocab   # node 4: wrong
        return tokens


def test_tree_paged_rejected_branches_write_zero_pool_bytes(target):
    """The byte-diff pin: with every candidate rejected, a paged tree
    window's only REAL pool write is the accepted depth-0 bonus token's
    page — rejected depths route to the scratch page, so every other
    page's bytes are untouched, including (at page-boundary steps) the
    already-backed NEXT page a rejected depth-1 write would land in."""
    model, params = target
    rng = np.random.default_rng(11)
    p = rng.integers(0, 61, size=6).astype(np.int32)
    full = _reference(model, params, p, 20)
    eng = Engine(model, params, num_slots=1, max_len=MAX_LEN,
                 prefill_chunk=8, speculate_k=2,
                 speculate_tree="fork2x2", kv_pages=8,
                 drafter=_AllWrongDrafter(full, 61))
    h = eng.submit(p, 12)
    while not h.tokens:  # prefill + first sample
        eng.step()
    ms = eng._mstates[None]
    T = eng.prefill_chunk
    scratch = ms.pool.pages.k.shape[1] - 1
    boundary_checked = False
    while not h.done:
        pos0 = int(eng._len[0])
        own = int(ms.table[0, pos0 // T])
        next_page = int(ms.table[0, (pos0 + 1) // T]) \
            if (pos0 + 1) // T < ms.table.shape[1] else -1
        kb = np.array(ms.pool.pages.k)
        vb = np.array(ms.pool.pages.v)
        steps_before = eng.stats["tree_verify_steps"]
        eng.step()
        if eng.stats["tree_verify_steps"] == steps_before:
            continue  # not a tree window (e.g. retirement bookkeeping)
        ka = np.array(ms.pool.pages.k)
        va = np.array(ms.pool.pages.v)
        changed = {i for i in range(ka.shape[1])
                   if not (np.array_equal(kb[:, i], ka[:, i])
                           and np.array_equal(vb[:, i], va[:, i]))}
        # All-rejected window: one real page (the bonus token's) plus
        # the scratch page.  Nothing else.
        assert changed <= {own, scratch}, (pos0, own, scratch, changed)
        if pos0 % T == T - 1 and next_page not in (-1, own):
            # Depth-1 writes would land in next_page; it is backed and
            # mapped, and its bytes did not move.
            assert next_page not in changed
            boundary_checked = True
    assert boundary_checked  # the run crossed a page boundary
    assert h.draft_accepted == 0  # every candidate really was rejected
    np.testing.assert_array_equal(full[6:18], np.asarray(h.tokens))
    assert TRACE_COUNTS["tree_verify_paged"] >= 1


def test_tree_validation(target):
    model, params = target
    with pytest.raises(ValueError, match="speculate_k"):
        Engine(model, params, num_slots=1, speculate_tree="fork2x2")
    with pytest.raises(ValueError, match="max_depth"):
        Engine(model, params, num_slots=1, speculate_k=1,
               speculate_tree="fork2x2")  # depth 2 > k=1
    with pytest.raises(ValueError, match="propose_tree"):
        Engine(model, params, num_slots=1, speculate_k=2,
               speculate_tree="fork2x2",
               drafter=_no_tree_drafter())
    with pytest.raises(ValueError, match="unknown tree shape"):
        Engine(model, params, num_slots=1, speculate_k=2,
               speculate_tree="nope", drafter=NgramDrafter())


def _no_tree_drafter():
    class _SeqOnly:
        def propose(self, context, k):
            return np.zeros(0, np.int32)

    return _SeqOnly()
