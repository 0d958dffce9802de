"""tpudp.serve tenancy: priority tiers, bit-exact preemption, weighted
admission, and co-resident models behind one scheduler.

The contracts under test:

  1. PREEMPTION IS INVISIBLE — a request evicted for higher-priority
     work resumes with tokens + PRNG chain carried over and finishes
     bit-identically to an uninterrupted run (greedy AND sampled,
     speculative and prefix-cached included); ``FinishReason.PREEMPTED``
     never reaches a handle.
  2. FAIR SHARES ARE THE CONFIG — at equal priority, stride scheduling
     admits classes in proportion to their weights, deterministically.
  3. PER-CLASS BOUNDS — each class's queue_limit sheds ITS overload
     with a typed ``QueueFull``; other classes are untouched.
  4. CO-RESIDENT MODELS — tenants routed to different model/params
     pairs each decode bit-identically to their own ``generate()``,
     through per-model compiled-once step programs.
  5. OFF-SWITCH — ``tenants=None`` is byte-for-byte the old engine:
     the stats schema is pinned (no new keys leak in) and the
     ``FinishReason`` ↔ counter map stays exhaustive.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import generate
from tpudp.models.gpt2 import gpt2_small
from tpudp.serve import (Engine, FinishReason, NgramDrafter, QueueFull,
                         TenantClass, TenantScheduler)
from tpudp.serve.engine import _FINISH_COUNTER
from tpudp.serve.faults import FaultySteps, PreemptionStorm
from tpudp.train import init_state, make_optimizer

TINY = dict(vocab_size=61, max_seq_len=64, num_layers=2, num_heads=2,
            d_model=32)


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt2_small(**TINY)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


def _reference(model, params, prompt, n):
    return np.asarray(
        generate(model, params, jnp.asarray(prompt[None]), n))[0,
                                                               prompt.size:]


def _two_tier(model, params, **kw):
    kw.setdefault("num_slots", 1)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("tenants", {"low": TenantClass(priority=0),
                              "high": TenantClass(priority=1)})
    return Engine(model, params, **kw)


# -- preemption: bit-exact resume --------------------------------------


def test_preemption_resumes_bit_identically(model_and_params):
    """A low-priority in-flight request is evicted the step a
    high-priority one waits, the high request runs to completion first,
    and the resumed low request's tokens equal an uninterrupted
    generate() — the preemption was pure latency."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    p_lo = rng.integers(0, 61, size=4).astype(np.int32)
    p_hi = rng.integers(0, 61, size=5).astype(np.int32)
    eng = _two_tier(model, params)
    h_lo = eng.submit(p_lo, 10, tenant="low")
    for _ in range(3):
        eng.step()
    assert h_lo.tokens and not h_lo.done
    h_hi = eng.submit(p_hi, 4, tenant="high")
    eng.step()
    assert h_lo.preemptions == 1 and h_lo._slot is None
    assert not h_lo.done and h_lo.finish_reason is None  # never visible
    assert eng.stats["preempted"] == 1
    # the high request owns the slot now and finishes first
    eng.run_until_complete()
    assert h_hi.finish_reason is FinishReason.COMPLETE
    assert h_lo.finish_reason is FinishReason.COMPLETE
    assert h_hi.token_times[-1] < h_lo.token_times[-1]
    np.testing.assert_array_equal(_reference(model, params, p_hi, 4),
                                  np.asarray(h_hi.tokens))
    np.testing.assert_array_equal(_reference(model, params, p_lo, 10),
                                  np.asarray(h_lo.tokens))
    assert eng.tenant_stats["low"]["preempted"] == 1
    # the resume is a re-admission, not a fresh grant — the fairness
    # accounting must not inflate for the preempted class
    assert eng.tenant_stats["low"]["admitted"] == 1
    assert eng.tenant_stats["low"]["readmitted"] == 1
    assert eng.slots_in_use == 0 and eng.queue_depth == 0


def test_preempted_sampled_request_keeps_prng_chain(model_and_params):
    """The eviction carries the per-slot PRNG chain, so a SAMPLED
    request's draws are bit-identical with and without preemption."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    def tokens_of(preempt):
        eng = _two_tier(model, params)
        h = eng.submit(p, 8, temperature=0.9, top_k=12, seed=7,
                       tenant="low")
        for _ in range(3):
            eng.step()
        if preempt:
            eng.submit(p, 2, tenant="high")
        eng.run_until_complete()
        assert h.finish_reason is FinishReason.COMPLETE
        assert h.preemptions == (1 if preempt else 0)
        return list(h.tokens)

    assert tokens_of(True) == tokens_of(False)


def test_double_preemption_same_request(model_and_params):
    """One request preempted TWICE across its lifetime still finishes
    bit-identically — the carry-over path is repeatable and never
    burns the step-failure requeue budget."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _two_tier(model, params)
    h = eng.submit(p, 12, tenant="low")
    for _ in range(3):
        eng.step()
    first = eng.submit(p, 2, tenant="high")
    eng.run_until_complete()  # high done, low resumed and done? no —
    # run_until_complete finishes everything; preempt again mid-way
    # requires interleaving, so use a second engine pass instead:
    assert h.preemptions == 1 and h.done
    eng2 = _two_tier(model, params)
    h2 = eng2.submit(p, 12, tenant="low")
    for _ in range(3):
        eng2.step()
    eng2.submit(p, 2, tenant="high")
    eng2.step()
    assert h2.preemptions == 1
    # drive until the low request is back in flight with fresh tokens
    while h2._slot is None or h2._nfill < h2._fill.size:
        eng2.step()
    eng2.submit(p, 2, tenant="high")
    eng2.step()
    assert h2.preemptions == 2
    assert not h2._requeued  # fault budget untouched by preemption
    eng2.run_until_complete()
    assert h2.finish_reason is FinishReason.COMPLETE
    np.testing.assert_array_equal(_reference(model, params, p, 12),
                                  np.asarray(h2.tokens))
    assert first.done and eng2.stats["preempted"] == 2


def test_preempt_vs_cancel_on_same_request(model_and_params):
    """Preempt then cancel while requeued: the handle retires CANCELLED
    out of its class queue and the engine stays clean.  Cancel then
    submit-high: the freed slot serves the high request with NO
    preemption (eviction only fires when no slot is free)."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _two_tier(model, params)
    h = eng.submit(p, 10, tenant="low")
    for _ in range(3):
        eng.step()
    hi = eng.submit(p, 3, tenant="high")
    eng.step()
    assert h.preemptions == 1 and not h.done
    assert h.cancel() is True  # cancelled while queued-after-preemption
    assert h.finish_reason is FinishReason.CANCELLED and h.tokens
    eng.run_until_complete()
    assert hi.finish_reason is FinishReason.COMPLETE
    assert eng.queue_depth == 0 and eng.slots_in_use == 0

    eng2 = _two_tier(model, params)
    h2 = eng2.submit(p, 10, tenant="low")
    for _ in range(3):
        eng2.step()
    h2.cancel()
    hi2 = eng2.submit(p, 3, tenant="high")
    eng2.run_until_complete()
    assert hi2.finish_reason is FinishReason.COMPLETE
    assert eng2.stats["preempted"] == 0  # free slot, no eviction needed
    np.testing.assert_array_equal(_reference(model, params, p, 3),
                                  np.asarray(hi2.tokens))


def test_preempt_during_chunked_prefill_with_prefix_cache(
        model_and_params):
    """Evicting a request mid-prefill publishes only its chunk-prefilled
    blocks, leaves no pinned block behind (the cache invariant checker
    referees), and the resume — which re-enters through the block-copy
    hit path — still matches generate() bit-exactly."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    p_long = rng.integers(0, 61, size=20).astype(np.int32)  # 3 chunks
    p_hi = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _two_tier(model, params, max_len=48, prefix_cache_blocks=8)
    h = eng.submit(p_long, 5, tenant="low")
    eng.step()  # one chunk prefilled (8 of 20)
    assert 0 < h._nfill < h._fill.size
    hi = eng.submit(p_hi, 3, tenant="high")
    eng.step()
    assert h.preemptions == 1
    eng.prefix_cache.check()  # no dangling pins, tree consistent
    eng.run_until_complete()
    assert eng.stats["prefix_hit_tokens"] > 0  # resume reused blocks
    np.testing.assert_array_equal(_reference(model, params, p_hi, 3),
                                  np.asarray(hi.tokens))
    np.testing.assert_array_equal(_reference(model, params, p_long, 5),
                                  np.asarray(h.tokens))
    eng.prefix_cache.check()


def test_preempt_speculating_slot(model_and_params):
    """Preempting a slot mid-speculation (drafts in flight, scratch
    positions reserved) reclaims the slot cleanly: the resumed request
    and the preemptor both match generate() bit-exactly and the verify
    program never recompiles."""
    model, params = model_and_params
    rng = np.random.default_rng(5)
    # repetitive prompt so the n-gram drafter actually drafts
    p = np.tile(rng.integers(0, 61, size=3), 5)[:12].astype(np.int32)
    p_hi = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _two_tier(model, params, speculate_k=2,
                    drafter=NgramDrafter(max_ngram=3, min_ngram=2))
    h = eng.submit(p, 10, tenant="low")
    while len(h.tokens) < 3:  # deep enough that speculation is running
        eng.step()
    hi = eng.submit(p_hi, 3, tenant="high")
    eng.step()
    assert h.preemptions == 1
    eng.run_until_complete()
    assert h.finish_reason is FinishReason.COMPLETE
    np.testing.assert_array_equal(_reference(model, params, p, 10),
                                  np.asarray(h.tokens))
    np.testing.assert_array_equal(_reference(model, params, p_hi, 3),
                                  np.asarray(hi.tokens))


def test_preemption_storm_no_leak_and_parity(model_and_params):
    """The deterministic storm injector: repeated high-priority bursts
    evict low-tier work over and over; nothing wedges, nothing leaks,
    every survivor is bit-exact — preemption is latency, never loss."""
    model, params = model_and_params
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 61, size=4 + (i % 3)).astype(np.int32)
               for i in range(6)]
    storm_prompts = [rng.integers(0, 61, size=4).astype(np.int32)
                     for _ in range(4)]
    eng = _two_tier(model, params, num_slots=2,
                    tenants={"low": TenantClass(priority=0, queue_limit=8),
                             "high": TenantClass(priority=1)})
    storm = PreemptionStorm("high", storm_prompts,
                            at_steps=[2, 5, 8, 11], max_new=2, seed=99)
    handles = [eng.submit(p, 6, tenant="low") for p in prompts]
    steps = 0
    while (eng.queue_depth or eng.slots_in_use
           or not storm.done) and steps < 400:
        eng.step()
        storm.tick(eng, steps)
        steps += 1
    assert steps < 400  # no wedge
    assert eng.slots_in_use == 0 and eng.queue_depth == 0  # no leak
    assert eng.stats["preempted"] >= 1
    for p, h in zip(prompts, handles):
        assert h.finish_reason is FinishReason.COMPLETE
        np.testing.assert_array_equal(_reference(model, params, p, 6),
                                      np.asarray(h.tokens))
    for i, h in enumerate(storm.handles):
        assert h is not None and h.finish_reason is FinishReason.COMPLETE
        np.testing.assert_array_equal(
            _reference(model, params, storm.handles[i].prompt, 2),
            np.asarray(h.tokens))


# -- weighted admission ------------------------------------------------


class _Queued:
    def __init__(self, tenant):
        self.tenant = tenant


def test_stride_scheduler_shares_match_weights():
    """The admission policy in isolation: at equal priority, 40 picks
    from saturated 3:1-weighted queues split 30/10 (deterministically —
    stride, not randomness), and priorities strictly dominate."""
    sched = TenantScheduler({"a": TenantClass(weight=3.0),
                             "b": TenantClass(weight=1.0)})
    for _ in range(40):
        sched.enqueue(_Queued("a"))
        sched.enqueue(_Queued("b"))
    picks = [sched.pop_next().tenant for _ in range(40)]
    assert picks.count("a") == 30 and picks.count("b") == 10
    # strict priority: an urgent class starves both while it has work
    sched2 = TenantScheduler({"a": TenantClass(weight=3.0),
                              "hi": TenantClass(priority=1)})
    sched2.enqueue(_Queued("a"))
    sched2.enqueue(_Queued("hi"))
    sched2.enqueue(_Queued("hi"))
    assert [sched2.pop_next().tenant for _ in range(3)] == \
        ["hi", "hi", "a"]


def test_stride_vtime_is_per_priority_tier():
    """A high-priority burst must not inflate the virtual time a
    re-entering low-tier class starts at: with a shared clock, a
    weight-3 class enqueueing AFTER 100 high-priority pops would re-
    enter ~100 passes behind its weight-1 peer (whose backlog queued at
    vtime 0) and the configured 3:1 share would invert.  Virtual time
    is per tier, so the split stays 30:10."""
    sched = TenantScheduler({"hi": TenantClass(priority=1),
                             "a": TenantClass(weight=3.0),
                             "b": TenantClass(weight=1.0)})
    for _ in range(50):
        sched.enqueue(_Queued("b"))       # b's backlog queues at vtime 0
    for _ in range(100):
        sched.enqueue(_Queued("hi"))
    for _ in range(100):                  # the burst drains first
        assert sched.pop_next().tenant == "hi"
    for _ in range(60):
        sched.enqueue(_Queued("a"))       # a re-enters AFTER the burst
    picks = [sched.pop_next().tenant for _ in range(40)]
    assert picks.count("a") == 30 and picks.count("b") == 10, picks


def test_readmitted_work_pops_free():
    """A resume (requeue_front) is not a fresh stride grant: popping it
    advances neither the class's pass nor the tier's virtual time, so a
    class whose work keeps getting preempted is never charged twice for
    one request and equal weights stay an equal split."""
    sched = TenantScheduler({"a": TenantClass(weight=1.0),
                             "b": TenantClass(weight=1.0)})
    first = _Queued("a")
    sched.enqueue(first)
    assert sched.pop_next() is first      # charged: a.pass_ -> 1.0
    sched.requeue_front(first)
    assert sched.pop_next() is first      # resume: free
    for _ in range(8):
        sched.enqueue(_Queued("a"))
        sched.enqueue(_Queued("b"))
    picks = [sched.pop_next().tenant for _ in range(16)]
    # one charged grant of head start for b, then strict alternation —
    # NOT two (the resume must not have been charged)
    assert picks.count("a") == 8 and picks.count("b") == 8
    assert sched.pop_next() is None


def test_idle_tenant_cannot_bank_credit():
    """A class that sat idle re-enters at the current virtual time: it
    gets its fair share going forward, never a monopolizing backlog of
    credit for the time it submitted nothing."""
    sched = TenantScheduler({"a": TenantClass(weight=1.0),
                             "b": TenantClass(weight=1.0)})
    for _ in range(20):
        sched.enqueue(_Queued("a"))
    for _ in range(10):
        sched.pop_next()  # b idle while a advances its pass
    for _ in range(20):
        sched.enqueue(_Queued("b"))
    nxt = [sched.pop_next().tenant for _ in range(10)]
    assert nxt.count("b") <= 6  # fair share + rounding, not a monopoly


def test_engine_admission_order_tracks_weights(model_and_params):
    """End to end: two saturated equal-priority classes at weights 3:1
    are admitted ~3:1 (the tenancy bench's fairness oracle), and every
    output stays bit-exact."""
    model, params = model_and_params
    rng = np.random.default_rng(7)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    ref = _reference(model, params, p, 2)
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 tenants={"gold": TenantClass(weight=3.0),
                          "free": TenantClass(weight=1.0)})
    hs = {"gold": [], "free": []}
    for name in ("gold", "free"):
        for i in range(16):
            hs[name].append(eng.submit(p, 2, tenant=name))
    # Admission order is recorded on the handles (_order); the first 16
    # admissions out of saturated queues must split ~12:4.
    eng.run_until_complete()
    first = sorted(hs["gold"] + hs["free"],
                   key=lambda h: h._order)[:16]
    n_gold = sum(h.tenant == "gold" for h in first)
    assert 11 <= n_gold <= 13, n_gold
    for h in hs["gold"] + hs["free"]:
        assert h.finish_reason is FinishReason.COMPLETE
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))


# -- per-class bounds, deadlines, routing errors -----------------------


def test_per_tenant_queue_limit_sheds_typed(model_and_params):
    """One class's overload sheds with QueueFull and per-tenant stats;
    the other class keeps admitting — bounded admission is per class."""
    model, params = model_and_params
    rng = np.random.default_rng(8)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 tenants={"a": TenantClass(queue_limit=2),
                          "b": TenantClass(queue_limit=2)})
    eng.submit(p, 2, tenant="a")  # takes the slot on next step
    eng.step()
    ha = [eng.submit(p, 2, tenant="a") for _ in range(2)]
    with pytest.raises(QueueFull, match="tenant 'a'"):
        eng.submit(p, 2, tenant="a")
    hb = eng.submit(p, 2, tenant="b")  # b's queue is its own
    assert eng.stats["shed"] == 1
    assert eng.tenant_stats["a"]["shed"] == 1
    assert eng.tenant_stats["b"]["shed"] == 0
    eng.run_until_complete()
    for h in ha + [hb]:
        assert h.finish_reason is FinishReason.COMPLETE


def test_tenant_default_deadline_applies(model_and_params):
    """A class-wide default_deadline_s budgets submits that carry no
    explicit deadline; an explicit deadline still wins."""
    model, params = model_and_params
    rng = np.random.default_rng(9)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 tenants={"slo": TenantClass(default_deadline_s=1e-6),
                          "free": TenantClass()})
    h = eng.submit(p, 4, tenant="slo")
    assert h.deadline_s == 1e-6
    h2 = eng.submit(p, 4, tenant="slo", deadline_s=60.0)
    assert h2.deadline_s == 60.0
    time.sleep(0.002)
    eng.run_until_complete()
    assert h.finish_reason is FinishReason.DEADLINE
    assert h2.finish_reason is FinishReason.COMPLETE
    assert eng.tenant_stats["slo"]["deadline_expired"] == 1


def test_tenant_routing_validation(model_and_params):
    model, params = model_and_params
    p = np.zeros(4, np.int32)
    with pytest.raises(ValueError, match="requires Engine"):
        Engine(model, params, num_slots=1, max_len=32,
               prefill_chunk=8).submit(p, 2, tenant="x")
    with pytest.raises(ValueError, match="requires tenants"):
        Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
               models={"m": (model, params)})
    with pytest.raises(ValueError, match="unregistered model"):
        Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
               tenants={"t": TenantClass(model="nope")})
    with pytest.raises(ValueError, match="non-empty"):
        Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
               tenants={})
    with pytest.raises(ValueError, match="weight"):
        TenantClass(weight=0.0)
    with pytest.raises(ValueError, match="queue_limit"):
        TenantClass(queue_limit=0)
    with pytest.raises(ValueError, match="default_deadline_s"):
        TenantClass(default_deadline_s=-1.0)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 tenants={"only": TenantClass()})
    with pytest.raises(ValueError, match="unknown tenant"):
        eng.submit(p, 2, tenant="other")
    with pytest.raises(ValueError, match="default"):
        eng.submit(p, 2)  # no class named "default" configured


# -- co-resident models ------------------------------------------------


def test_co_resident_models_parity_and_compile_once(model_and_params):
    """Two models behind one scheduler: each tenant's requests decode
    bit-identically to THEIR model's generate(), interleaved in one
    host loop; each model's programs compile exactly once and churn
    never recompiles."""
    from tpudp.serve import TRACE_COUNTS

    model, params = model_and_params
    small = gpt2_small(vocab_size=47, max_seq_len=64, num_layers=1,
                       num_heads=2, d_model=24)
    sparams = init_state(small, make_optimizer(),
                         input_shape=(1, 8)).params
    rng = np.random.default_rng(10)
    # a geometry no other test in the SUITE uses (step programs are
    # shared per model config, whatever weights an engine serves), so
    # the jit cache is cold for it
    eng = Engine(model, params, num_slots=3, max_len=56, prefill_chunk=8,
                 tenants={"default": TenantClass(),
                          "cheap": TenantClass(model="small")},
                 models={"small": (small, sparams)})
    base = TRACE_COUNTS["decode_step"]
    pa = [rng.integers(0, 61, size=n).astype(np.int32) for n in (4, 9)]
    pb = [rng.integers(0, 47, size=n).astype(np.int32) for n in (5, 11)]
    ha = [eng.submit(p, 6) for p in pa]
    hb = [eng.submit(p, 6, tenant="cheap") for p in pb]
    eng.run_until_complete()
    for p, h in zip(pa, ha):
        np.testing.assert_array_equal(_reference(model, params, p, 6),
                                      np.asarray(h.tokens))
    for p, h in zip(pb, hb):
        np.testing.assert_array_equal(_reference(small, sparams, p, 6),
                                      np.asarray(h.tokens))
    assert TRACE_COUNTS["decode_step"] == base + 2  # one per model
    traced = (TRACE_COUNTS["decode_step"], TRACE_COUNTS["prefill_chunk"])
    eng.generate_many([pa[0]], 3)
    hb2 = eng.submit(pb[0], 3, tenant="cheap")
    eng.run_until_complete()
    np.testing.assert_array_equal(
        _reference(small, sparams, pb[0], 3), np.asarray(hb2.tokens))
    assert (TRACE_COUNTS["decode_step"],
            TRACE_COUNTS["prefill_chunk"]) == traced  # no recompiles


def test_co_resident_sampled_streams_independent(model_and_params):
    """A sampled request's draws do not depend on which MODELS share
    the scheduler — per-slot chains advance only on own sampling
    events, across co-resident step programs too."""
    model, params = model_and_params
    small = gpt2_small(vocab_size=47, max_seq_len=64, num_layers=1,
                       num_heads=2, d_model=24)
    sparams = init_state(small, make_optimizer(),
                         input_shape=(1, 8)).params
    rng = np.random.default_rng(11)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    def tokens_of(crowded):
        eng = Engine(model, params, num_slots=3, max_len=32,
                     prefill_chunk=8,
                     tenants={"default": TenantClass(),
                              "cheap": TenantClass(model="small")},
                     models={"small": (small, sparams)})
        if crowded:
            eng.submit(rng.integers(0, 47, size=6).astype(np.int32), 8,
                       temperature=1.1, seed=5, tenant="cheap")
        h = eng.submit(p, 8, temperature=0.9, top_k=12, seed=7)
        eng.run_until_complete()
        return list(h.tokens)

    assert tokens_of(True) == tokens_of(False)


def test_co_resident_model_validation(model_and_params):
    model, params = model_and_params
    shorter = gpt2_small(vocab_size=61, max_seq_len=16, num_layers=1,
                         num_heads=2, d_model=24)
    sp = init_state(shorter, make_optimizer(), input_shape=(1, 8)).params
    with pytest.raises(ValueError, match="max_seq_len"):
        Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
               tenants={"t": TenantClass(model="s")},
               models={"s": (shorter, sp)})
    # vocab bounds are the ROUTED model's, not the default's
    small = gpt2_small(vocab_size=47, max_seq_len=64, num_layers=1,
                       num_heads=2, d_model=24)
    smp = init_state(small, make_optimizer(), input_shape=(1, 8)).params
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 tenants={"default": TenantClass(),
                          "cheap": TenantClass(model="small")},
                 models={"small": (small, smp)})
    with pytest.raises(ValueError, match="prompt ids"):
        eng.submit(np.asarray([50], np.int32), 2, tenant="cheap")
    eng.submit(np.asarray([50], np.int32), 2)  # fine for the default


# -- step-failure containment composes with tenancy --------------------


def test_step_fault_requeues_into_tenant_queues(model_and_params):
    """A device-step failure under tenancy requeues survivors into
    their OWN class queues (front, admission order) and every request
    still finishes bit-identically."""
    model, params = model_and_params
    rng = np.random.default_rng(12)
    pa = rng.integers(0, 61, size=5).astype(np.int32)
    pb = rng.integers(0, 61, size=9).astype(np.int32)
    hook = FaultySteps(fail_at={6})
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 tenants={"a": TenantClass(), "b": TenantClass()},
                 step_fault_hook=hook)
    ha = eng.submit(pa, 6, tenant="a")
    hb = eng.submit(pb, 5, tenant="b")
    eng.run_until_complete()
    assert hook.fired and eng.stats["step_failures"] == 1
    assert eng.stats["requeued"] >= 1 and eng.stats["errors"] == 0
    np.testing.assert_array_equal(_reference(model, params, pa, 6),
                                  np.asarray(ha.tokens))
    np.testing.assert_array_equal(_reference(model, params, pb, 5),
                                  np.asarray(hb.tokens))


# -- drain/close across classes (the PR 3 drain contract, per-tenant) --


def test_drain_finishes_every_tenant_queue(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(13)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 tenants={"a": TenantClass(), "b": TenantClass(),
                          "hi": TenantClass(priority=1)})
    handles = ([eng.submit(p, 3, tenant="a") for _ in range(2)]
               + [eng.submit(p, 3, tenant="b")]
               + [eng.submit(p, 3, tenant="hi")])
    eng.step()
    eng.drain()
    assert eng.closed
    for h in handles:
        assert h.finish_reason is FinishReason.COMPLETE
    ref = _reference(model, params, p, 3)
    for h in handles:
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))


def test_close_sheds_every_tenant_queue(model_and_params):
    """close() walks ALL class queues: every queued request across
    every class gets a terminal SHED, in-flight gets CANCELLED — no
    handle left pending anywhere."""
    model, params = model_and_params
    rng = np.random.default_rng(14)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 tenants={"a": TenantClass(), "b": TenantClass(),
                          "hi": TenantClass(priority=1)})
    h_run = eng.submit(p, 10, tenant="a")
    while not h_run.tokens:
        eng.step()
    # queued AFTER h_run holds the slot; close() fires before another
    # step, so even the high-priority one is still queued (preemption
    # only happens inside step())
    queued = ([eng.submit(p, 3, tenant="a")]
              + [eng.submit(p, 3, tenant="b") for _ in range(2)]
              + [eng.submit(p, 3, tenant="hi")])
    eng.close()
    assert h_run.finish_reason is FinishReason.CANCELLED and h_run.tokens
    for h in queued:
        assert h.done and h.finish_reason is FinishReason.SHED
    assert eng.queue_depth == 0 and eng.slots_in_use == 0
    assert eng.stats["shed"] == 4
    assert eng.tenant_stats["b"]["shed"] == 2
    assert eng.tenant_stats["hi"]["shed"] == 1


# -- off-switch: stats schema + finish-reason map (satellite pins) -----

# The engine's stats schema with tenancy OFF, exactly as PR 5 left it:
# the keys a workload exercising completion, cancellation, deadlines,
# queue-limit shedding, and step-failure containment produces.  Tenancy
# must not leak new keys (e.g. "preempted") into this set — consumers
# (serve_bench rows, the soak gate) treat the schema as an interface.
PR5_BASE_STATS = {
    "submitted", "admitted", "steps", "prefill_chunks", "decode_steps",
    "active_slot_steps", "tokens", "completed", "cancelled",
    "deadline_expired", "shed", "step_failures", "requeued", "errors",
    # the TTFT split at each request's first token (PR 26; obs on)
    "first_tokens", "ttft_s", "ttft_queue_s", "ttft_prefill_wait_s",
}
PR5_SPEC_STATS = {"verify_steps", "draft_tokens", "draft_accepted"}
PR5_PREFIX_STATS = {"prefix_lookups", "prefix_hit_tokens",
                    "prefix_published_blocks"}


def test_stats_schema_pinned_with_tenancy_off(model_and_params):
    """With tenants=None the engine's stats key set is EXACTLY the PR 5
    schema for a workload that exercises every counter-producing path —
    no tenancy key may appear, and tenant_stats is empty."""
    model, params = model_and_params
    rng = np.random.default_rng(15)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 queue_limit=2)
    eng.submit(p, 2)
    eng.submit(p, 2)
    with pytest.raises(QueueFull):
        eng.submit(p, 2)                       # shed
    eng.step()
    h_cancel = eng.submit(p, 2)
    h_cancel.cancel()                          # cancelled
    h_dead = eng.submit(p, 2, ttft_deadline_s=1e-7)
    time.sleep(0.001)
    eng.run_until_complete()                   # completed + deadline
    assert h_dead.finish_reason is FinishReason.DEADLINE
    hook = FaultySteps(fail_at=set(range(200)), kind="decode")
    eng.step_fault_hook = hook
    h_err = eng.submit(p, 3)  # needs 2 decode steps -> fails twice
    eng.run_until_complete()                   # requeued then error
    assert h_err.finish_reason is FinishReason.ERROR
    assert set(eng.stats) == PR5_BASE_STATS
    assert eng.tenant_stats == {}

    spec = Engine(model, params, num_slots=1, max_len=32,
                  prefill_chunk=8, speculate_k=2,
                  drafter=NgramDrafter(max_ngram=3, min_ngram=2))
    rep = np.tile(rng.integers(0, 61, size=3), 4)[:9].astype(np.int32)
    spec.generate_many([rep], 6)
    assert set(spec.stats) == (PR5_BASE_STATS - {
        "cancelled", "deadline_expired", "shed", "step_failures",
        "requeued", "errors"}) | PR5_SPEC_STATS

    pref = Engine(model, params, num_slots=1, max_len=32,
                  prefill_chunk=8, prefix_cache_blocks=4)
    pref.generate_many([rng.integers(0, 61, size=9).astype(np.int32)], 2)
    assert set(pref.stats) == (PR5_BASE_STATS - {
        "cancelled", "deadline_expired", "shed", "step_failures",
        "requeued", "errors"}) | PR5_PREFIX_STATS


def test_finish_reason_counter_map_exhaustive():
    """Every FinishReason maps to a stats counter and vice versa — the
    guard against a new reason (PREEMPTED was the latest) landing
    without accounting, which would silently drop retirements from the
    stats schema."""
    assert set(_FINISH_COUNTER) == set(FinishReason)
    for reason, counter in _FINISH_COUNTER.items():
        assert isinstance(counter, str) and counter
    # success reasons share one counter; every failure reason is its own
    assert _FINISH_COUNTER[FinishReason.COMPLETE] == \
        _FINISH_COUNTER[FinishReason.EOS] == "completed"
    failures = {r: c for r, c in _FINISH_COUNTER.items()
                if r not in (FinishReason.COMPLETE, FinishReason.EOS)}
    assert len(set(failures.values())) == len(failures)


def test_tenancy_off_engine_has_no_tenancy_behavior(model_and_params):
    """tenants=None: queue_depth/admission/FIFO semantics are the old
    engine's (covered bit-exactly by tests/test_serve.py); here pin the
    tenancy surface itself — no scheduler, empty tenant_stats, handles
    carry tenant=None and zero preemptions."""
    model, params = model_and_params
    rng = np.random.default_rng(16)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    h = eng.submit(p, 3)
    eng.run_until_complete()
    assert h.tenant is None and h.preemptions == 0
    assert eng._sched is None and eng.tenant_stats == {}
    assert "preempted" not in eng.stats
