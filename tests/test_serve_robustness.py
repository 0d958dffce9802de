"""tpudp.serve robustness layer: the contract is that NOTHING a client,
drafter, or device step does can wedge the arena or corrupt a surviving
stream.

  1. BOUNDED ADMISSION — ``queue_limit`` sheds overload with a typed
     ``QueueFull`` instead of growing the host queue; draining the queue
     re-opens admission.
  2. DEADLINES — expired ``deadline_s``/``ttft_deadline_s`` budgets
     retire requests with ``FinishReason.DEADLINE``; emitted tokens stay
     on the handle, the slot frees for queued work.
  3. DRAFTER QUARANTINE — a raising / malformed / slow drafter is
     permanently quarantined and every surviving greedy output stays
     bit-identical to ``generate()`` (drafts are hints; the referee is
     parity, exactly as in tests/test_speculate.py).
  4. STEP CONTAINMENT — an exception escaping a device step requeues the
     in-flight requests once (tokens + PRNG chain carried over, so the
     retry continues bit-identically) and retires second-time failures
     with ``ERROR``; the arena keeps serving.
  5. GRACEFUL SHUTDOWN — ``drain()`` finishes accepted work and rejects
     new submits; ``close()`` retires everything immediately.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import generate
from tpudp.models.gpt2 import gpt2_small
from tpudp.serve import (Engine, EngineClosed, FinishReason, NgramDrafter,
                         QueueFull, RequestFailed)
from tpudp.serve.faults import (BitFlipLogits, FailingDrafter, FaultySteps,
                                InjectedFault, MalformedDrafter, SlowDrafter,
                                SlowSteps)
from tpudp.train import init_state, make_optimizer
from tpudp.utils.watchdog import Watchdog

TINY = dict(vocab_size=61, max_seq_len=64, num_layers=2, num_heads=2,
            d_model=32)


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt2_small(**TINY)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


def _reference(model, params, prompt, n):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]), n))


def _engine(model, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 8)
    return Engine(model, params, **kw)


# -- bounded admission -------------------------------------------------


def test_queue_limit_sheds_with_queue_full(model_and_params):
    """Submits past queue_limit raise QueueFull and bump the shed
    counter; draining the queue (admission) re-opens the door —
    backpressure, not a one-way valve."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _engine(model, params, num_slots=1, queue_limit=2)
    h1 = eng.submit(p, 3)
    h2 = eng.submit(p, 3)
    with pytest.raises(QueueFull, match="queue_limit"):
        eng.submit(p, 3)
    assert eng.stats["shed"] == 1
    eng.step()  # admits h1 -> queue depth back under the limit
    h3 = eng.submit(p, 3)
    eng.run_until_complete()
    assert all(h.finish_reason is FinishReason.COMPLETE
               for h in (h1, h2, h3))
    ref = _reference(model, params, p, 3)[0, 4:]
    for h in (h1, h2, h3):
        np.testing.assert_array_equal(ref, np.asarray(h.tokens))


def test_queue_limit_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="queue_limit"):
        _engine(model, params, queue_limit=0)
    with pytest.raises(ValueError, match="drafter_timeout_s"):
        _engine(model, params, drafter_timeout_s=0.0)
    with pytest.raises(ValueError, match="step_timeout_s"):
        _engine(model, params, step_timeout_s=-1.0)


# -- deadlines ---------------------------------------------------------


def test_ttft_deadline_expires_queued_request(model_and_params):
    """A queued request whose TTFT budget expires before it reaches a
    slot retires with DEADLINE (no slot, no prefill chunk wasted); the
    co-resident request is untouched."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _engine(model, params, num_slots=1)
    h1 = eng.submit(p, 6)
    eng.step()  # h1 takes the only slot
    h2 = eng.submit(p, 3, ttft_deadline_s=1e-6)
    time.sleep(0.002)
    eng.step()
    assert h2.done and h2.finish_reason is FinishReason.DEADLINE
    assert h2.tokens == [] and h2._slot is None
    assert eng.stats["deadline_expired"] == 1
    with pytest.raises(RequestFailed, match="deadline"):
        h2.result()
    eng.run_until_complete()
    np.testing.assert_array_equal(
        _reference(model, params, p, 6)[0, 4:], np.asarray(h1.tokens))


def test_deadline_mid_flight_keeps_tokens_and_frees_slot(model_and_params):
    """An in-flight request past deadline_s retires with DEADLINE: the
    tokens already emitted stay on the handle and the freed slot serves
    the next queued request (bit-exact, proving clean slot reuse)."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 61, size=4).astype(np.int32)
    p2 = rng.integers(0, 61, size=9).astype(np.int32)
    eng = _engine(model, params, num_slots=1)
    h1 = eng.submit(p1, 20, deadline_s=0.05)
    h2 = eng.submit(p2, 4)
    while not h1.tokens:
        eng.step()
    assert not h1.done
    time.sleep(0.06)  # blow h1's total budget mid-flight
    eng.step()
    assert h1.done and h1.finish_reason is FinishReason.DEADLINE
    assert len(h1.tokens) >= 1  # partial progress preserved
    partial = list(h1.tokens)
    eng.run_until_complete()
    assert h1.tokens == partial  # nothing appended after expiry
    np.testing.assert_array_equal(
        _reference(model, params, p2, 4)[0, 9:], np.asarray(h2.tokens))
    assert eng.stats["deadline_expired"] == 1
    assert eng.slots_in_use == 0 and eng.queue_depth == 0


def test_ttft_deadline_stops_applying_after_first_token(model_and_params):
    """ttft_deadline_s is a first-token SLO only: once a token has been
    emitted, an elapsed TTFT budget must not retire the request."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _engine(model, params, num_slots=1)
    h = eng.submit(p, 4, ttft_deadline_s=5.0)
    while not h.tokens:
        eng.step()
    time.sleep(0.002)  # well under 5s; and the budget no longer applies
    eng.run_until_complete()
    assert h.finish_reason is FinishReason.COMPLETE
    np.testing.assert_array_equal(
        _reference(model, params, p, 4)[0, 4:], np.asarray(h.tokens))


def test_deadline_validation(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params)
    p = np.zeros(4, np.int32)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(p, 2, deadline_s=0.0)
    with pytest.raises(ValueError, match="ttft_deadline_s"):
        eng.submit(p, 2, ttft_deadline_s=-1.0)


# -- drafter quarantine ------------------------------------------------


def _parity_run(model, params, eng, prompts, max_new):
    handles = [eng.submit(p, n) for p, n in zip(prompts, max_new)]
    eng.run_until_complete()
    for p, n, h in zip(prompts, max_new, handles):
        assert h.finish_reason is FinishReason.COMPLETE
        np.testing.assert_array_equal(
            _reference(model, params, p, n)[0, p.size:],
            np.asarray(h.tokens))
    return handles


def test_raising_drafter_quarantined_with_parity(model_and_params):
    """A drafter that dies mid-run is quarantined; every output stays
    bit-identical to generate(), and the engine stops paying for verify
    windows from the quarantine on."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    # Repetitive prompts so the healthy inner drafter actually drafts.
    prompts = [np.tile(rng.integers(0, 61, size=3), 4)[:9].astype(np.int32)
               for _ in range(3)]
    eng = _engine(model, params, speculate_k=2,
                  drafter=FailingDrafter(inner=NgramDrafter(),
                                         ok_proposals=2))
    _parity_run(model, params, eng, prompts, [6, 6, 6])
    assert eng.drafter_quarantined
    assert "InjectedFault" in eng.drafter_quarantine_reason
    assert eng.stats["drafter_quarantined"] == 1
    # Quarantine is permanent: later requests never re-enter the verify
    # path (no drafter call can stall or corrupt them again).
    verify_steps = eng.stats["verify_steps"]
    _parity_run(model, params, eng, prompts[:1], [4])
    assert eng.stats["verify_steps"] == verify_steps


@pytest.mark.parametrize("mode", MalformedDrafter.MODES)
def test_malformed_drafter_quarantined_with_parity(model_and_params, mode):
    model, params = model_and_params
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 61, size=5).astype(np.int32)]
    eng = _engine(model, params, speculate_k=3,
                  drafter=MalformedDrafter(mode))
    _parity_run(model, params, eng, prompts, [6])
    assert eng.drafter_quarantined
    assert eng.stats["drafter_quarantined"] == 1


def test_malformed_proposal_counts_as_rejected(model_and_params):
    """An out-of-vocab proposal is charged proposed-and-rejected, so
    acceptance accounting stays truthful through a quarantine."""
    model, params = model_and_params
    rng = np.random.default_rng(6)
    p = rng.integers(0, 61, size=5).astype(np.int32)
    eng = _engine(model, params, speculate_k=2,
                  drafter=MalformedDrafter("out_of_vocab"))
    h = eng.submit(p, 4)
    eng.run_until_complete()
    assert h.draft_proposed > 0 and h.draft_accepted == 0
    assert h.acceptance_rate == 0.0 and eng.acceptance_rate == 0.0


def test_slow_drafter_quarantined_by_time_budget(model_and_params):
    """A drafter exceeding drafter_timeout_s per propose is quarantined
    even though its tokens are valid — a stalling drafter is as bad as a
    lying one for a latency SLO."""
    model, params = model_and_params
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 61, size=5).astype(np.int32)]
    eng = _engine(model, params, speculate_k=2, drafter_timeout_s=0.01,
                  drafter=SlowDrafter(0.05))
    _parity_run(model, params, eng, prompts, [5])
    assert eng.drafter_quarantined
    assert "drafter_timeout_s" in eng.drafter_quarantine_reason


def test_blocking_drafter_detected_by_watchdog(model_and_params):
    """A drafter that BLOCKS past the watchdog deadline (no
    drafter_timeout_s set — the host-side timing check never sees a call
    that hasn't returned) is caught by the scoped watchdog guard armed
    around propose(): the monitor fires while propose is blocked
    (kill=True would exit for the scheduler right there) and kill=False
    quarantines the drafter the moment the call comes back.  Outputs
    stay bit-identical throughout."""
    model, params = model_and_params
    rng = np.random.default_rng(18)
    prompts = [rng.integers(0, 61, size=5).astype(np.int32)]
    wd = Watchdog(timeout_s=0.05, kill=False, poll_s=0.01).start()
    try:
        eng = _engine(model, params, speculate_k=2, watchdog=wd,
                      step_timeout_s=0.05, drafter=SlowDrafter(0.2))
        _parity_run(model, params, eng, prompts, [5])
        assert eng.drafter_quarantined
        assert "watchdog deadline" in eng.drafter_quarantine_reason
        assert eng.stats["step_failures"] == 0  # charged to the drafter
    finally:
        wd.stop()


# -- step-failure containment ------------------------------------------


def test_transient_step_fault_requeues_and_completes_with_parity(
        model_and_params):
    """One injected device-step failure: every in-flight request is
    requeued once and finishes bit-identically to generate() — a
    transient fault costs latency, never correctness or data."""
    model, params = model_and_params
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (5, 9, 3)]
    hook = FaultySteps(fail_at={6})  # whatever program call 6 lands on
    eng = _engine(model, params, step_fault_hook=hook)
    _parity_run(model, params, eng, prompts, [6, 5, 7])
    assert hook.fired and eng.stats["step_failures"] == 1
    assert eng.stats["requeued"] >= 1 and eng.stats["errors"] == 0
    assert eng.slots_in_use == 0 and eng.queue_depth == 0


def test_step_fault_sampled_request_resumes_bit_identically(
        model_and_params):
    """The requeue carries the per-slot PRNG chain, so even a SAMPLED
    request survives a step failure with bit-identical draws (the
    serving analogue of elastic resume's exactly-once contract)."""
    model, params = model_and_params
    rng = np.random.default_rng(9)
    p = rng.integers(0, 61, size=5).astype(np.int32)

    def tokens_of(hook):
        eng = _engine(model, params, num_slots=1, step_fault_hook=hook)
        h = eng.submit(p, 8, temperature=0.9, top_k=12, seed=7)
        eng.run_until_complete()
        assert h.finish_reason is FinishReason.COMPLETE
        return list(h.tokens)

    clean = tokens_of(None)
    faulted = tokens_of(FaultySteps(fail_at={4}, kind="decode"))
    assert faulted == clean


def test_persistent_step_fault_retires_error_and_arena_survives(
        model_and_params):
    """A fault that keeps firing exhausts the requeue-once budget: the
    affected requests retire with ERROR (result() raises; partial tokens
    stay) while the arena itself keeps serving — clear the hook and the
    next request completes with parity."""
    model, params = model_and_params
    rng = np.random.default_rng(10)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    hook = FaultySteps(fail_at=set(range(200)), kind="decode")
    eng = _engine(model, params, num_slots=1, step_fault_hook=hook)
    h = eng.submit(p, 6)
    eng.run_until_complete()
    assert h.done and h.finish_reason is FinishReason.ERROR
    assert isinstance(h.error, InjectedFault)
    with pytest.raises(RequestFailed, match="error"):
        h.result()
    assert eng.stats["errors"] == 1 and eng.stats["requeued"] == 1
    assert eng.slots_in_use == 0 and eng.queue_depth == 0
    # The arena was never wedged: with the fault gone, service resumes.
    eng.step_fault_hook = None
    h2 = eng.submit(p, 6)
    eng.run_until_complete()
    assert h2.finish_reason is FinishReason.COMPLETE
    np.testing.assert_array_equal(
        _reference(model, params, p, 6)[0, 4:], np.asarray(h2.tokens))


def test_step_fault_during_prefill_is_contained(model_and_params):
    """Failures in the prefill program are contained the same way as
    decode failures (the donated-arena rebuild covers every program)."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    p = rng.integers(0, 61, size=20).astype(np.int32)  # 3 chunks
    hook = FaultySteps(fail_at={1}, kind="prefill")
    eng = _engine(model, params, num_slots=1, max_len=48,
                  step_fault_hook=hook)
    h = eng.submit(p, 5)
    eng.run_until_complete()
    assert h.finish_reason is FinishReason.COMPLETE
    assert eng.stats["step_failures"] == 1
    np.testing.assert_array_equal(
        _reference(model, params, p, 5)[0, 20:], np.asarray(h.tokens))


# -- graceful shutdown -------------------------------------------------


def test_drain_finishes_accepted_work_and_rejects_new(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (4, 7, 5)]
    eng = _engine(model, params, num_slots=1)
    handles = [eng.submit(p, 4) for p in prompts]
    eng.step()  # first request in flight, two queued
    eng.drain()
    assert eng.closed and not eng.accepting
    assert all(h.finish_reason is FinishReason.COMPLETE for h in handles)
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(
            _reference(model, params, p, 4)[0, p.size:],
            np.asarray(h.tokens))
    with pytest.raises(EngineClosed, match="no longer accepts"):
        eng.submit(prompts[0], 2)
    assert eng.step() == []  # closed engine's step is a no-op
    eng.drain()  # idempotent


def test_close_cancels_in_flight_and_sheds_queued(model_and_params):
    model, params = model_and_params
    rng = np.random.default_rng(13)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _engine(model, params, num_slots=1)
    h1 = eng.submit(p, 10)
    h2 = eng.submit(p, 3)
    h3 = eng.submit(p, 3)
    while not h1.tokens:
        eng.step()
    eng.close()
    assert h1.finish_reason is FinishReason.CANCELLED and h1.tokens
    assert h2.finish_reason is FinishReason.SHED
    assert h3.finish_reason is FinishReason.SHED
    assert eng.slots_in_use == 0 and eng.queue_depth == 0
    assert eng.stats["shed"] == 2 and eng.stats["cancelled"] == 1
    with pytest.raises(EngineClosed):
        eng.submit(p, 2)
    eng.close()  # idempotent


# -- generate_many orphan fix ------------------------------------------


def test_generate_many_failure_cancels_already_submitted(model_and_params):
    """A validation error on prompt i must not orphan prompts 0..i-1 in
    the queue forever (pre-fix they pinned queue slots until the engine
    died); the engine stays fully usable afterwards."""
    model, params = model_and_params
    rng = np.random.default_rng(14)
    good = rng.integers(0, 61, size=4).astype(np.int32)
    with_bad = [good, good, np.zeros(0, np.int32)]  # empty prompt: invalid
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="prompt"):
        eng.generate_many(with_bad, 3)
    assert eng.queue_depth == 0 and eng.slots_in_use == 0
    assert eng.stats["cancelled"] == 2
    outs = eng.generate_many([good], 3)
    np.testing.assert_array_equal(
        _reference(model, params, good, 3)[0], outs[0])


# -- cancel() racing run_until_complete() ------------------------------


def test_cancel_queued_and_inflight_from_inside_token_iterator(
        model_and_params):
    """Cancel a still-queued request AND the in-flight request from
    inside the in-flight request's own token iterator (the consumer-
    disconnects-mid-stream shape): iteration ends promptly, the slot is
    reused cleanly (bit-parity referee), and stats stay consistent."""
    model, params = model_and_params
    rng = np.random.default_rng(15)
    p1 = rng.integers(0, 61, size=4).astype(np.int32)
    p2 = rng.integers(0, 61, size=6).astype(np.int32)
    p3 = rng.integers(0, 61, size=9).astype(np.int32)
    eng = _engine(model, params, num_slots=1)
    h1 = eng.submit(p1, 8)
    h2 = eng.submit(p2, 5)
    h3 = eng.submit(p3, 4)
    streamed = []
    for tok in h1:  # iteration drives the engine
        streamed.append(tok)
        if len(streamed) == 2:
            assert h2.cancel() is True   # still queued
            assert h1.cancel() is True   # in flight (this iterator!)
    assert h1.done and h1.cancelled and streamed == h1.tokens
    assert len(h1.tokens) == 2
    assert h2.done and h2.cancelled and h2.tokens == []
    assert not h3.done
    eng.run_until_complete()
    assert h3.finish_reason is FinishReason.COMPLETE
    np.testing.assert_array_equal(
        _reference(model, params, p3, 4)[0, 9:], np.asarray(h3.tokens))
    assert eng.stats["cancelled"] == 2 and eng.stats["completed"] == 1
    assert eng.stats["admitted"] == 2  # h2 never took a slot
    assert eng.slots_in_use == 0 and eng.queue_depth == 0


# -- watchdog arming ---------------------------------------------------


def test_watchdog_detects_wedged_step_and_engine_recovers(
        model_and_params):
    """A stalled device call (SlowSteps inside the watchdog's scoped
    deadline) is detected from OUTSIDE the blocked call; with kill=False
    the hang surfaces as a step failure at the next device call, is
    contained like any other, and the engine keeps serving."""
    model, params = model_and_params
    rng = np.random.default_rng(16)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    wd = Watchdog(timeout_s=0.05, kill=False, poll_s=0.01).start()
    try:
        eng = _engine(model, params, num_slots=1, watchdog=wd,
                      step_timeout_s=0.05,
                      step_fault_hook=SlowSteps(stall_at={3}, delay_s=0.2))
        h = eng.submit(p, 6)
        eng.run_until_complete()  # must terminate — the one forbidden
        #                           outcome is a wedge
        assert eng.stats["step_failures"] >= 1
        assert h.done
        # Containment acknowledged the hang, so the engine still serves.
        eng.step_fault_hook = None
        h2 = eng.submit(p, 4)
        eng.run_until_complete()
        assert h2.finish_reason is FinishReason.COMPLETE
        np.testing.assert_array_equal(
            _reference(model, params, p, 4)[0, 4:], np.asarray(h2.tokens))
    finally:
        wd.stop()


# -- finish_reason contract --------------------------------------------


def test_finish_reason_success_paths(model_and_params):
    """COMPLETE vs EOS are distinguished; both are success (result()
    returns) and both count under stats['completed']."""
    model, params = model_and_params
    rng = np.random.default_rng(17)
    p = rng.integers(0, 61, size=5).astype(np.int32)
    ref = _reference(model, params, p, 8)[0, 5:]
    eos = int(ref[2])
    eng = _engine(model, params)
    h_full = eng.submit(p, 8)
    h_eos = eng.submit(p, 8, eos_id=eos)
    eng.run_until_complete()
    assert h_full.finish_reason is FinishReason.COMPLETE and h_full.ok
    assert h_eos.finish_reason is FinishReason.EOS and h_eos.ok
    assert eng.stats["completed"] == 2
    np.testing.assert_array_equal(h_full.result()[5:], ref)
    assert h_eos.result()[-1] == eos


# -- tooling gate ------------------------------------------------------


# -- SDC canaries (silent corruption on the serving path) --------------


def _canary_engine(model, params, **kw):
    kw.setdefault("canary_every_s", 0.0)
    kw.setdefault("canary_new_tokens", 4)
    return _engine(model, params, **kw)


def test_canary_pins_reference_and_runs_clean(model_and_params):
    """Greedy decode is deterministic, so the first clean canary run IS
    the oracle: later runs byte-compare against it.  A healthy engine
    under real traffic must pin the reference, keep re-running, and
    never quarantine — while user outputs stay bit-exact."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    eng = _canary_engine(model, params)
    hs = [eng.submit(p, 5) for _ in range(3)]
    eng.run_until_complete()
    for _ in range(40):
        eng.step()
    m = eng.metrics()["canary"]
    assert m["runs"] >= 2 and m["ref_pinned"]
    assert m["mismatch"] == 0 and not m["quarantined"]
    want = _reference(model, params, p, 5)[0, p.size:]
    for h in hs:
        assert h.finish_reason is FinishReason.COMPLETE
        np.testing.assert_array_equal(want, np.asarray(h.tokens))


def test_canary_pairs_never_emitted(model_and_params):
    """Canary traffic is the engine's own probe: its (request, token)
    pairs must never reach the emitted stream a server loop forwards
    to clients."""
    model, params = model_and_params
    eng = _canary_engine(model, params)
    emitted = []
    for _ in range(60):
        emitted += eng.step()
    assert eng.metrics()["canary"]["runs"] >= 1
    assert all(not getattr(r, "_canary", False) for r, _ in emitted)


def test_canary_mismatch_quarantines_and_parks_live_work(
        model_and_params):
    """A canary-only bit flip (invisible to every loud detector — no
    raise, no NaN, no counter) must: quarantine the engine with a
    reason naming the first divergent token, stop admission with a
    typed error, make step() a no-op, and PARK live requests unfinished
    so the cluster can migrate them out — never finish them on the
    condemned engine."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    p = rng.integers(0, 61, size=4).astype(np.int32)
    # call 5 = token 1 of the SECOND canary run (4 tokens each): run 1
    # pins the reference, run 2 diverges — and because the corrupted
    # token conditions later decode steps, downstream tokens shift too.
    inj = BitFlipLogits([(5, None, 3)], vocab=61, canary_only=True)
    eng = _canary_engine(model, params, token_fault_hook=inj)
    live = eng.submit(p, 20, seed=7)
    for _ in range(200):
        if eng.quarantined:
            break
        eng.step()
    assert eng.quarantined
    m = eng.metrics()["canary"]
    assert m["mismatch"] == 1 and m["quarantined"]
    assert "canary" in eng.quarantine_reason
    assert inj.fired and inj.fired[0][0] == 5
    assert live.finish_reason is None and eng.slots_in_use >= 1
    with pytest.raises(EngineClosed):
        eng.submit(p, 3)
    assert eng.step() == []


def test_canary_loud_failure_is_error_not_corruption(model_and_params):
    """A canary that fails LOUDLY (deadline, error) is an availability
    event, not corruption evidence: counted canary_errors, engine stays
    in service."""
    model, params = model_and_params
    hook = FaultySteps(fail_at=set(range(1, 200)))  # every step raises
    eng = _canary_engine(model, params, step_fault_hook=hook)
    for _ in range(30):
        eng.step()
    m = eng.metrics()["canary"]
    assert m["errors"] >= 1 and m["mismatch"] == 0
    assert not eng.quarantined


def test_canary_config_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="canary_every_s"):
        _engine(model, params, canary_every_s=-1.0)
    with pytest.raises(ValueError, match="canary_new_tokens"):
        _engine(model, params, canary_every_s=1.0, canary_new_tokens=0)


def test_bitflip_logits_schedule_determinism():
    """The serving injector mirrors the training injectors' pinned
    determinism: calls index ELIGIBLE commits only (canary_only skips
    user traffic WITHOUT counting, so a canary schedule is stable no
    matter how much real traffic interleaves), a schedule entry fires
    once, and the corrupted token is always in-vocab and different."""

    class _R:
        pass

    canary = _R()
    canary._canary = True
    user = _R()
    inj = BitFlipLogits([(1, None, 3)], vocab=61, canary_only=True)
    assert inj(0, 7, user) == 7          # user commit: not counted
    assert inj(0, 7, canary) == 7        # eligible call 0: no match
    out = inj(2, 7, canary)              # eligible call 1: fires
    assert out != 7 and 0 <= out < 61
    assert inj.fired == [(1, 2, 7, out)]
    assert inj(2, 7, canary) == 7        # schedule exhausted
    # vocab fallback: a flip that would leave the vocabulary drops to
    # lower bits until the corrupt token is decodable
    inj2 = BitFlipLogits([(0, None, 6)], vocab=61)
    got = inj2(0, 60, object())
    assert got != 60 and 0 <= got < 61
    with pytest.raises(ValueError):
        BitFlipLogits([(-1, None, 0)])
    with pytest.raises(ValueError):
        BitFlipLogits([(0, None, 0)], vocab=1)
