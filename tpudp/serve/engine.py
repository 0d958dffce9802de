"""Continuous-batching inference engine — many requests, ONE compiled step.

``tpudp.models.generate`` decodes one request at a time: a second request
waits for the first's entire ``lax.scan`` to finish, so TPU utilization
collapses under concurrency.  But the decode step's cost is dominated by
WEIGHT reads (every parameter crosses HBM once per step regardless of
batch), so batching concurrent requests into one step multiplies
tokens/sec nearly for free — the serving analogue of the training
lesson that throughput comes from letting one compiled program amortize
work across the batch.

Design (static shapes everywhere — the TPU rule that shapes are compile
-time constants holds for serving too):

  * **Slot-based KV arena** — ONE preallocated ``(layers, num_slots,
    max_len, kv_heads, head_dim)`` KVCache.  A request is admitted by
    picking a free slot index and retired by freeing it; array shapes
    never change, so the jitted decode step compiles exactly once per
    ``(config, num_slots, max_len)`` and admission/retirement churn never
    recompiles (``TRACE_COUNTS`` observes this; a test pins it).
  * **Weights are arguments** — every step program takes the params as
    its first (traced, never donated) argument (``_build_steps``), so
    the device holds ONE copy of the weights however many programs run
    over them, and programs are built once per model CONFIG: engines
    over the same config share one set of compiled programs whatever
    weights they serve.  (Until PR 21 the programs closed over the
    weights as compile-time constants; on the TPU v5e each program then
    carried its own copy — see ``_build_steps``.)
  * **Slot-masked decode step** — all ``num_slots`` rows run every step
    with PER-ROW positions (``models.generate._forward_cached``'s vector
    -``pos`` path).  Inactive rows compute garbage that is never read:
    each row is independent, and any garbage KV a masked row writes at
    its current depth is overwritten by the write of whichever token is
    actually processed at that depth before any query can attend to it
    (writes happen before the attention read inside the same forward).
  * **Chunked prefill** — prompts enter through the same cached forward
    in fixed ``prefill_chunk``-token chunks (one chunk per engine step,
    single slot, batch 1, the scalar-``pos`` path sliced to that slot's
    arena row), so a long prompt never stalls in-flight decodes for more
    than one chunk.  Chunk starts are multiples of ``prefill_chunk`` and
    ``max_len`` is rounded to a chunk multiple, so the fixed-size chunk
    write can never be clamped into clobbering earlier positions.
  * **Per-request sampling** — temperature/top-k/top-p/PRNG key live in
    per-slot ARRAYS (``tpudp.ops.sampling``), traced not static, so any
    mix of sampling params shares the one compiled step.  Each slot's
    key chain advances once per OWN sampling event, making a request's
    sampled output reproducible regardless of admission order or which
    requests are co-resident — greedy requests are bit-identical to
    standalone ``generate()`` (the parity tests referee).
  * **Prefix caching** (``prefix_cache_blocks > 0``) — a block-granular
    KV pool + radix tree over token prefixes (``tpudp.serve.
    prefix_cache``; blocks sized to ``prefill_chunk`` so cache
    granularity aligns with chunk boundaries).  On admission the
    scheduler looks up the longest cached block-aligned prefix of the
    request's fill and COPIES those blocks into the slot's arena rows
    (one compiled ``dynamic_update_slice`` program, traced
    block/slot/pos scalars — compile-once like every other step),
    prefilling only the uncached tail; on retirement the slot's
    block-aligned PREFILLED prefix is published back to the pool
    (insert-or-ref in the radix tree, cold unreferenced leaves evicted
    under the block budget).  Prefill is deterministic given tokens and
    only chunk-prefilled positions are ever published, so copied KV
    equals recomputed KV bit-for-bit and greedy outputs stay identical
    to ``generate()`` (``stats["prefix_hit_tokens"]`` /
    ``stats["prefix_lookups"]`` account the traffic; ``0`` blocks — the
    default — disables the subsystem byte-for-byte).
  * **True paged attention** (``kv_pages > 0``) — the dense per-model
    slot arenas are replaced by ONE shared page pool per KV geometry
    plus per-slot block tables (``(num_slots, max_pages)`` int32): the
    decode/verify/prefill/fused programs read K/V THROUGH the table
    inside the attention contraction (``tpudp.ops.paged_attention`` —
    blockwise over ``(pages, page_size)`` tiles, fp outputs bitwise
    identical to the dense math: the paged-parity contract) and commit
    each new token's K/V directly into the one page containing its
    position — the per-step full-view gather/scatter of the original
    paged engine is gone (``paged_attn='gather'`` keeps that baseline
    for comparison; ``paged_attn='kernel'`` — the default on TPU —
    runs the whole hot path through Pallas kernels: paged decode, the
    flash-window verify/prefill kernel, kernels dispatched inside the
    fused loop bodies, and the tree-verify kernel, tolerance-bounded
    like flash with per-program einsum fall-back recorded in
    ``metrics()``).
    A prefix-cache hit becomes a TABLE
    WRITE (refcount bump on the radix tree's pages — zero
    ``copy_block_in`` copies) with copy-on-write at the divergence
    block: shared pages are never written, the first divergent chunk
    re-prefills into a fresh private page.  Retirement publishes by
    transferring page ownership to the tree (host metadata, no device
    copy).  Pages are allocated lazily as slots deepen — the
    overcommit that multiplies capacity under shared-prefix traffic —
    and pool pressure first evicts cold cache leaves, then vacates the
    most-recently-admitted slot through the bit-exact resume path.
    Co-resident models of one KV geometry share one pool, so an idle
    tenant reserves zero KV instead of a dense arena.
    ``kv_dtype="int8"`` stores page payloads quantized (half the bytes
    per token — a capacity doubler behind the same tables; outputs
    then track the fp engine within quantization tolerance instead of
    bit-exactly).  ``kv_pages=0`` — the default — is byte-for-byte the
    dense engine.
  * **Speculative decoding** (``speculate_k > 0``) — a host-side drafter
    (``tpudp.serve.speculate``) proposes up to k tokens per decoding
    slot; ONE verify forward scores the ``k+1``-token window at per-row
    positions and accepts the longest prefix the target model agrees
    with, so a step emits up to k+1 tokens per weight read.  Rejected
    tokens simply don't advance ``lengths`` — their stale KV rows are
    overwritten by the next window's ``update_cache_rows`` write before
    any query can see them (the same overwrite-before-visible rule the
    masked slots rely on).  Rows with no drafts (still prefilling
    neighbours, drafter came up empty) run through the same verify step
    with ``n_draft = 0`` and behave exactly like plain decode — mixed
    batches never need a second program, and the verify step compiles
    once per (config, num_slots, max_len, k).

  * **Fused decode windows** (``decode_fuse > 1``) — on "pure decode"
    iterations (no queued work, nothing prefilling, no speculation this
    step) the scheduler dispatches ONE jitted ``lax.while_loop`` program
    that runs up to ``decode_fuse`` decode iterations entirely on
    device: per-slot attention/KV append via the same vector-position
    forward, per-slot traced sampling with the PRNG chains advanced
    INSIDE the loop, and a loop predicate that exits early once every
    running slot has hit EOS or its token budget.  The per-token host
    round trip — scheduler iteration → one jitted step → host sync,
    the decode ceiling at small batch on a real TPU, where dispatch
    overhead beats FLOPs (arXiv:2204.06514) — becomes ONE fetch per
    up-to-N-token window.  Committed tokens, per-slot PRNG state, and
    arena positions come back as loop carry, so falling back to the
    single-step path (admission, retirement, speculation, preemption,
    deadlines — any step where the host must intervene) resumes
    bit-identically; deadlines are detected at window edges (overshoot
    bounded by the window).  ``decode_fuse=1`` — the default — is
    byte-for-byte the single-step engine, stats keys and trace counts
    included.  ``fuse_stream=True`` adds an ordered ``io_callback``
    inside the loop that taps each iteration's committed tokens into a
    host ring buffer (:attr:`Engine.fused_stream`) — observability
    only, never the commit path.

Host-side scheduling (admission, retirement, chunk bookkeeping, draft
proposal, cancellation) is plain Python between device steps — the same
split as the training stack (host data pipeline around a jitted step).

**Robustness layer** (the serving mirror of the trainer's watchdog +
elastic-resume posture; SURVEY.md §5 records the reference hanging
forever on any fault):

  * **Bounded admission** — ``Engine(queue_limit=N)`` sheds overload with
    a typed :class:`QueueFull` instead of growing the host queue without
    bound (``stats["shed"]`` counts refusals).
  * **Deadlines** — ``submit(..., deadline_s=, ttft_deadline_s=)``
    budgets are checked at every scheduler iteration; an expired request
    retires with ``FinishReason.DEADLINE`` (emitted tokens stay on the
    handle, the slot frees for the next queued request).
  * **Drafter quarantine** — a drafter that raises, returns malformed or
    out-of-vocab tokens, or exceeds ``drafter_timeout_s`` per propose is
    permanently quarantined: the engine falls back to the plain decode
    program (outputs unchanged — drafts were only ever hints) and
    records why.  ``tpudp.serve.faults`` provides deterministic
    injectors.
  * **Step-failure containment** — an exception escaping a device step
    cannot wedge the arena: the donated KV cache is rebuilt, every
    in-flight request is requeued ONCE (its emitted tokens and PRNG
    chain carry over, so the retried request continues bit-identically),
    and a request failing a second time retires with
    ``FinishReason.ERROR``.  Queued work is untouched — the arena keeps
    serving.
  * **Graceful shutdown** — :meth:`Engine.drain` stops admission and
    finishes all accepted work; :meth:`Engine.close` stops admission and
    retires everything immediately; both make later ``submit()`` raise
    :class:`EngineClosed`.
  * **Watchdog arming** — ``Engine(watchdog=wd, step_timeout_s=s)``
    wraps every blocking device call in a scoped watchdog deadline
    (``tpudp.utils.watchdog.Watchdog.step``), so a wedged TPU step is
    detected from OUTSIDE the blocked call, mirroring the trainer.

**Multi-tenancy layer** (``tpudp.serve.tenancy``; ``tenants=None`` — the
default — is byte-for-byte the old engine, stats keys and trace counts
included):

  * **Tenant classes** — ``Engine(tenants={name: TenantClass(...)})``
    plus ``submit(..., tenant=name)`` classes traffic into priority
    tiers: per-class bounded queues shed with the same typed
    :class:`QueueFull`, per-class ``default_deadline_s`` applies the
    deadline machinery class-wide, and admission is strict-priority
    across classes with deterministic stride (weighted fair) scheduling
    among classes at equal priority.
  * **Preemption** — when a higher-priority request waits and no slot
    is free, the scheduler evicts the lowest-priority in-flight slot
    through the SAME carry-over path as step-failure requeue: emitted
    tokens and the per-slot PRNG chain ride along, the request resumes
    at the front of its class queue and completes bit-identically, so
    ``FinishReason.PREEMPTED`` is never user-visible (the handle's
    ``finish_reason`` stays None until the request actually finishes).
    Preemption changes array VALUES only — slot state and the arena
    keep their shapes, so no preemption storm can ever recompile.
  * **Co-resident models** — ``Engine(models={name: (model, params)})``
    registers additional model/params pairs behind the same scheduler:
    each gets its own slot arena and its config's step programs bound
    to its weights (programs are memoized per config), a
    ``TenantClass(model=name)`` routes its class there, and one host
    loop batches each model's decoding slots through that model's own
    step — per-request math is exactly the single-model engine's, so
    greedy outputs stay bit-identical to each model's ``generate()``.

**Observability layer** (``tpudp.obs``, docs/OBSERVABILITY.md): every
device call rides an allocation-free span named after its kind (the
``_device`` seam — the same names the fault injectors and watchdog
regions use), request lifecycle lands as events off the hot path
(admit/finish/preempt/quarantine/containment, tenant+priority tagged),
the rest of ``step`` is spanned by phase, and the ``exposed`` span
clocks the device's waits from inside: it is open exactly while the
engine knows none of its programs is in flight (found by its blocking
fetches and by ``is_ready()`` checks at the phase boundaries), and its
time is charged to the phase the host was in (``EXPOSED_PHASES``) —
telemetry adds no host sync to any designated hot path, which
``tpudp.analysis lint`` enforces.  Step-failure containment and
watchdog timeouts dump the span ring to per-host flight records
(``flight_dir`` / ``TPUDP_FLIGHT_DIR``; no directory = no writes), so
a kill always leaves a timeline naming the failing region.
``obs=False`` no-ops the recorder and the exposed clock (no readiness
check runs); outputs and trace counts are the same either way.
"""

from __future__ import annotations

import collections
import contextlib
import enum
import functools
import itertools
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpudp.models.generate import (Int8Pages, KVCache, _forward_cached,
                                   _forward_paged, _forward_state,
                                   _forward_tree, _forward_tree_paged,
                                   _layer_pages, _stack_pages, gather_pages,
                                   page_layout, page_type, slot_arena,
                                   update_cache_rows, validate_decode_config,
                                   write_token_pages)
from tpudp.obs import NO_SPAN, FlightRecorder, Recorder
from tpudp.ops.sampling import (sample_tokens, split_keys, tree_depths,
                                verify_tokens, verify_tree_tokens)

# Trace-time side-effect counters: each jitted step body bumps its entry
# when (and only when) XLA traces it, so tests can assert the decode step
# compiles ONCE per engine geometry no matter how many requests churn
# through the slots.
TRACE_COUNTS = collections.Counter()

#: Span names of the ``_device`` seam: each times the DISPATCH of one
#: step program (asynchronous — the device's own time is felt in the
#: ``fetch`` / ``first_token_wait`` spans that block on its result).
DEVICE_SPANS = ("prefill", "decode", "verify", "tree_verify",
                "fused_decode", "fused_spec", "prefix_in", "prefix_out")

#: ``Engine.metrics()["stats"]`` seconds counters and the spans each
#: sums — the recorder's cumulative totals, so monotone like every other
#: stat (the ``*_seconds_total`` family an operator rates against
#: ``step_s``).  Absent with ``Engine(obs=False)``.
OBS_PHASE_SECONDS = {
    "step_s": ("step",), "admit_s": ("admit",),
    "dispatch_s": DEVICE_SPANS,
    "first_token_wait_s": ("first_token_wait",), "pages_s": ("pages",),
    "fetch_wait_s": ("fetch",), "commit_s": ("commit",),
    "chunk_s": ("chunk",), "batch_s": ("batch",), "canary_s": ("canary",),
}

#: ``Engine.metrics()["stats"]`` counters of a power-retention family's
#: work, counted on the host from the slots' depths (no fetch): slot-layers
#: whose state a decode run read, tail rows it attended, slot-layers whose
#: full tail it folded, and prompt tokens its prefill chunks took.
RETENTION_STATS = ("retention_state_reads", "retention_tail_rows",
                   "retention_folds", "retention_prefill_tokens")

#: The spans that are ``step``'s direct children: its time in none of
#: them is the scheduler's own, ``stats["step_self_s"]``.  The prefix
#: cache's block copies are not among them: they are dispatched inside
#: ``admit`` and ``commit``.
STEP_PHASES = ("canary", "admit", "chunk", "first_token_wait",
               "commit", "batch", "pages", "fetch",
               *(k for k in DEVICE_SPANS if not k.startswith("prefix_")))

#: Where the ``exposed`` span's time goes (``Engine.metrics()
#: ["exposed"]``): the phase the host was in while none of the engine's
#: device work was in flight — a child of ``step``, a block copy's
#: dispatch, ``step``'s own time, or ``caller``, the caller's code
#: between two ``step`` calls.
EXPOSED_PHASES = ("caller", "step", *STEP_PHASES, "prefix_in", "prefix_out")


class FinishReason(str, enum.Enum):
    """Why a request stopped.  ``COMPLETE``/``EOS`` are success; the rest
    are failures and make :meth:`Request.result` raise
    :class:`RequestFailed` (the emitted tokens stay on the handle)."""

    COMPLETE = "complete"    # max_new_tokens emitted
    EOS = "eos"              # sampled the request's eos_id
    CANCELLED = "cancelled"  # Engine.cancel()/Request.cancel()/close()
    DEADLINE = "deadline"    # deadline_s / ttft_deadline_s expired
    ERROR = "error"          # a device-step failure exhausted the requeue
    SHED = "shed"            # queued work discarded by Engine.close()
    PREEMPTED = "preempted"  # slot evicted for higher-priority work —
    #                          NEVER user-visible: the request requeues
    #                          with tokens + PRNG chain carried over and
    #                          finishes bit-identically under a terminal
    #                          reason (handle.finish_reason stays None
    #                          while preempted; stats["preempted"] and
    #                          Request.preemptions account it)


# stats counter bumped per finish reason (COMPLETE and EOS share
# "completed" — both are successful retirements, and existing consumers
# count successes there).
_FINISH_COUNTER = {
    FinishReason.COMPLETE: "completed",
    FinishReason.EOS: "completed",
    FinishReason.CANCELLED: "cancelled",
    FinishReason.DEADLINE: "deadline_expired",
    FinishReason.ERROR: "errors",
    FinishReason.SHED: "shed",
    FinishReason.PREEMPTED: "preempted",
}


class QueueFull(RuntimeError):
    """submit() refused: the engine's queue is at ``queue_limit``.
    Overload degrades by shedding work at the door instead of growing
    host memory without bound; callers retry, redirect, or drop."""


class EngineClosed(RuntimeError):
    """submit() (or generate_many()) called after :meth:`Engine.drain` or
    :meth:`Engine.close` — the engine no longer accepts work."""


class RequestFailed(RuntimeError):
    """:meth:`Request.result` called on a request that did not finish
    successfully.  Carries the handle (``.request``) and its
    ``.finish_reason``; tokens emitted before the failure remain on
    ``request.tokens``."""

    def __init__(self, request: "Request"):
        self.request = request
        self.finish_reason = request.finish_reason
        detail = f" ({request.error})" if request.error is not None else ""
        super().__init__(
            f"request {request.id} finished with "
            f"{request.finish_reason.value!r} after "
            f"{len(request.tokens)} of {request.max_new_tokens} "
            f"tokens{detail}")


class _Ring(collections.deque):
    """Bounded ``(slot, token)`` ring for ``fuse_stream`` — a deque
    subclass so the type names its contract; deques are already
    weak-referenceable, which the module registry below relies on to
    never keep a dead engine's ring alive."""


#: ring_id -> ring for the fused loop's io_callback tap.  Weak values:
#: the engine holds the only strong reference, so a collected engine's
#: ring drops out of the registry on its own.
_STREAM_RINGS: "weakref.WeakValueDictionary[int, _Ring]" = (
    weakref.WeakValueDictionary())
_RING_IDS = itertools.count()


def _stream_tap(ring_id, toks, running) -> None:
    """Host side of the fused loop's ordered ``io_callback``: append
    ``(slot, token)`` for every row that committed this iteration into
    the engine's ring buffer.  Observability only — the canonical commit
    path is the window's returned carry, so a full (bounded) ring drops
    oldest entries rather than stalling the device."""
    ring = _STREAM_RINGS.get(int(ring_id))
    if ring is None:
        return
    toks = np.asarray(toks)
    for s in np.nonzero(np.asarray(running))[0]:
        ring.append((int(s), int(toks[s])))


def _decode_math(forward, state, last_tokens, lengths, active, temps,
                 top_k, top_p, keys):
    """The ONE decode-step body shared by the dense and paged programs:
    ``forward`` hides the KV indirection (dense arena row writes vs
    page gather/scatter — it receives ``active`` so the paged scatter
    can mask), everything else — sampling, the per-slot PRNG advance
    discipline — exists exactly once, so the two twins can never drift
    apart."""
    logits, state = forward(state, last_tokens[:, None], lengths, active)
    carry, sub = split_keys(keys)
    toks = sample_tokens(logits[:, 0], temps, top_k, top_p, sub)
    # Only rows that actually sampled advance their key chain — a
    # request's draw stream must not depend on co-resident requests.
    new_keys = jnp.where(active[:, None], carry, keys)
    return state, toks, new_keys


def _first_token(logits, keys, meta):
    """The ONE first-token tail every prefill program ends with: the
    chunk's ``last`` row of logits ``(1, vocab)`` is sampled as a decode
    step samples a row — ``split_keys`` on the slot's initial key, then
    ``sample_tokens`` with the subkey — and, on the chunk that ends the
    prompt, the carry becomes the slot's entry of the key chain ``keys``.
    ``meta`` is the chunk's packed host scalars (:func:`_prefill_meta`).
    Any other chunk samples greedily and leaves the chain as it was; its
    token is never read.  Returns ``(token, keys)``."""
    slot, final = meta[2], meta[3] != 0
    key = lax.bitcast_convert_type(meta[4:6], jnp.uint32)
    temp, top_p = lax.bitcast_convert_type(meta[7:9], jnp.float32)
    carry, sub = split_keys(key[None])
    tok = sample_tokens(logits, temp[None], meta[6][None], top_p[None], sub)
    return tok[0], keys.at[slot].set(jnp.where(final, carry[0], keys[slot]))


def _verify_math(forward, state, tokens, lengths, active, n_draft,
                 temps, top_k, top_p, keys):
    """The ONE speculative-verify body shared by the dense and paged
    programs (window scoring, longest-agreeing-prefix acceptance, PRNG
    discipline — see :func:`_decode_math`)."""
    logits, state = forward(state, tokens, lengths, active)
    carry, sub = split_keys(keys)
    out, n_emit = verify_tokens(logits, tokens[:, 1:], n_draft,
                                temps, top_k, top_p, sub)
    new_keys = jnp.where(active[:, None], carry, keys)
    return state, out, n_emit, new_keys


def _fused_decode_math(forward, state, last_tokens, lengths, active,
                       temps, top_k, top_p, keys, budgets, eos_ids,
                       ring_id, *, n_steps, stream):
    """The ONE fused-window ``lax.while_loop`` shared by the dense and
    paged programs: loop carry, early-exit predicate, per-iteration
    commit/PRNG discipline, and the optional ordered
    ``io_callback`` stream tap all exist exactly once — only the
    per-iteration ``forward`` (arena vs page indirection) differs."""
    n_slots = last_tokens.shape[0]
    out0 = jnp.zeros((n_slots, n_steps), jnp.int32)
    n_emit0 = jnp.zeros((n_slots,), jnp.int32)

    def cond(carry):
        i, _state, _last, _lens, running, _keys, _out, _n_emit = carry
        return (i < n_steps) & jnp.any(running)

    def body(carry):
        i, state, last, lens, running, keys, out, n_emit = carry
        logits, state = forward(state, last[:, None], lens, running)
        carry_keys, sub = split_keys(keys)
        toks = sample_tokens(logits[:, 0], temps, top_k, top_p, sub)
        # Only rows still running advance their key chain / commit —
        # a retired row's chain must read exactly as of its last
        # committed token (the bit-exact resume contract shared with
        # requeue/preemption carry-over).
        keys = jnp.where(running[:, None], carry_keys, keys)
        toks = jnp.where(running, toks, last)
        if stream:
            from jax.experimental import io_callback

            io_callback(_stream_tap, None, ring_id, toks, running,
                        ordered=True)
        lens = jnp.where(running, lens + 1, lens)
        col = jnp.arange(n_steps)[None, :] == n_emit[:, None]
        out = jnp.where(col & running[:, None], toks[:, None], out)
        n_emit = jnp.where(running, n_emit + 1, n_emit)
        running = running & (toks != eos_ids) & (n_emit < budgets)
        return i + 1, state, toks, lens, running, keys, out, n_emit

    iters, state, _last, _lens, _running, keys, out, n_emit = (
        lax.while_loop(cond, body,
                       (jnp.int32(0), state, last_tokens, lengths,
                        active, keys, out0, n_emit0)))
    return state, out, n_emit, keys, iters


def _fused_spec_math(forward, draft_cfg, draft_params, state, hist,
                     last_tokens, lengths, active, temps, top_k, top_p,
                     keys, budgets, eos_ids, ring_id, *,
                     n_draft_k, n_steps, stream,
                     chunk_draft_prefill=False):
    """The ONE fused speculative-decode ``lax.while_loop`` shared by the
    dense and paged programs: each iteration drafts ``n_draft_k`` greedy
    tokens per running slot WITH THE DRAFT MODEL ON DEVICE, scores the
    ``k+1`` window with one batched verify forward, and runs the
    rejection-sampling accept/commit inside the carry — the host round
    trip per window (``_run_verify``'s draft gather + verify fetch)
    collapses to one fetch per up-to-``n_steps``-window program.

    The drafter math replicates ``speculate._draft_greedy`` batched over
    slots: an UNCACHED prefill of the ``(slots, hist_w)`` token history
    (pads behind the causal mask — contributing exact zeros — like the
    host drafter's padded bucket), then ``n_draft_k`` cached greedy
    steps.  The draft KV lives in its own arena INSIDE THE CARRY
    (``hist_w + k`` wide, the host drafter's exact ``bucket + k``
    geometry so a ``DraftModelDrafter(bucket=max_len)`` referee drafts
    bit-identically), zeroed at each window's re-prefill exactly as the
    host drafter recomputes per propose.  The PRNG discipline is
    ``_verify_math``'s verbatim: one split per window, subkey consumed
    by :func:`verify_tokens`, carry committed only for rows still
    running — so greedy AND sampled streams are bit-identical to the
    host-drafted engine's under identical chains (the parity oracle).
    The committed tokens scatter back into ``hist`` so the next window
    drafts from the grown context, again matching the host drafter.

    Per-row truncation mirrors the host replay: a window's emissions cut
    at the first EOS and at the remaining budget, the row's length/last/
    chain freeze when it stops, and the loop exits early once no row
    runs — the returned carry equals having run ``n_windows[s]`` verify
    steps per slot, which is the fall-back seam to ``_run_verify``.

    ``chunk_draft_prefill`` (the kernel builds set it) re-prefills the
    draft history in causal q-chunks instead of one ``hist_w``-wide
    forward: each row's attention sees the same padded cache width with
    the same mask, so per-row logits are BITWISE identical — only the
    peak score-tile footprint inside the loop body shrinks from
    ``(slots, heads, hist_w, hist_w + k)`` to one chunk's rows (the
    committed budget-ledger delta the kernel twin pins).
    """
    n_slots, hist_w = hist.shape
    W = n_draft_k + 1
    out0 = jnp.zeros((n_slots, n_steps * W), jnp.int32)
    zeros_i = jnp.zeros((n_slots,), jnp.int32)

    def cond(carry):
        (i, _state, _hist, _last, _lens, running, _keys, _out, _n_emit,
         _n_win, _n_acc) = carry
        return (i < n_steps) & jnp.any(running)

    def body(carry):
        (i, state, hist, last, lens, running, keys, out, n_emit, n_win,
         n_acc) = carry
        carry_keys, sub = split_keys(keys)
        # -- draft: k greedy tokens per slot from the draft model (the
        # batched _draft_greedy), re-prefilled from hist each window.
        dcache = KVCache.zeros(draft_cfg, n_slots, hist_w + n_draft_k)
        if chunk_draft_prefill:
            ch = next(c for c in range(min(hist_w, 8), 0, -1)
                      if hist_w % c == 0)
            lg0, dcache = _forward_cached(draft_cfg, draft_params,
                                          hist[:, :ch], dcache, 0)
            dlast = jnp.take_along_axis(
                lg0, jnp.clip(lens, 0, ch - 1)[:, None, None],
                axis=1)[:, 0]

            def pchunk(dc, c):
                dcache, dlast = dc
                toks = lax.dynamic_slice_in_dim(hist, c * ch, ch, axis=1)
                lg, dcache = _forward_cached(draft_cfg, draft_params,
                                             toks, dcache, c * ch)
                rel = lens - c * ch
                pick = jnp.take_along_axis(
                    lg, jnp.clip(rel, 0, ch - 1)[:, None, None],
                    axis=1)[:, 0]
                dlast = jnp.where(((rel >= 0) & (rel < ch))[:, None],
                                  pick, dlast)
                return (dcache, dlast), None

            (dcache, dlast), _ = lax.scan(pchunk, (dcache, dlast),
                                          jnp.arange(1, hist_w // ch))
        else:
            dlogits, dcache = _forward_cached(draft_cfg, draft_params,
                                              hist, dcache, 0)
            dlast = jax.vmap(lambda l, n: lax.dynamic_index_in_dim(
                l, n, axis=0, keepdims=False))(dlogits, lens)

        def dstep(dc, j):
            dcache, dlast = dc
            tok = jnp.argmax(dlast, axis=-1).astype(jnp.int32)
            lg, dcache = _forward_cached(draft_cfg, draft_params,
                                         tok[:, None], dcache,
                                         lens + 1 + j)
            return (dcache, lg[:, 0]), tok

        _, drafts_t = lax.scan(dstep, (dcache, dlast),
                               jnp.arange(n_draft_k))
        drafts = drafts_t.T  # (n_slots, k)

        # -- verify: the k+1 window through the TARGET forward + the
        # shared rejection-sampling op (the _verify_math body inline).
        window = jnp.concatenate([last[:, None], drafts], axis=1)
        logits, state = forward(state, window, lens, running)
        nd = jnp.where(running, n_draft_k, 0)
        toks, n_w = verify_tokens(logits, drafts, nd, temps, top_k,
                                  top_p, sub)
        keys = jnp.where(running[:, None], carry_keys, keys)

        # -- in-carry replay: cut each row's emissions at its first EOS
        # and at its remaining budget (exactly the host _commit loop).
        jidx = jnp.arange(W)[None, :]
        valid = jidx < n_w[:, None]
        eos_at = jnp.min(jnp.where(valid & (toks == eos_ids[:, None]),
                                   jidx, W), axis=1)
        take = jnp.minimum(n_w, jnp.minimum(eos_at + 1,
                                            budgets - n_emit))
        take = jnp.where(running, take, 0)
        if stream:
            from jax.experimental import io_callback

            for j in range(W):
                io_callback(_stream_tap, None, ring_id, toks[:, j],
                            running & (j < take), ordered=True)
        # Committed tokens land in the output buffer at columns
        # [n_emit, n_emit+take) and back into hist at positions
        # [lens+1, lens+1+take) — the next window's draft context.
        cols = jnp.arange(out.shape[1])[None, :]
        rel = cols - n_emit[:, None]
        put = (rel >= 0) & (rel < take[:, None])
        vals = jnp.take_along_axis(toks, jnp.clip(rel, 0, W - 1), axis=1)
        out = jnp.where(put, vals, out)
        hp = jnp.arange(hist_w)[None, :]
        rel_h = hp - (lens + 1)[:, None]
        put_h = (rel_h >= 0) & (rel_h < take[:, None])
        vals_h = jnp.take_along_axis(toks, jnp.clip(rel_h, 0, W - 1),
                                     axis=1)
        hist = jnp.where(put_h, vals_h, hist)
        last_new = jnp.take_along_axis(
            toks, jnp.maximum(take - 1, 0)[:, None], axis=1)[:, 0]
        last = jnp.where(running, last_new, last)
        lens = lens + take
        n_emit = n_emit + take
        n_win = n_win + running.astype(jnp.int32)
        acc = jnp.where(running & (nd > 0), n_w - 1, 0)
        n_acc = n_acc + acc
        hit_eos = running & (take == eos_at + 1)
        running = running & ~hit_eos & (n_emit < budgets)
        return (i + 1, state, hist, last, lens, running, keys, out,
                n_emit, n_win, n_acc)

    (iters, state, _hist, _last, _lens, _running, keys, out, n_emit,
     n_win, n_acc) = lax.while_loop(
        cond, body, (jnp.int32(0), state, hist, last_tokens, lengths,
                     active, keys, out0, zeros_i, zeros_i, zeros_i))
    return state, out, n_emit, n_win, n_acc, keys, iters


def _ancestor_matrix(parents: tuple) -> tuple:
    """Static ancestor-or-self visibility ``(T+1, T+1)`` bool matrix for
    a tree-``parents`` tuple: row ``i`` marks the in-window nodes node
    ``i`` may attend (itself and its transitive parents).  Plain Python
    at trace time — the tree shape is a compile-time static."""
    T1 = len(parents)
    rows = []
    for i in range(T1):
        vis = [False] * T1
        j = i
        while j >= 0:
            vis[j] = True
            j = parents[j]
        rows.append(tuple(vis))
    return tuple(rows)


def _tree_verify_math(forward, commit, state, tokens, lengths, active,
                      n_cand, temps, top_k, top_p, keys, *, parents):
    """The ONE tree-verify body shared by the dense and paged programs:
    score a static tree of candidate branches (``tokens`` ``(slots,
    T+1)``, node 0 = each row's last token) in a single tree-masked
    forward, walk the accept/reject procedure
    (:func:`tpudp.ops.sampling.verify_tree_tokens`), then commit ONLY
    the accepted root-to-leaf path's K/V — ``forward`` returns the
    window K/V instead of writing it (the no-write tree twins), and
    ``commit`` lands path node ``d``'s vectors at position ``lens + d``
    (dense arena-row writes, or PR 14 single-page writes where rejected
    branches route to the scratch page: zero pool writes).  The PRNG
    discipline is ``_verify_math``'s verbatim; the returned
    tuple has the verify step's exact shape so the host replay seam is
    shared."""
    depths = tree_depths(parents)
    anc = _ancestor_matrix(parents)
    logits, wk, wv = forward(state, tokens, lengths, depths, anc)
    carry, sub = split_keys(keys)
    out, n_emit, path = verify_tree_tokens(logits, tokens[:, 1:],
                                           parents, n_cand, temps,
                                           top_k, top_p, sub)
    new_keys = jnp.where(active[:, None], carry, keys)
    state = commit(state, wk, wv, lengths, path, n_emit, active)
    return state, out, n_emit, new_keys


def _moe_counts(routed):
    """A step's :data:`tpudp.models.moe.SERVE_MOE_COUNTERS` as ``(4,)``
    int32, summed over its expert layers (``routed``: what a family's
    ``forward_paged`` appended)."""
    from tpudp.models.moe import serve_moe_counts

    return sum(serve_moe_counts(counts) for _, counts in routed)


def _build_latent_steps(cfg, paged_attn: str):
    """The step programs of a latent-attention expert family
    (``tpudp.models.pangu``): ``decode_paged`` and ``prefill_paged`` and
    no other (the engine refuses at construction what would need one).
    Same calling convention as their GPT-2 twins, one more result each:
    the run's :func:`_moe_counts`, which the scheduler fetches
    WITH the step's tokens (no sync of their own).  Rows of inactive
    slots and of a chunk's padding reach no routed expert and are in no
    count.  ``paged_attn``: ``'kernel'`` (absorbed attention as the
    ``latent_attn`` Mosaic call, a TRACE_COUNTS key and pinned trace of
    its own) or ``'einsum'`` (XLA contractions a page tile)."""
    kernel = paged_attn == "kernel"

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step_paged(params, pool, table, last_tokens, lengths,
                          active, temps, top_k, top_p, keys):
        """One token for every slot through the latent page pool: the
        shared ``_decode_math`` body over ``generate._forward_paged``'s
        third family (absorbed attention through ``table``, the dropless
        expert layer on the active rows)."""
        if kernel:
            TRACE_COUNTS["decode_paged_latent_kernel"] += 1
        else:
            TRACE_COUNTS["decode_paged_latent"] += 1
        routed: list = []

        def fwd(pool, tokens, lengths, active):
            return _forward_paged(cfg, params, tokens, pool, table, lengths,
                                  active, paged_attn, routed=routed)

        return (*_decode_math(fwd, pool, last_tokens, lengths, active,
                              temps, top_k, top_p, keys),
                _moe_counts(routed))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_step_paged(params, pool, row_table, tokens, meta, keys):
        """One page-aligned prompt chunk of one slot: its latents commit
        as one page write a layer, the window attends causally through
        the slot's table row, rows past ``last`` (a final chunk's padding)
        reach no expert, and only row ``last`` goes through the head and
        the shared first-token tail (:func:`_first_token`)."""
        if kernel:
            TRACE_COUNTS["prefill_paged_latent_kernel"] += 1
        else:
            TRACE_COUNTS["prefill_paged_latent"] += 1
        routed: list = []
        logits, new_pool = _forward_paged(
            cfg, params, tokens, pool, row_table[None], meta[0],
            jnp.ones((1,), bool), paged_attn, last=meta[1], routed=routed)
        return (*_first_token(logits[:, 0], keys, meta), new_pool,
                _moe_counts(routed))

    return (None,) * 6 + (decode_step_paged, None, prefill_step_paged,
                          None, None, None)


def _build_windowed_steps(cfg, paged_attn: str):
    """The step programs of a family whose attention layers are window
    and full mixed, with routed experts (``tpudp.models.laguna``):
    ``decode_paged`` and ``prefill_paged`` and no other, as
    :func:`_build_latent_steps`, with its extra result.  ``pool`` is a
    ``generate.WindowedPages`` (both pools donated) and ``table`` the pair
    ``(full layers' table, window layers' table)``, the second with the
    entries behind the window freed by the host before dispatch.
    ``paged_attn``: ``'kernel'`` or ``'einsum'``."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step_paged(params, pool, table, last_tokens, lengths,
                          active, temps, top_k, top_p, keys):
        """One token for every slot through the two page pools: the
        shared ``_decode_math`` body over ``generate._forward_paged``'s
        fourth family."""
        TRACE_COUNTS["decode_paged_windowed"] += 1
        routed: list = []

        def fwd(pool, tokens, lengths, active):
            return _forward_paged(cfg, params, tokens, pool, tuple(table),
                                  lengths, active, paged_attn, routed=routed)

        return (*_decode_math(fwd, pool, last_tokens, lengths, active,
                              temps, top_k, top_p, keys),
                _moe_counts(routed))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_step_paged(params, pool, row_table, tokens, meta, keys):
        """One page-aligned prompt chunk of one slot: its K/V commit as
        one page write a layer into that layer's pool, the window attends
        through the slot's two table rows, rows past ``last`` reach no
        expert, and only row ``last`` goes through the head and the
        shared first-token tail (:func:`_first_token`)."""
        TRACE_COUNTS["prefill_paged_windowed"] += 1
        routed: list = []
        logits, new_pool = _forward_paged(
            cfg, params, tokens, pool, tuple(row[None] for row in row_table),
            meta[0], jnp.ones((1,), bool), paged_attn, last=meta[1],
            routed=routed)
        return (*_first_token(logits[:, 0], keys, meta), new_pool,
                _moe_counts(routed))

    return (None,) * 6 + (decode_step_paged, None, prefill_step_paged,
                          None, None, None)


def _build_retention_steps(cfg, paged_attn: str):
    """The step programs of a power-retention family
    (``tpudp.models.brumby``): ``decode_step`` and ``prefill_step`` over
    its slot arena, ``generate.RetentionState`` (donated: updated in
    place), and no other (the engine refuses at construction what would
    need one).  Same calling convention as the dense arena's programs.
    ``paged_attn``: the decode read, ``'kernel'`` (the
    ``retention_decode`` Mosaic call, a TRACE_COUNTS key of its own) or
    ``'einsum'``; a prefill chunk runs the chunked form in XLA either
    way."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step(params, state, last_tokens, lengths, active, temps,
                    top_k, top_p, keys):
        """One token for every slot: the shared ``_decode_math`` body over
        ``generate._forward_state`` (each decoding slot's tail row written,
        state and tail read, the tails that filled folded)."""
        if paged_attn == "kernel":
            TRACE_COUNTS["decode_retention_kernel"] += 1
        else:
            TRACE_COUNTS["decode_retention"] += 1

        def fwd(state, tokens, lengths, active):
            return _forward_state(cfg, params, tokens, state, lengths,
                                  active, paged_attn)

        return _decode_math(fwd, state, last_tokens, lengths, active, temps,
                            top_k, top_p, keys)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_step(params, state, tokens, meta, keys):
        """One prompt chunk of one slot from its state (zero at position
        0), its whole fold blocks folded and the rest left as the slot's
        tail; rows past ``last`` leave both as they were, and only row
        ``last`` goes through the head and the shared first-token tail
        (:func:`_first_token`)."""
        TRACE_COUNTS["prefill_retention"] += 1
        logits, state = _forward_state(
            cfg, params, tokens, state, meta[0], jnp.ones((1,), bool),
            paged_attn, slot=meta[2], last=meta[1])
        return *_first_token(logits[:, 0], keys, meta), state

    return (decode_step, None, prefill_step) + (None,) * 9


@functools.lru_cache(maxsize=16)
def _build_steps(cfg, paged_attn: str = "einsum", draft_cfg=None):
    """Jitted step programs for one model CONFIG.  Every program takes
    the weights as its first (traced, never donated) argument — the
    fused speculative programs take the draft model's weights second —
    so the device holds ONE copy of each weight tree however many
    programs run over it, and builds are memoized per ``(cfg,
    paged_attn, draft_cfg)``: engines over the same config share one
    set of compiled programs whatever weights they serve.

    ``draft_cfg`` — a draft model's config — additionally builds the
    fused SPECULATIVE programs (``fused_spec_step`` and its paged
    twin): an ``Engine(speculate_k=k, decode_fuse=N,
    drafter=DraftModelDrafter(...))`` runs draft→verify→accept as one
    ``lax.while_loop`` program (``_fused_spec_math``).  ``None`` (every
    other engine) builds no such program — the returned tuple carries
    ``None`` in those positions.

    ``paged_attn`` selects the PAGED programs' KV indirection (the
    dense programs never change): ``'einsum'`` is the GATHER-FREE
    bit-exact path (K/V read through the block table inside the
    attention contraction, single-token page writes; see
    ``tpudp.ops.paged_attention``); ``'gather'`` is PR 13's
    gather→dense-math→scatter baseline, kept for the bench comparison
    and as the kernel tests' oracle; ``'kernel'`` — the TPU default —
    runs the WHOLE hot path through the Pallas kernels: single-token
    decode through the paged-decode kernel, the k+1 verify window and
    chunked prefill through the flash-window kernel, the fused
    ``lax.while_loop`` programs dispatching those kernels per
    iteration, and tree verify through the tree kernel (fp pools; an
    int8 pool's tree program auto-falls-back to the einsum/gather tree
    path at trace time — the one feature the tree kernel declines).
    Every kernel program is tolerance-bounded like flash, hence its
    own TRACE_COUNTS key, pinned trace, and budget-ledger row.

    Why arguments and not closed-over constants (PRs 2-20 froze the
    weights into the programs, a win measured on XLA:CPU only): on the
    TPU v5e at GPT-2-small every program embedded its own ~340 MB copy
    of the bf16 weights — ``bytes_in_use`` grew by that much per
    compiled program, backend compile took 29-41 s per program and each
    persistent-cache entry was ~765 MB (chip run, PR 21, CHANGES.md).

    Shapes stay traced, so one build serves every engine geometry,
    compiling once per (num_slots, max_len[, k]) exactly as before.

    A latent-attention or a windowed config (``generate.page_layout``)
    builds its two paged programs only (:func:`_build_latent_steps`,
    :func:`_build_windowed_steps`), a power-retention config its two
    slot-arena programs (:func:`_build_retention_steps`).
    """
    if page_layout(cfg) == "state":
        return _build_retention_steps(cfg, paged_attn)
    if page_layout(cfg) == "latent":
        return _build_latent_steps(cfg, paged_attn)
    if page_layout(cfg) == "windowed":
        return _build_windowed_steps(cfg, paged_attn)

    def _dense_fwd(params):
        """The dense indirection for the shared step bodies: plain
        arena-row reads/writes (masked rows land in their own rows —
        the overwrite-before-visible rule needs no ``active``)."""
        def fwd(cache, tokens, lengths, active):
            del active
            return _forward_cached(cfg, params, tokens, cache, lengths)
        return fwd

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step(params, cache, last_tokens, lengths, active, temps,
                    top_k, top_p, keys):
        """One token for every slot: feed each row's last token at its
        own depth, sample per-row (``_decode_math`` — the body shared
        with the paged twin).  All sampling params and positions
        are traced arrays, so this compiles once per (num_slots,
        max_len).  The cache is donated: XLA updates the arena in place
        instead of copying it every step."""
        TRACE_COUNTS["decode_step"] += 1
        return _decode_math(_dense_fwd(params), cache, last_tokens, lengths,
                            active, temps, top_k, top_p, keys)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def verify_step(params, cache, tokens, lengths, active, n_draft, temps,
                    top_k, top_p, keys):
        """One speculative window for every slot: feed each row's
        ``[last, d_0 .. d_{k-1}]`` window at its own depth, accept the
        longest draft prefix the target model agrees with
        (``_verify_math`` — the body shared with the paged twin), emit
        up to k+1 tokens per row.
        The window width is the only addition to the decode step's
        shape set, so this compiles once per (num_slots, max_len, k)
        and admission/retirement/cancellation churn never recompiles.
        Rows with ``n_draft == 0`` degenerate to exactly the 1-token
        decode (the window's tail writes are overwritten before they
        become visible, like every other masked write in the arena)."""
        TRACE_COUNTS["verify_step"] += 1
        return _verify_math(_dense_fwd(params), cache, tokens, lengths, active,
                            n_draft, temps, top_k, top_p, keys)

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("n_steps", "stream"))
    def fused_decode_step(params, cache, last_tokens, lengths, active, temps,
                          top_k, top_p, keys, budgets, eos_ids, ring_id,
                          *, n_steps, stream=False):
        """Up to ``n_steps`` decode iterations in ONE device program: a
        ``lax.while_loop`` whose body is exactly the decode step's math
        (same vector-position forward, same per-row masked sampling, the
        per-slot PRNG chains advanced inside the loop once per OWN
        committed token), with a predicate that exits early once every
        running slot has sampled its ``eos_ids`` entry (-1 = none) or
        exhausted its ``budgets`` entry (remaining ``max_new_tokens``).
        Each iteration commits one token per still-running row into the
        ``(num_slots, n_steps)`` output buffer; rows that stop keep
        their key chain and length frozen, so the returned carry is
        bit-identical to having run ``n_emit[s]`` single decode steps
        for every slot — the fall-back seam the scheduler relies on.
        ``n_steps`` is static (it shapes the output buffer), so the
        program compiles once per (num_slots, max_len, n_steps);
        ``budgets``/``eos_ids``/``ring_id`` are traced values.  With
        ``stream`` (static) an ordered ``io_callback`` taps each
        iteration's committed tokens into the host ring buffer named by
        ``ring_id`` — an observability side channel, never the commit
        path.  Returns ``(cache, out, n_emit, keys, iters)``; the ONE
        host fetch per window
        replaces the per-token fetch.  Loop body/carry/predicate live
        in ``_fused_decode_math`` — the one copy shared with the paged
        twin."""
        TRACE_COUNTS["fused_decode"] += 1
        return _fused_decode_math(
            _dense_fwd(params), cache, last_tokens, lengths, active, temps,
            top_k, top_p, keys, budgets, eos_ids, ring_id,
            n_steps=n_steps, stream=stream)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_step(params, cache, tokens, meta, keys):
        """One fixed-size prompt chunk for one slot: slice the slot's
        arena row, run the scalar-pos cached forward (batch 1), write
        the row back.  The start position, the chunk's last valid row,
        the slot and the sampling scalars ride in ``meta``
        (:func:`_prefill_meta`), traced — chunk number, slot index, and
        prompt length never recompile.  The logits at the chunk's LAST
        VALID token (index ``last``; the tail of a final partial chunk
        is padding) go through the shared first-token tail
        (:func:`_first_token`); returns its token, the key chain, and
        the updated arena."""
        TRACE_COUNTS["prefill_chunk"] += 1
        slot = meta[2]
        k = lax.dynamic_slice_in_dim(cache.k, slot, 1, axis=1)
        v = lax.dynamic_slice_in_dim(cache.v, slot, 1, axis=1)
        logits, row = _forward_cached(cfg, params, tokens,
                                      KVCache(k, v), meta[0])
        last_logits = lax.dynamic_index_in_dim(
            logits, meta[1], axis=1, keepdims=False)  # (1, vocab)
        return *_first_token(last_logits, keys, meta), KVCache(
            lax.dynamic_update_slice_in_dim(cache.k, row.k, slot, axis=1),
            lax.dynamic_update_slice_in_dim(cache.v, row.v, slot, axis=1))

    if draft_cfg is None:
        fused_spec_step = None
    else:
        @functools.partial(jax.jit, donate_argnums=(2,),
                           static_argnames=("n_draft_k", "n_steps",
                                            "stream"))
        def fused_spec_step(params, draft_params, cache, hist,
                            last_tokens, lengths, active, temps, top_k,
                            top_p, keys, budgets, eos_ids, ring_id,
                            *, n_draft_k, n_steps, stream=False):
            """Up to ``n_steps`` SPECULATIVE windows in ONE device
            program: each ``lax.while_loop`` iteration drafts
            ``n_draft_k`` greedy tokens per running slot with the
            draft model (its weights the program's second argument),
            scores the k+1 window with the
            verify forward, and commits the accepted prefix + bonus
            token in-carry — ``_fused_spec_math``, the one copy shared
            with the paged twin.  ``hist`` ``(num_slots, max_len)``
            holds each slot's prompt+committed tokens (the drafter's
            context; committed tokens scatter back into it between
            windows).  Compiles once per (num_slots, max_len, k,
            n_steps); returns ``(cache, out, n_emit, n_windows,
            n_accepted, keys, iters)`` with ONE host fetch per
            multi-window program — the per-window draft gather AND
            verify fetch are gone."""
            TRACE_COUNTS["fused_spec_decode"] += 1
            return _fused_spec_math(
                _dense_fwd(params), draft_cfg, draft_params, cache, hist,
                last_tokens, lengths, active, temps, top_k, top_p, keys,
                budgets, eos_ids, ring_id, n_draft_k=n_draft_k,
                n_steps=n_steps, stream=stream)

    def _tree_dense_fwd(params):
        """Dense tree-verify indirection: the no-write tree forward
        reads the arena directly and hands back the window K/V."""
        def fwd(cache, tokens, lengths, depths, anc):
            return _forward_tree(cfg, params, tokens, cache, lengths,
                                 depths, anc)
        return fwd

    def _tree_dense_commit(cache, wk, wv, lengths, path, n_emit, active):
        """Dense accepted-path commit: path node ``d``'s K/V lands at
        arena position ``lens + d`` (unconditionally — positions past
        the accepted depth hold garbage beyond the row's length, the
        arena's standing overwrite-before-visible contract, and masked
        rows land in their own rows like every dense write)."""
        del n_emit, active
        k_all, v_all = cache.k, cache.v
        for d in range(path.shape[1]):
            idx = path[:, d][None, :, None, None, None]
            ksel = jnp.take_along_axis(wk, idx, axis=2)
            vsel = jnp.take_along_axis(wv, idx, axis=2)
            k_all = jax.vmap(update_cache_rows, in_axes=(0, 0, None))(
                k_all, ksel, lengths + d)
            v_all = jax.vmap(update_cache_rows, in_axes=(0, 0, None))(
                v_all, vsel, lengths + d)
        return KVCache(k_all, v_all)

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("parents",))
    def tree_verify_step(params, cache, tokens, lengths, active, n_cand, temps,
                         top_k, top_p, keys, *, parents):
        """One speculative TREE window for every slot
        (``Engine(speculate_tree=shape)``): ``tokens`` ``(num_slots,
        T+1)`` holds each row's last token at node 0 and the drafter's
        candidate branches at nodes 1..T; one tree-masked forward
        scores every branch, ``verify_tree_tokens`` walks the
        accept/reject, and only the accepted root-to-leaf path's K/V
        commits (``_tree_verify_math``).  ``parents`` is static — one
        compile per (geometry, tree shape); the tree attention is
        tolerance-bounded vs the sequential write-then-attend window
        (its joint softmax spans cache+window), hence its own
        TRACE_COUNTS key and pinned trace.  Return tuple mirrors
        ``verify_step`` so the host replay seam is shared."""
        TRACE_COUNTS["tree_verify"] += 1
        return _tree_verify_math(
            _tree_dense_fwd(params), _tree_dense_commit, cache, tokens,
            lengths,
            active, n_cand, temps, top_k, top_p, keys,
            parents=parents)

    # -- paged twins (Engine(kv_pages=N)): identical math read through
    # per-slot block tables into one shared page pool.  The DEFAULT
    # ("einsum") indirection is GATHER-FREE: each layer writes the
    # window's new tokens straight into the pages containing them and
    # reads K/V through the table inside the attention contraction
    # (bit-identical outputs — tpudp.ops.paged_attention's contract —
    # with the dense logical view never materialized); "gather" keeps
    # PR 13's gather→dense→scatter baseline.  The pool (a KVPages or
    # Int8Pages pytree: token rows of kv_heads * head_dim values, the
    # form the paged kernels read) is donated like the dense arena; the
    # TABLE is host-authoritative and read-only on device.
    kernel_build = paged_attn == "kernel"
    win_impl = "gather" if paged_attn == "gather" else (
        "kernel" if kernel_build else "einsum")

    def _paged_fwd(params, table, impl):
        """The paged indirection for the shared step bodies —
        ``generate._forward_paged`` with the build's impl baked in
        (``active`` masks the write path to the scratch page for idle
        rows)."""
        def fwd(pool, tokens, lengths, active):
            return _forward_paged(cfg, params, tokens, pool, table,
                                  lengths, active, impl=impl)
        return fwd

    if paged_attn == "kernel":
        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_step_paged(params, pool, table, last_tokens, lengths,
                              active, temps, top_k, top_p, keys):
            """Paged decode through the PALLAS paged-decode kernel
            (``Engine(paged_attn='kernel')`` — the TPU default): same sampling/
            PRNG contract and shared ``_decode_math`` body as the
            einsum twin, but the attention contraction runs the
            online-softmax kernel with the block table as scalar
            prefetch — tolerance-bounded like flash, hence its own
            TRACE_COUNTS key and pinned trace."""
            TRACE_COUNTS["decode_paged_kernel"] += 1
            return _decode_math(_paged_fwd(params, table, "kernel"), pool,
                                last_tokens, lengths, active, temps,
                                top_k, top_p, keys)
    else:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_step_paged(params, pool, table, last_tokens, lengths,
                              active, temps, top_k, top_p, keys):
            """Paged decode: one token for every slot, KV read/written
            through ``table`` into ``pool``.  Same sampling/PRNG
            contract as ``decode_step`` — literally the same
            ``_decode_math`` body; compiles once per (num_slots,
            max_len, num_pages)."""
            TRACE_COUNTS["decode_paged"] += 1
            return _decode_math(_paged_fwd(params, table, paged_attn), pool,
                                last_tokens, lengths, active, temps,
                                top_k, top_p, keys)

    if kernel_build:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def verify_step_paged(params, pool, table, tokens, lengths, active,
                              n_draft, temps, top_k, top_p, keys):
            """Paged speculative verify through the flash-window kernel:
            the k+1 window attends its own in-window prefix and the
            cache in ONE kernel launch per layer (per-row visibility
            ``k_pos <= pos + j`` — the window K/V are already in pages
            by write-before-attend).  Same shared ``_verify_math`` body
            and commit contract as the einsum twin; tolerance-bounded,
            own TRACE_COUNTS key and pinned trace."""
            TRACE_COUNTS["verify_paged_kernel"] += 1
            return _verify_math(_paged_fwd(params, table, "kernel"), pool,
                                tokens, lengths, active, n_draft, temps,
                                top_k, top_p, keys)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_step_paged(params, pool, row_table, tokens, meta, keys):
            """Paged prompt chunk through the flash-prefill kernel
            (grid ``chunk_tiles × kv_pages``, causal in-chunk mask,
            online-softmax carry in VMEM): the chunk's KV commits as
            one whole-page write first, then attention streams pages —
            the max_pages-wide score tiles of the einsum path are
            never materialized.  Ends with the shared first-token tail,
            as its einsum twin."""
            TRACE_COUNTS["prefill_paged_kernel"] += 1
            logits, new_pool = _forward_paged(
                cfg, params, tokens, pool, row_table[None], meta[0],
                jnp.ones((1,), bool), impl="kernel")
            last_logits = lax.dynamic_index_in_dim(
                logits, meta[1], axis=1, keepdims=False)  # (1, vocab)
            return *_first_token(last_logits, keys, meta), new_pool

        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("n_steps", "stream"))
        def fused_decode_step_paged(params, pool, table, last_tokens, lengths,
                                    active, temps, top_k, top_p, keys,
                                    budgets, eos_ids, ring_id, *,
                                    n_steps, stream=False):
            """Paged fused decode with the decode KERNEL inside the
            ``lax.while_loop`` body: every iteration's attention is one
            paged-decode kernel launch per layer (table as scalar
            prefetch, loop-invariant), so the fully-fused path runs
            kernels end-to-end.  Same shared ``_fused_decode_math``
            carry/predicate/PRNG/stream contract as the einsum twin."""
            TRACE_COUNTS["fused_decode_paged_kernel"] += 1
            return _fused_decode_math(
                _paged_fwd(params, table, "kernel"), pool, last_tokens,
                lengths, active, temps, top_k, top_p, keys, budgets, eos_ids,
                ring_id, n_steps=n_steps, stream=stream)
    else:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def verify_step_paged(params, pool, table, tokens, lengths, active,
                              n_draft, temps, top_k, top_p, keys):
            """Paged speculative verify (the shared ``_verify_math``
            body): the k+1 window's writes may cross one page boundary
            — each window position commits into its own page-containing
            row (the host preallocates the table entries)."""
            TRACE_COUNTS["verify_paged"] += 1
            return _verify_math(_paged_fwd(params, table, win_impl), pool,
                                tokens, lengths, active, n_draft, temps,
                                top_k, top_p, keys)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def prefill_step_paged(params, pool, row_table, tokens, meta, keys):
            """Paged prompt chunk for one slot: the same scalar-pos
            cached forward the dense prefill runs, read/written through
            the slot's table row, and the same first-token tail.  Chunk
            starts are page-aligned (pages are sized to
            ``prefill_chunk``), so exactly one real page is written per
            chunk — on the gather-free path as per-token commits into
            that page, never a view scatter."""
            TRACE_COUNTS["prefill_paged"] += 1
            logits, new_pool = _forward_paged(
                cfg, params, tokens, pool, row_table[None], meta[0],
                jnp.ones((1,), bool), impl=win_impl)
            last_logits = lax.dynamic_index_in_dim(
                logits, meta[1], axis=1, keepdims=False)  # (1, vocab)
            return *_first_token(last_logits, keys, meta), new_pool

        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("n_steps", "stream"))
        def fused_decode_step_paged(params, pool, table, last_tokens, lengths,
                                    active, temps, top_k, top_p, keys,
                                    budgets, eos_ids, ring_id, *,
                                    n_steps, stream=False):
            """Paged fused decode window: the dense fused loop —
            ``_fused_decode_math``, the one shared copy of carry,
            early-exit predicate, PRNG discipline, commits, and the
            optional stream tap — with the paged indirection inside the
            ``lax.while_loop`` (the table is loop-invariant; the host
            preallocates pages covering the window before dispatch, so
            an in-window page-boundary crossing is always backed).  On
            the gather-free default each loop iteration writes ONE
            token row per running slot and reads through the table —
            the per-step full-view gather/scatter stream is gone."""
            TRACE_COUNTS["fused_decode_paged"] += 1
            return _fused_decode_math(
                _paged_fwd(params, table, win_impl), pool, last_tokens,
                lengths, active, temps, top_k, top_p, keys, budgets, eos_ids,
                ring_id, n_steps=n_steps, stream=stream)

    if draft_cfg is None:
        fused_spec_paged = None
    elif kernel_build:
        @functools.partial(jax.jit, donate_argnums=(2,),
                           static_argnames=("n_draft_k", "n_steps",
                                            "stream"))
        def fused_spec_paged(params, draft_params, pool, table, hist,
                             last_tokens, lengths, active, temps, top_k,
                             top_p, keys, budgets, eos_ids, ring_id,
                             *, n_draft_k, n_steps, stream=False):
            """Paged fused speculation with KERNELS inside the loop
            body: each iteration's k+1 verify window runs the
            flash-window kernel (per-row window visibility through the
            table) while the draft model keeps its dense carry-local
            arena — ``_fused_spec_math``, the one shared copy of the
            draft/verify/accept carry, with the draft re-prefill
            q-chunked (bitwise-identical logits, one chunk's score
            tiles live instead of the full history's)."""
            TRACE_COUNTS["fused_spec_paged_kernel"] += 1
            return _fused_spec_math(
                _paged_fwd(params, table, "kernel"), draft_cfg, draft_params,
                pool, hist, last_tokens, lengths, active, temps, top_k,
                top_p, keys, budgets, eos_ids, ring_id,
                n_draft_k=n_draft_k, n_steps=n_steps, stream=stream,
                chunk_draft_prefill=True)
    else:
        @functools.partial(jax.jit, donate_argnums=(2,),
                           static_argnames=("n_draft_k", "n_steps",
                                            "stream"))
        def fused_spec_paged(params, draft_params, pool, table, hist,
                             last_tokens, lengths, active, temps, top_k,
                             top_p, keys, budgets, eos_ids, ring_id,
                             *, n_draft_k, n_steps, stream=False):
            """Paged fused speculative window: ``_fused_spec_math`` —
            the one shared copy of draft/verify/accept carry — with the
            paged indirection inside the loop (the table is
            loop-invariant; the host backs every window position's page
            before dispatch, including the k-token speculative tail).
            The DRAFT model's KV stays a dense carry-local arena
            either way — it is scratch recomputed per window, never
            pooled state."""
            TRACE_COUNTS["fused_spec_paged"] += 1
            return _fused_spec_math(
                _paged_fwd(params, table, win_impl), draft_cfg, draft_params,
                pool, hist, last_tokens, lengths, active, temps, top_k,
                top_p, keys, budgets, eos_ids, ring_id,
                n_draft_k=n_draft_k, n_steps=n_steps, stream=stream)

    def _tree_paged_fwd(params, table):
        """Paged tree-verify indirection: materialize the read-only
        dense view (gather — the tree step's documented read cost;
        nothing is scattered back) and run the no-write tree forward
        over it."""
        def fwd(pool, tokens, lengths, depths, anc):
            view = gather_pages(cfg, pool, table)
            return _forward_tree(cfg, params, tokens, view, lengths,
                                 depths, anc)
        return fwd

    def _tree_paged_commit(table):
        """Paged accepted-path commit: PR 14 single-page writes of path
        node ``d``'s K/V at position ``lens + d``, ACTIVE-masked past
        the accepted depth — rejected branches and rejected depths
        route to the trailing scratch page, so they cost ZERO real
        pool writes (the byte-diff pin)."""
        def commit(pool, wk, wv, lengths, path, n_emit, active):
            acc = n_emit - 1
            layers = []
            for i in range(cfg.num_layers):
                pages = _layer_pages(pool, i)
                for d in range(path.shape[1]):
                    idx = path[:, d][:, None, None, None]
                    ksel = jnp.take_along_axis(wk[i], idx, axis=1)
                    vsel = jnp.take_along_axis(wv[i], idx, axis=1)
                    pages = write_token_pages(
                        pages, ksel, vsel, table, lengths + d,
                        active & (d <= acc))
                layers.append(pages)
            return _stack_pages(pool, layers)
        return commit

    def _tree_kernel_fwd(params, table):
        """Kernelized paged tree-verify indirection: node queries read
        the cache THROUGH the table inside the tree kernel (strict
        ``< pos0`` visibility + in-window ancestor mask as a
        scalar-prefetched constant) — the gathered dense view never
        exists.  fp pools only."""
        def fwd(pool, tokens, lengths, depths, anc):
            return _forward_tree_paged(cfg, params, tokens, pool, table,
                                       lengths, depths, anc)
        return fwd

    if kernel_build:
        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("parents",))
        def tree_verify_paged(params, pool, table, tokens, lengths, active,
                              n_cand, temps, top_k, top_p, keys,
                              *, parents):
            """Paged tree window on the kernel build: fp pools run the
            TREE KERNEL (cache pages streamed through the table, the
            in-flight window folded in under the ancestor mask — no
            gather); int8 pools are the one feature the tree kernel
            declines, so they fall back AT TRACE TIME to the exact
            einsum/gather tree path and bump ITS counter — the
            per-program fallback ``Engine.metrics()`` reports."""
            if isinstance(pool, Int8Pages):
                TRACE_COUNTS["tree_verify_paged"] += 1
                return _tree_verify_math(
                    _tree_paged_fwd(params, table), _tree_paged_commit(table),
                    pool, tokens, lengths, active, n_cand, temps, top_k,
                    top_p, keys, parents=parents)
            TRACE_COUNTS["tree_verify_paged_kernel"] += 1
            return _tree_verify_math(
                _tree_kernel_fwd(params, table), _tree_paged_commit(table),
                pool, tokens, lengths, active, n_cand, temps, top_k, top_p,
                keys, parents=parents)
    else:
        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnames=("parents",))
        def tree_verify_paged(params, pool, table, tokens, lengths, active,
                              n_cand, temps, top_k, top_p, keys,
                              *, parents):
            """Paged speculative tree window (the shared
            ``_tree_verify_math`` body): tree-masked scoring over the
            gathered view, then accepted-path-only single-page commits —
            rejected branches write nothing into the pool."""
            TRACE_COUNTS["tree_verify_paged"] += 1
            return _tree_verify_math(
                _tree_paged_fwd(params, table), _tree_paged_commit(table),
                pool, tokens, lengths, active, n_cand, temps, top_k,
                top_p, keys, parents=parents)

    return (decode_step, verify_step, prefill_step, fused_decode_step,
            fused_spec_step, tree_verify_step,
            decode_step_paged, verify_step_paged, prefill_step_paged,
            fused_decode_step_paged, fused_spec_paged, tree_verify_paged)


class _ModelState:
    """Per-model serving state behind the one scheduler: a slot KV
    arena, the config's step programs bound to this model's weights,
    and (optionally) a prefix cache.  The default model is
    ``_mstates[None]``; co-resident models registered via ``Engine(models={name: (model, params)})`` get their
    own instance each.  Every arena shares the engine's (num_slots,
    max_len) geometry — a request occupies the SAME slot index in every
    arena, but only its own model's rows ever hold its real KV; the
    other arenas' copies of that row accumulate garbage that the
    overwrite-before-visible rule makes unreadable, exactly like an
    inactive slot's row."""

    #: One attribute per ``_build_steps`` program, in its return order.
    _STEPS = ("decode_step", "verify_step", "prefill_step", "fused_step",
              "fused_spec_step", "tree_step",
              "decode_paged", "verify_paged", "prefill_paged",
              "fused_paged", "fused_spec_paged", "tree_paged")
    __slots__ = ("name", "model", "config", "params", *_STEPS,
                 "cache", "prefix_cache", "pool", "index",
                 "table", "wtable", "slot_nodes")

    def __init__(self, name, model, params, steps, draft_params=None):
        self.name = name
        self.model = model
        self.config = model.config
        self.params = params

        # The programs take the weights as leading arguments
        # (_build_steps: the fused speculative ones the draft weights
        # second); binding them here keeps every call site a plain
        # ``ms.decode_step(cache, ...)``.
        for attr, step in zip(self._STEPS, steps):
            weights = ((params, draft_params)
                       if attr.startswith("fused_spec") else (params,))
            setattr(self, attr, None if step is None
                    else functools.partial(step, *weights))
        self.cache = None
        self.prefix_cache = None
        # Paged mode (Engine(kv_pages=N)): no dense arena — ``pool`` is
        # the shared PagePool of this model's KV-geometry group,
        # ``index`` its radix PageIndex (cached KV is a function of
        # MODEL and tokens, so trees never cross models even when the
        # pool does), ``table`` the host-authoritative (num_slots,
        # max_pages) int32 block table uploaded per step, and
        # ``slot_nodes[s]`` maps each of slot s's SHARED pages to the
        # pinned tree node behind it (private pages are the table
        # entries absent here).
        self.pool = None
        self.index = None
        self.table = None
        # A family with sliding-window layers (generate.WindowedPages)
        # keeps a second table of the same shape over the pool's window
        # pages: entry j is mapped while logical page j can still be
        # attended and freed once the window has passed it.  None for
        # every other family: THE test where the windowed path parts.
        self.wtable = None
        self.slot_nodes = None


def _seed_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` made on the host, bit for bit: the
    seed as a 64-bit integer (past 64 bits ``OverflowError``, as there),
    its high word (0 unless 64-bit types are on, where JAX has cut the
    seed to 32 bits) and its low word as ``uint32``."""
    seed = int(np.int64(seed))
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


def _prefill_meta(start: int, last: int, slot: int, key, temp: float,
                  top_k: int, top_p: float) -> np.ndarray:
    """A prefill program's host scalars in ONE int32 array (the float
    entries by bit pattern): the chunk's start position, its last valid
    row, the slot, whether the chunk ends the prompt and is sampled
    (``key`` given: the slot's initial key), the key, and the slot's
    ``top_k``, temperature and ``top_p``.  A chunk with no key samples
    greedily and its token is not read (:func:`_first_token`)."""
    meta = np.zeros(9, np.int32)
    meta[:3] = start, last, slot
    if key is None:
        temp, top_p = 0.0, 1.0
    else:
        meta[3] = 1
        meta[4:6] = np.asarray(key, np.uint32).view(np.int32)
        meta[6] = top_k
    meta[7:] = np.array([temp, top_p], np.float32).view(np.int32)
    return meta


def _readiness_probe(out):
    """The output of a just-dispatched program that the exposed clock
    asks ``is_ready()``: the first array among the result's top-level
    entries.  A state a program returns (an arena or a page pool, a
    tuple of arrays) is passed over: the next program donates it, and a
    deleted buffer cannot be asked.  A result that is only such a state
    (the prefix cache's block copies) gives None."""
    if type(out) is tuple:
        for x in out:
            if isinstance(x, jax.Array):
                return x
    return None


class Request:
    """Handle returned by :meth:`Engine.submit`.

    ``tokens`` grows as the engine steps; iterate the handle to stream
    them (iteration drives the engine), or call :meth:`result` for the
    full prompt+completion sequence.  ``token_times`` records a
    ``time.perf_counter()`` stamp per emitted token (the serve bench's
    per-token latency source); on the same clock ``submit_time``,
    ``admit_time`` (first slot granted) and ``first_chunk_time`` (first
    prefill chunk dispatched) split the time to the first token into
    queueing, holding a slot while other prompts prefill, and the
    request's own prefill (:meth:`ttft_split`).  With speculation on,
    ``draft_proposed``/``draft_accepted`` count this request's drafted
    and accepted tokens (``acceptance_rate`` is their ratio).
    :meth:`cancel` retires the request immediately — a disconnected
    client must not pin a slot until ``max_new_tokens``.

    ``finish_reason`` (a :class:`FinishReason`) records WHY the request
    stopped; it is ``None`` until ``done``.  :meth:`result` raises
    :class:`RequestFailed` for any non-success reason instead of
    silently returning a truncated sequence."""

    def __init__(self, engine: "Engine", rid: int, prompt: np.ndarray,
                 max_new_tokens: int, temperature: float, top_k: int,
                 top_p: float, seed: int, eos_id: int | None,
                 deadline_s: float | None = None,
                 ttft_deadline_s: float | None = None,
                 tenant: str | None = None):
        self._engine = engine
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k  # 0 = disabled
        self.top_p = top_p  # 1.0 = disabled
        self.seed = seed
        self.eos_id = eos_id
        self.deadline_s = deadline_s
        self.ttft_deadline_s = ttft_deadline_s
        self.tenant = tenant       # class name (None: tenancy off)
        self.preemptions = 0       # times this request lost its slot to
        #                            higher-priority work (each resume is
        #                            bit-identical, so this is latency
        #                            accounting, never a correctness flag)
        self.migrations = 0        # times this request moved host-to-host
        #                            (tpudp/serve/disagg.py) — distinct
        #                            from preemptions and from page
        #                            pressure at every level: a migration
        #                            is also a bit-exact resume, just on
        #                            a different engine
        self._ms = None            # _ModelState this request decodes with
        self.tokens: list[int] = []
        self.token_times: list[float] = []
        self.submit_time = time.perf_counter()
        self.admit_time: float | None = None
        self.first_chunk_time: float | None = None
        self.done = False
        self.finish_reason: FinishReason | None = None
        self.error: BaseException | None = None
        self.draft_proposed = 0
        self.draft_accepted = 0
        self._slot: int | None = None
        self._fill = prompt  # tokens to prefill (prompt, or prompt +
        #                      emitted tokens after a step-failure requeue)
        self._nfill = 0      # fill tokens already in the cache
        self._order = 0      # admission order (prefill FIFO tiebreak)
        self._requeued = False      # one-shot step-failure requeue budget
        self._resume_key = None     # PRNG chain saved across a requeue

    @property
    def acceptance_rate(self) -> float | None:
        """Accepted / proposed draft tokens for THIS request (None until
        a drafter has proposed something for it)."""
        if not self.draft_proposed:
            return None
        return self.draft_accepted / self.draft_proposed

    def ttft_split(self) -> tuple[float, float, float] | None:
        """``(ttft_s, queue_s, prefill_wait_s)`` of the first token:
        submit to first token, of which submit to slot grant and slot
        grant to first prefill chunk; the remainder is the request's
        own prefill (and any preemption before the token).  None until
        the token exists, and for a request adopted from another host
        (its first token was not made under this engine's clock)."""
        if (self.migrations or not self.token_times
                or self.admit_time is None or self.first_chunk_time is None):
            return None
        return (self.token_times[0] - self.submit_time,
                self.admit_time - self.submit_time,
                self.first_chunk_time - self.admit_time)

    @property
    def cancelled(self) -> bool:
        return self.finish_reason is FinishReason.CANCELLED

    @property
    def ok(self) -> bool:
        """Finished successfully (budget reached or EOS sampled)."""
        return self.finish_reason in (FinishReason.COMPLETE,
                                      FinishReason.EOS)

    def cancel(self) -> bool:
        """Retire this request now (see :meth:`Engine.cancel`)."""
        return self._engine.cancel(self)

    def __iter__(self):
        i = 0
        while True:
            while i >= len(self.tokens) and not self.done:
                self._engine.step()
            if i < len(self.tokens):
                yield self.tokens[i]
                i += 1
            else:
                return

    def result(self) -> np.ndarray:
        """Drive the engine until this request finishes; return the full
        ``prompt + generated`` int32 sequence.  Raises
        :class:`RequestFailed` if the request did not finish successfully
        (cancelled, deadline, error, shed) instead of silently returning
        a truncated sequence — the partial tokens stay on ``tokens``."""
        while not self.done:
            self._engine.step()
        if not self.ok:
            raise RequestFailed(self)
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int32)])


class Engine:
    """Continuous-batching engine over a slot-based KV arena.

    ``model`` is a tpudp GPT2 or Llama (dense attention/MLP — the same
    family contract as ``generate()``), or a latent-attention expert
    model (``tpudp.models.pangu``), which is served through pages only
    (``kv_pages > 0``; its cache is ``generate.LatentPages``, its expert
    layers run inside the two step programs and count themselves in
    ``metrics()["stats"]``) and refuses, by option name, what it has no
    program for: ``kv_dtype``, ``speculate_k``, ``speculate_tree``,
    ``decode_fuse > 1``, ``models=``, ``paged_attn='gather'``, and the
    ticket methods (docs/SERVING.md, the family table); or a
    model whose attention layers are window and full mixed
    (``tpudp.models.laguna``), served through pages only likewise: its
    cache is ``generate.WindowedPages``, a pool a layer kind behind a
    block table each, the window layers' pages freed behind the window
    (``kv_pages`` counts the global pool's and must hold every slot's
    full reservation; the window pool's size follows from the window);
    prefix sharing is off for it.  ``num_slots`` bounds concurrent
    in-flight requests (queued requests wait for a free slot);
    ``max_len`` bounds ``prompt + max_new_tokens`` per request (default:
    the model's ``max_seq_len``, rounded down to a ``prefill_chunk``
    multiple).  One engine = one arena = one compiled decode step.

    ``speculate_k > 0`` turns on speculative decoding: ``drafter``
    (default :class:`tpudp.serve.speculate.NgramDrafter`; any object
    with ``propose(context, k)``) proposes up to k tokens per decoding
    slot each step and one batched verify forward accepts the agreeing
    prefix — up to k+1 tokens per weight read, greedy outputs still
    bit-identical to ``generate()``.  The arena reserves ``speculate_k``
    scratch positions per slot (a window's rejected tail must never wrap
    past ``max_len``), so ``prompt + max_new_tokens + speculate_k`` must
    fit in ``max_len``.

    ``prefix_cache_blocks > 0`` turns on prefix caching
    (``tpudp.serve.prefix_cache``): retired requests publish their
    block-aligned prefilled KV into a block pool indexed by a radix
    tree, and a new request whose fill shares a cached block-aligned
    prefix copies those blocks instead of re-prefilling them (greedy
    outputs bit-identical either way; ``0`` — the default — disables
    the subsystem byte-for-byte, stats keys included).  The public
    handle is :attr:`prefix_cache` (``None`` when off).

    ``kv_pages > 0`` turns on TRUE PAGED ATTENTION (module docstring
    bullet): no dense arenas — slots read KV through per-slot block
    tables into one shared refcounted page pool (``kv_pages`` pages of
    ``prefill_chunk`` tokens each, carved across co-resident models'
    KV-geometry groups), prefix reuse is a table write with
    copy-on-write at the divergence block, and publish is an ownership
    transfer.  Outputs stay bit-identical to the dense engine and to
    ``generate()``; ``kv_dtype="int8"`` additionally quantizes page
    payloads (tolerance-bounded outputs, double capacity).
    ``paged_attn`` picks the attention backend.  ``None`` — the
    default — resolves to ``'einsum'`` on the CPU platform and
    ``'kernel'`` on every accelerator backend (the dispatch decision is
    recorded in :meth:`metrics`).  ``'einsum'`` reads K/V through the table inside
    the contraction — gather-free, bit-exact; ``'gather'`` is the
    PR 13 gather→dense→scatter baseline; ``'kernel'`` runs the WHOLE
    hot path through the Pallas kernels — paged-decode, the k+1
    verify window and chunked prefill through the flash-window
    kernel, the fused ``lax.while_loop`` programs dispatching kernels
    per iteration, and tree verify through the tree kernel.  Where a
    feature lacks kernel support (today: tree verify over an int8
    pool) an auto-resolved engine selects the einsum path for that
    program and shows it in ``metrics()["paged_attn"]``; an explicit
    ``'kernel'`` raises instead.  Kernel
    programs are tolerance-bounded like flash.  Public handles:
    :attr:`page_pool` / :attr:`page_index`; mutually exclusive with
    ``prefix_cache_blocks`` (the dense COPY cache, which stays
    byte-for-byte unchanged when paging is off).

    ``decode_fuse > 1`` turns on fused decode windows: on pure-decode
    iterations (no queued work, nothing prefilling, no speculation this
    step) the scheduler runs ONE ``lax.while_loop`` program for up to
    ``decode_fuse`` decode steps on device, early-exiting when every
    running slot hits EOS or its budget — one host round trip per
    window instead of per token, outputs bit-identical either way.
    Any step where the host must intervene falls back to the
    single-step path and resumes bit-identically (the window's carry IS
    the single-step state).  ``fuse_stream=True`` additionally taps
    each in-window commit into :attr:`fused_stream` (a bounded
    ``(slot, token)`` ring) via an ordered ``io_callback``.
    ``decode_fuse=1`` — the default — is byte-for-byte the single-step
    engine, stats keys and trace counts included.

    Robustness knobs (see the module docstring): ``queue_limit`` bounds
    the submit queue (:class:`QueueFull` sheds overload);
    ``drafter_timeout_s`` is the per-propose budget past which the
    drafter is quarantined; ``watchdog``/``step_timeout_s`` arm a scoped
    :class:`tpudp.utils.watchdog.Watchdog` deadline around every
    blocking device call; ``step_fault_hook`` (a public attribute; also
    settable later) is called as ``hook(kind, index)`` immediately
    before each device call — the fault-injection seam
    ``tpudp.serve.faults`` plugs into.  ``token_fault_hook(slot, tok,
    request) -> tok`` sits in the single token-commit funnel — the
    SILENT-corruption seam (a flipped sampled token commits and
    conditions every later decode step, exactly what corrupted logits
    produce); ``tpudp.serve.faults.BitFlipLogits`` plugs in here.

    Serving canary (``canary_every_s``; the serve half of the tpudp.sdc
    silent-data-corruption defense): every that-many seconds the engine
    submits a pinned known-prompt GREEDY request through the normal
    scheduler and byte-compares its token stream against the reference
    pinned by the first clean run — greedy decode on fixed weights is
    deterministic, so ANY divergence means a chip computed
    wrong-but-finite numbers somewhere under this engine.  A mismatch
    QUARANTINES the engine (:attr:`quarantined`: admission stops, the
    step loop idles, emitted-so-far tokens stay valid) so
    ``DisaggCluster`` can migrate the live requests out by ticket with
    bit-exact continuation.  Canary requests never appear in
    ``step()``'s emitted pairs; loud canary failures (containment,
    deadline) count as ``canary_errors``, not corruption.

    Tenancy knobs (``tpudp.serve.tenancy``; module docstring
    "Multi-tenancy layer"): ``tenants={name: TenantClass(...)}`` turns
    on per-class bounded queues, priority preemption, and weighted
    admission — ``submit(..., tenant=name)`` classes each request, and
    with classes configured ``queue_limit`` bounds the TOTAL queued
    across classes while each class's own ``queue_limit`` bounds its
    share.  ``models={name: (model, params)}`` registers co-resident
    models a ``TenantClass(model=name)`` can route to (requires
    ``tenants``); every registered model must accommodate the engine's
    ``max_len``.  ``tenants=None`` (the default) is byte-for-byte the
    old single-tenant engine.
    """

    def __init__(self, model, params: dict, *, num_slots: int = 8,
                 max_len: int | None = None, prefill_chunk: int = 16,
                 speculate_k: int = 0, drafter=None,
                 speculate_tree=None,
                 prefix_cache_blocks: int = 0,
                 kv_pages: int = 0, kv_dtype: str | None = None,
                 paged_attn: str | None = None,
                 decode_fuse: int = 1, fuse_stream: bool = False,
                 queue_limit: int | None = None,
                 drafter_timeout_s: float | None = None,
                 watchdog=None, step_timeout_s: float | None = None,
                 step_fault_hook=None, token_fault_hook=None,
                 canary_every_s: float | None = None,
                 canary_prompt=None, canary_new_tokens: int = 8,
                 tenants: dict | None = None,
                 models: dict | None = None, obs: bool = True,
                 flight_dir: str | None = None):
        cfg = model.config
        validate_decode_config(cfg, "Engine")
        layout = page_layout(cfg)
        #: The family's name in a refusal; None for GPT-2 and LLaMA, which
        #: have every program.  The two expert families have their two
        #: paged programs only, the power-retention family its two
        #: slot-arena programs.
        self._two_programs = {
            "latent": "latent-attention",
            "windowed": "window-and-full-attention",
            "state": "power-retention"}.get(layout)
        self._retention = layout == "state"
        if self._retention:
            refusals = [
                ("kv_pages", kv_pages > 0,
                 "the state arena is indexed by slot: no pages"),
                ("kv_dtype", kv_dtype is not None,
                 "the state is kept in its own type, the tail in the "
                 "compute type"),
                ("prefix_cache_blocks", prefix_cache_blocks > 0,
                 "no prefix reuse: a state is not a function of a prefix's "
                 "blocks (no state snapshots)"),
                ("speculate_tree", speculate_tree is not None,
                 "no tree-verify program"),
                ("speculate_k", speculate_k > 0,
                 "no verify program (no rollback of a state)"),
                ("decode_fuse", decode_fuse > 1, "no fused decode program"),
                ("models", bool(models), "no co-residence: one model an "
                 "arena"),
                ("paged_attn", paged_attn == "gather",
                 "no dense view of a state: 'einsum' or 'kernel'"),
                ("prefill_chunk", prefill_chunk % cfg.fold_tokens != 0,
                 f"prefill_chunk must be a multiple of fold_tokens "
                 f"({cfg.fold_tokens}): a chunk starts on a fold boundary")]
        elif self._two_programs:
            # What these families do not serve yet, refused by option name
            # (docs/SERVING.md, the family table).
            refusals = [
                ("kv_pages", not kv_pages,
                 f"kv_pages must be > 0: {layout} pages have no dense "
                 "slot arena"),
                ("kv_dtype", kv_dtype is not None,
                 f"{layout} pages are kept in the compute dtype"),
                ("speculate_tree", speculate_tree is not None,
                 "no tree-verify program"),
                ("speculate_k", speculate_k > 0, "no verify program"),
                ("decode_fuse", decode_fuse > 1, "no fused decode program"),
                ("models", bool(models), "no co-residence: one model a pool"),
                ("paged_attn", paged_attn == "gather",
                 f"no dense view of {layout} pages: 'einsum' or 'kernel'")]
            if layout == "windowed":
                full = num_slots * ((cfg.max_seq_len if max_len is None
                                     else max_len) // max(prefill_chunk, 1))
                refusals += [
                    ("kv_pages", 0 < kv_pages < full,
                     f"kv_pages must hold every slot's full reservation "
                     f"({full} pages): a vacated slot's window pages "
                     f"cannot be resumed yet")]
        if self._two_programs:
            for option, refused, why in refusals:
                if refused:
                    raise ValueError(
                        f"Engine({option}=...) is not served for the "
                        f"{self._two_programs} family "
                        f"({type(cfg).__name__}): {why}")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if speculate_k < 0:
            raise ValueError(
                f"speculate_k must be >= 0, got {speculate_k}")
        if decode_fuse < 1:
            raise ValueError(
                f"decode_fuse must be >= 1 (1 disables the fused decode "
                f"loop), got {decode_fuse}")
        if fuse_stream and decode_fuse <= 1:
            raise ValueError(
                "fuse_stream requires decode_fuse >= 2 — the stream tap "
                "rides the fused lax.while_loop program")
        if prefix_cache_blocks < 0:
            raise ValueError(
                f"prefix_cache_blocks must be >= 0 (0 disables prefix "
                f"caching), got {prefix_cache_blocks}")
        if kv_pages < 0:
            raise ValueError(
                f"kv_pages must be >= 0 (0 keeps the dense slot arena), "
                f"got {kv_pages}")
        if kv_pages and prefix_cache_blocks:
            raise ValueError(
                "kv_pages (paged attention: slots reference one shared "
                "page pool in place, prefix reuse is a table write) and "
                "prefix_cache_blocks (the dense COPY cache) are mutually "
                "exclusive — paged mode subsumes the copy path")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        if kv_dtype is not None and not kv_pages:
            raise ValueError(
                "kv_dtype requires kv_pages > 0 — quantized KV lives in "
                "page-pool payloads behind the table indirection")
        if paged_attn not in (None, "einsum", "gather", "kernel"):
            raise ValueError(
                f"paged_attn must be None (auto: 'einsum' on CPU, "
                f"'kernel' on accelerators), 'einsum' (gather-free "
                f"bit-exact blockwise attention), 'gather' (PR 13's "
                f"gather→dense→scatter baseline), or 'kernel' (the "
                f"Pallas hot-path kernels, tolerance-bounded); got "
                f"{paged_attn!r}")
        if paged_attn is not None and paged_attn != "einsum" \
                and not kv_pages and not self._retention:
            raise ValueError(
                f"paged_attn={paged_attn!r} requires kv_pages > 0 — the "
                f"paged-attention backend choice only exists behind the "
                f"block-table indirection")
        # The default resolution: unset paged_attn means "the Pallas
        # kernels on every accelerator".  Only the CPU platform (every
        # tier-1 test) resolves to the bit-exact einsum path — there an
        # explicit 'kernel' still runs (interpret mode) for parity
        # testing.  The test is "is this the CPU", not "is this named
        # tpu": an accelerator backend must never land on einsum unasked.
        self.paged_attn_requested = paged_attn
        if paged_attn is None:
            paged_attn = ("kernel" if (kv_pages or self._retention)
                          and jax.default_backend() != "cpu" else "einsum")
        if drafter is not None and speculate_k == 0:
            raise ValueError("drafter requires speculate_k >= 1 "
                             "(speculation is off at k=0)")
        if speculate_k > 0 and drafter is None:
            from tpudp.serve.speculate import NgramDrafter

            drafter = NgramDrafter()
        dcfg = getattr(drafter, "config", None)
        if dcfg is not None and dcfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"drafter vocab_size ({dcfg.vocab_size}) must match the "
                f"target model's ({cfg.vocab_size}) — speculation "
                f"requires a shared tokenizer")
        max_len = cfg.max_seq_len if max_len is None else max_len
        if max_len > cfg.max_seq_len:
            raise ValueError(
                f"max_len ({max_len}) exceeds the model's max_seq_len "
                f"({cfg.max_seq_len})")
        # Chunk writes start at multiples of prefill_chunk; a max_len that
        # is not a multiple would let the final chunk's fixed-size write
        # be CLAMPED backwards by dynamic_update_slice, silently
        # clobbering earlier positions.  Round down (never up: the
        # position table bound above must hold).
        self.max_len = (max_len // prefill_chunk) * prefill_chunk
        if self.max_len < prefill_chunk:
            raise ValueError(
                f"max_len ({max_len}) must fit at least one prefill "
                f"chunk ({prefill_chunk})")
        if speculate_k > 0 and self.max_len <= speculate_k:
            raise ValueError(
                f"max_len ({self.max_len}) must exceed speculate_k "
                f"({speculate_k}) — the arena reserves k scratch "
                f"positions per slot for the speculative window")
        # Tree speculation (opt-in): a static shape of candidate
        # branches verified per step by the tree programs.  Rides the
        # speculative window's arena reserve, so the shape's depth is
        # bounded by speculate_k; tolerance-bounded attention (like
        # paged_attn='kernel'), hence opt-in.
        self.speculate_tree = None
        if speculate_tree is not None:
            from tpudp.serve.speculate import tree_shape

            if speculate_k == 0:
                raise ValueError(
                    "speculate_tree requires speculate_k >= 1 — the "
                    "tree rides the speculative window's arena reserve")
            shape = tree_shape(speculate_tree)
            if shape.max_depth > speculate_k:
                raise ValueError(
                    f"speculate_tree {shape.name!r} max_depth "
                    f"({shape.max_depth}) exceeds speculate_k "
                    f"({speculate_k}) — the arena reserves exactly k "
                    f"scratch positions per slot")
            if not hasattr(drafter, "propose_tree"):
                raise ValueError(
                    f"speculate_tree requires a drafter with "
                    f"propose_tree() (e.g. NgramDrafter); "
                    f"{type(drafter).__name__} has none")
            self.speculate_tree = shape
        # Fused speculation (the tentpole seam): with a MODEL drafter
        # whose weights can ride into the device program, a
        # fuse-eligible iteration runs draft→verify→accept as one
        # lax.while_loop program instead of host-drafted per-step
        # verify.  The draft model must cover max_len + k positions:
        # the in-carry drafter prefills the full max_len-wide history
        # (the host DraftModelDrafter's pinned-bucket geometry — the
        # bit-parity referee) and decodes k past it.  Anything else
        # (ngram drafter, short draft model, decode_fuse=1, tree mode)
        # keeps the host-drafted path byte-for-byte.
        dparams = getattr(drafter, "params", None)
        self._spec_fusable = (
            speculate_k > 0 and decode_fuse > 1
            and speculate_tree is None
            and dcfg is not None and dparams is not None
            and dcfg.max_seq_len >= self.max_len + speculate_k)
        self._draft_pair = ((dcfg, dparams) if self._spec_fusable
                            else None)
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(
                f"queue_limit must be >= 1 (or None for unbounded), "
                f"got {queue_limit}")
        if drafter_timeout_s is not None and drafter_timeout_s <= 0:
            raise ValueError(f"drafter_timeout_s must be > 0, got "
                             f"{drafter_timeout_s}")
        if step_timeout_s is not None and step_timeout_s <= 0:
            raise ValueError(
                f"step_timeout_s must be > 0, got {step_timeout_s}")
        self.model = model
        self.config = cfg
        self.params = params
        self.num_slots = num_slots
        self.prefill_chunk = prefill_chunk
        self.speculate_k = speculate_k
        self.drafter = drafter
        self._prefix_cache_blocks = prefix_cache_blocks
        # True paged attention (kv_pages > 0): per-slot block tables
        # into ONE shared page pool per KV geometry, copy-on-write
        # prefix reuse, no dense arenas.  kv_pages=0 — the default — is
        # byte-for-byte the dense engine (no paged program traced, no
        # paged stats keys, no pool allocated).
        self._paged = kv_pages > 0
        self.kv_pages = kv_pages
        self.kv_dtype = kv_dtype
        # Paged-attention backend (only meaningful with kv_pages > 0):
        # "einsum" — gather-free blockwise attention through the table,
        # bit-exact vs dense; "gather" — the PR 13 gather/scatter
        # baseline; "kernel" — the Pallas hot-path kernels
        # (tolerance-bounded, TPU default).  paged_attn_requested keeps
        # the constructor value (None = auto) for metrics().
        self.paged_attn = paged_attn
        # Static per-program dispatch table: which impl each paged
        # program family actually traces with.  The decision is made
        # here, once, at build time — an AUTO-resolved kernel engine
        # falls back to the bit-exact einsum program wherever a feature
        # lacks kernel support (today: tree verify over an int8 pool),
        # and metrics() exposes this table so the fall-back is visible.
        # An EXPLICIT paged_attn='kernel' that cannot be honoured for a
        # program this engine will run is an error, never an einsum.
        self.paged_attn_dispatch: dict[str, str] = {}
        if self._paged:
            fams = ("decode_paged", "prefill_paged") \
                if self._two_programs else (
                "decode_paged", "verify_paged", "prefill_paged",
                "fused_decode_paged", "fused_spec_paged",
                "tree_verify_paged")
            self.paged_attn_dispatch = {f: paged_attn for f in fams}
            if paged_attn == "kernel" and kv_dtype == "int8":
                if (self.paged_attn_requested == "kernel"
                        and self.speculate_tree is not None):
                    raise ValueError(
                        "paged_attn='kernel' cannot be honoured for "
                        "speculate_tree over kv_dtype='int8': the "
                        "tree-verify kernel reads fp pages only.  Leave "
                        "paged_attn unset (the tree program then runs "
                        "einsum, recorded in metrics()['paged_attn']) "
                        "or drop kv_dtype/speculate_tree")
                self.paged_attn_dispatch["tree_verify_paged"] = "einsum"
        self._max_pages = self.max_len // prefill_chunk  # table width
        # Fused decode windows (module docstring "Fused decode windows"):
        # decode_fuse=1 — the default — never touches the fused program
        # and is byte-for-byte the single-step engine.
        self.decode_fuse = decode_fuse
        self._fuse_stream = bool(fuse_stream)
        self.fused_stream: _Ring | None = None
        self._ring_id = -1
        if self._fuse_stream:
            self._ring_id = next(_RING_IDS)
            # Bound = a few windows' worth of tokens: the ring is an
            # observability tap (the window's returned carry is the
            # commit path), so overflow drops oldest instead of growing.
            self.fused_stream = _Ring(
                maxlen=max(4 * num_slots * decode_fuse, 64))
            _STREAM_RINGS[self._ring_id] = self.fused_stream
        # Per-model serving state (arena + weight-bound programs +
        # optional prefix cache), default model under key None.
        # Co-resident models (key = registered name) each add their own
        # _ModelState behind the same scheduler; with none registered
        # this is exactly the old single-model engine state.
        self._mstates: dict[str | None, _ModelState] = {}
        self._add_model(None, model, params)
        # Tenancy: per-class queues + priority/stride admission
        # (tpudp.serve.tenancy).  None = the old single-FIFO engine.
        self.tenants = tenants
        self._sched = None
        if tenants is not None:
            from tpudp.serve.tenancy import TenantScheduler

            self._sched = TenantScheduler(tenants)
        if models:
            if self._sched is None:
                raise ValueError(
                    "models= (co-resident models) requires tenants= — "
                    "requests route to a model through their "
                    "TenantClass(model=name)")
            for mname, pair in models.items():
                if not isinstance(mname, str) or not mname:
                    raise ValueError(
                        f"model names must be non-empty strings, "
                        f"got {mname!r}")
                try:
                    m, p = pair
                except (TypeError, ValueError):
                    raise ValueError(
                        f"models[{mname!r}] must be a (model, params) "
                        f"pair") from None
                self._add_model(mname, m, p)
        if self._sched is not None:
            for tname in self._sched.names:
                route = self._sched.cls(tname).model
                if route is not None and route not in self._mstates:
                    raise ValueError(
                        f"tenants[{tname!r}] routes to unregistered "
                        f"model {route!r} (registered: "
                        f"{sorted(k for k in self._mstates if k)})")
        if self._paged:
            self._build_page_pools()
        # Each slot's PRNG key chain lives on the device and is touched
        # there only by step programs.  Until a slot's first sample (which
        # the prefill program that ends its fill makes) its initial key
        # stays on the host: ``_pending_keys[s]``, None once the chain is
        # on the device (``_slot_key`` reads whichever holds it).
        self._keys = jnp.zeros((num_slots, 2), jnp.uint32)
        self._pending_keys: list[np.ndarray | None] = [None] * num_slots
        # An expert family's per-run counters, on the device until the
        # next token fetch takes them along (_count_moe).
        self._moe_pending: list = []
        # Host-authoritative per-slot state, uploaded each step (tiny
        # arrays; values are data, never shapes).
        self._len = np.zeros(num_slots, np.int32)
        self._last = np.zeros(num_slots, np.int32)
        self._temps = np.zeros(num_slots, np.float32)
        self._topk = np.zeros(num_slots, np.int32)
        self._topp = np.ones(num_slots, np.float32)
        self._slots: list[Request | None] = [None] * num_slots
        self._queue: collections.deque[Request] = collections.deque()
        self._next_id = 0
        self._admitted = 0
        self.stats = collections.Counter()
        # Robustness state.
        self.queue_limit = queue_limit
        self.drafter_timeout_s = drafter_timeout_s
        self.step_fault_hook = step_fault_hook
        self.token_fault_hook = token_fault_hook
        # Serving canary (silent-corruption defense, module docstring):
        # reference pinned by the first clean completion; a later
        # mismatch quarantines the engine.
        if canary_every_s is not None and canary_every_s < 0:
            raise ValueError(
                f"canary_every_s must be >= 0 (0 = a canary in flight "
                f"whenever possible), got {canary_every_s}")
        if canary_new_tokens < 1:
            raise ValueError(
                f"canary_new_tokens must be >= 1, got {canary_new_tokens}")
        self.canary_every_s = canary_every_s
        if canary_prompt is None:
            # Deterministic pinned prompt: fixed tokens valid for any
            # vocab — the same bytes every process lifetime.
            canary_prompt = (np.arange(1, 9, dtype=np.int32)
                             % model.config.vocab_size)
        self._canary_prompt = np.asarray(canary_prompt, np.int32)
        self._canary_new_tokens = canary_new_tokens
        self._canary_ref: tuple | None = None
        self._canary_active = None
        self._canary_last = -float("inf")  # first canary fires at once
        self._quarantined = False
        self.quarantine_reason: str | None = None
        self._watchdog = watchdog
        self._step_timeout_s = step_timeout_s
        self._device_calls = 0
        self._accepting = True
        self._closed = False
        self._drafter_quarantined = False
        self.drafter_quarantine_reason: str | None = None
        self.last_step_error: BaseException | None = None
        # Structured telemetry (tpudp.obs): a bounded span/event ring —
        # request lifecycle events off the hot path, allocation-free
        # begin/end around every device call — plus a flight recorder
        # that dumps the ring on step-failure containment and watchdog
        # timeouts.  ``obs=False`` turns the recorder into O(1) no-ops;
        # dumps are enabled by directory (``flight_dir`` or
        # TPUDP_FLIGHT_DIR), so the default engine writes nothing.
        self.obs = Recorder(name="serve", enabled=obs)
        # The exposed clock (``_poll``, ``_enqueued``, ``_device``): the
        # open ``exposed`` span's token, the phase the host is in (what
        # the span's time is charged to), and an output of the newest
        # device work the engine enqueued — one that no later program
        # donates, so a readiness check on it never meets a deleted
        # buffer (None: unknown until the next dispatch).
        self._xtok = NO_SPAN
        self._xphase = "caller"
        self._newest = self._keys
        self.flight = FlightRecorder(self.obs, flight_dir,
                                     component="serve")
        if watchdog is not None and getattr(watchdog, "flight",
                                            None) is None:
            # A wedged device call must leave a black box even when the
            # watchdog hard-exits: the monitor thread dumps this
            # engine's ring before callbacks/kill (tpudp/utils/
            # watchdog.py).  Only claim an unowned watchdog — a shared
            # one keeps its first owner's recorder.
            watchdog.flight = self.flight

    # -- model registry ------------------------------------------------

    def _add_model(self, name: str | None, model, params) -> None:
        """Register one model behind the scheduler: its own slot arena
        (same (num_slots, max_len) geometry as every other model's),
        its config's step programs bound to its weights (memoized per
        config — two engines or two tenants over one config compile
        once), and its own prefix cache when caching is on (cached KV
        is a function of MODEL and tokens; blocks must never cross
        models)."""
        cfg = model.config
        if name is not None:
            validate_decode_config(cfg, f"Engine(models[{name!r}])")
            if cfg.max_seq_len < self.max_len:
                raise ValueError(
                    f"models[{name!r}] max_seq_len ({cfg.max_seq_len}) "
                    f"is below the engine arena max_len "
                    f"({self.max_len}) — co-resident models share the "
                    f"slot geometry")
            dcfg = getattr(self.drafter, "config", None)
            if dcfg is not None and dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"drafter vocab_size ({dcfg.vocab_size}) must match "
                    f"co-resident model {name!r}'s ({cfg.vocab_size}) — "
                    f"speculation requires a shared tokenizer")
        dcfg, dparams = self._draft_pair or (None, None)
        ms = _ModelState(name, model, params,
                         _build_steps(cfg, self.paged_attn
                                      if self._paged or self._retention
                                      else "einsum", dcfg),
                         draft_params=dparams)
        # Prefix cache: blocks sized to prefill_chunk so a cached block
        # boundary is always a chunk boundary (imported lazily — the
        # module imports TRACE_COUNTS from here, and the cache is
        # optional).  None when off: every prefix-cache code path below
        # is gated on it, so prefix_cache_blocks=0 is byte-for-byte the
        # pre-cache engine (stats keys and trace counts included).
        if self._prefix_cache_blocks:
            from tpudp.serve.prefix_cache import PrefixCache

            ms.prefix_cache = PrefixCache(cfg, self._prefix_cache_blocks,
                                          self.prefill_chunk)
        # Paged mode allocates NO dense arena — the shared page pools
        # (and per-model tables/indexes) are carved once every model is
        # registered (_build_page_pools); until then ms.cache stays
        # None, which every dense-only path below is gated on.
        if not self._paged:
            ms.cache = slot_arena(cfg, self.num_slots, self.max_len)
        self._mstates[name] = ms

    def _build_page_pools(self) -> None:
        """Carve ``kv_pages`` across the registered models' KV-geometry
        groups: models sharing what their page type calls its geometry
        (``generate.page_type``: layers, kv_heads, head_dim, dtype)
        literally share ONE PagePool buffer — an idle tenant reserves
        zero pages instead of a dense ``(num_slots, max_len)`` arena —
        while distinct geometries split the page budget evenly (pages
        of different shapes cannot share a buffer).  Every model gets
        its own radix PageIndex over the group pool (cached KV is a
        function of model and tokens) plus a host-side block table."""
        from tpudp.serve.prefix_cache import PageIndex, PagePool

        groups: dict[tuple, list[_ModelState]] = {}
        for ms in self._mstates.values():
            key = page_type(ms.config, self.kv_dtype).geometry(ms.config)
            groups.setdefault(key, []).append(ms)
        per_group = self.kv_pages // len(groups)
        if per_group < self._max_pages:
            raise ValueError(
                f"kv_pages ({self.kv_pages}) carves to {per_group} "
                f"pages per KV-geometry group ({len(groups)} groups) — "
                f"below the {self._max_pages} pages one max_len "
                f"({self.max_len}) request needs; raise kv_pages")
        for members in groups.values():
            cfg = members[0].config
            # Sliding-window layers get a pool of their own, sized by the
            # window and never by the context: the pages a slot's window
            # can overlap, and the one being written.
            window = getattr(cfg, "sliding_window", None) \
                if page_layout(cfg) == "windowed" else None
            pool = PagePool(
                cfg, per_group, self.prefill_chunk, self.kv_dtype,
                window_pages=0 if window is None else self.num_slots * (
                    -(-window // self.prefill_chunk) + 1))
            for ms in members:
                ms.pool = pool
                ms.index = PageIndex(pool)
                ms.table = np.full((self.num_slots, self._max_pages),
                                   -1, np.int32)
                if window is not None:
                    ms.wtable = np.full_like(ms.table, -1)
                ms.slot_nodes = [dict() for _ in range(self.num_slots)]

    @property
    def prefix_cache(self):
        """The DEFAULT model's prefix cache (``None`` when caching is
        off) — the public handle tests and tools inspect.  Co-resident
        models each hold their own cache internally."""
        return self._mstates[None].prefix_cache

    @property
    def page_pool(self):
        """The DEFAULT model's shared :class:`PagePool` (``None`` with
        paging off) — co-resident models of the same KV geometry share
        this very object."""
        return self._mstates[None].pool

    @property
    def page_index(self):
        """The DEFAULT model's radix :class:`PageIndex` (``None`` with
        paging off)."""
        return self._mstates[None].index

    @property
    def tenant_stats(self) -> dict:
        """Per-tenant counters (``{name: Counter}``): submitted,
        admitted (fresh slot grants), readmitted (resumes after
        preemption or step-failure requeue), shed, preempted, tokens,
        plus one count per terminal finish reason.  Empty dict with
        tenancy off."""
        if self._sched is None:
            return {}
        return {name: self._sched.stats(name)
                for name in self._sched.names}

    # -- submission ----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int | None = None,
               top_p: float | None = None, seed: int = 0,
               eos_id: int | None = None,
               deadline_s: float | None = None,
               ttft_deadline_s: float | None = None,
               tenant: str | None = None) -> Request:
        """Queue one generation request; returns its streaming handle.

        Same sampling contract as ``generate()``: ``temperature=0`` is
        greedy (``top_k``/``top_p`` rejected), otherwise softmax sampling
        truncated to top-k and/or the top-p nucleus, seeded per request
        (draws are independent of co-resident requests).  ``eos_id``
        retires the request early when sampled (the eos token is
        included in ``tokens``).

        ``deadline_s`` bounds the request's total wall-clock budget from
        submit; ``ttft_deadline_s`` bounds the wait for the FIRST token
        (a queueing/prefill SLO — it stops applying once a token is
        emitted).  An expired request retires with
        ``FinishReason.DEADLINE`` at the next scheduler iteration; its
        emitted tokens stay on the handle and its slot frees.

        ``tenant`` names the request's admission class on a
        tenant-aware engine (``Engine(tenants=...)``): the class's
        ``queue_limit`` bounds ITS queue (typed :class:`QueueFull`),
        its ``default_deadline_s`` fills in a missing ``deadline_s``,
        and its ``model`` routes the request to a registered
        co-resident model.  ``tenant=None`` routes to the class named
        ``"default"`` when one exists; on a tenancy-off engine passing
        ``tenant`` is an error.

        Raises :class:`EngineClosed` after :meth:`drain`/:meth:`close`,
        and :class:`QueueFull` when ``queue_limit`` queued requests are
        already waiting (the typed backpressure signal — checked before
        any validation, so overload is refused at minimum cost)."""
        if not self._accepting:
            raise EngineClosed(
                "Engine.drain()/close() was called; the engine no longer "
                "accepts work")
        tname = tc = None
        if self._sched is not None:
            tname = self._sched.resolve(tenant)
            tc = self._sched.cls(tname)
        elif tenant is not None:
            raise ValueError(
                "submit(tenant=...) requires Engine(tenants=...) — this "
                "engine has no tenant classes configured")
        if (self.queue_limit is not None
                and self.queue_depth >= self.queue_limit):
            self.stats["shed"] += 1
            if tname is not None:
                self._sched.stats(tname)["shed"] += 1
            raise QueueFull(
                f"queue_limit ({self.queue_limit}) queued requests "
                f"already waiting; request refused (shed)")
        if tc is not None and self._sched.full(tname):
            self.stats["shed"] += 1
            self._sched.stats(tname)["shed"] += 1
            raise QueueFull(
                f"tenant {tname!r} queue_limit ({tc.queue_limit}) "
                f"queued requests already waiting; request refused "
                f"(shed)")
        if tc is not None and deadline_s is None:
            deadline_s = tc.default_deadline_s  # class-wide SLO
        ms = self._mstates[tc.model if tc is not None else None]
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must hold at least one token")
        vocab = ms.config.vocab_size
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise ValueError(f"prompt ids must be in [0, {vocab})")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        total = prompt.size + max_new_tokens + self.speculate_k
        if total > self.max_len:
            spec = (f" + speculate_k ({self.speculate_k} scratch "
                    f"positions for the verify window)"
                    if self.speculate_k else "")
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}){spec} exceeds the arena max_len "
                f"({self.max_len})")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if (top_k is not None or top_p is not None) and temperature == 0.0:
            raise ValueError("top_k/top_p require temperature > 0 (greedy "
                             "decoding ignores them)")
        if top_k is not None and top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if eos_id is not None and not 0 <= eos_id < vocab:
            raise ValueError(f"eos_id must be in [0, {vocab})")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if ttft_deadline_s is not None and ttft_deadline_s <= 0:
            raise ValueError(
                f"ttft_deadline_s must be > 0, got {ttft_deadline_s}")
        r = Request(self, self._next_id, prompt, max_new_tokens,
                    float(temperature), int(top_k or 0),
                    float(1.0 if top_p is None else top_p), seed, eos_id,
                    deadline_s=deadline_s, ttft_deadline_s=ttft_deadline_s,
                    tenant=tname)
        r._ms = ms
        self._next_id += 1
        if self._sched is not None:
            self._sched.enqueue(r)
            self._sched.stats(tname)["submitted"] += 1
        else:
            self._queue.append(r)
        self.stats["submitted"] += 1
        return r

    def generate_many(self, prompts, max_new_tokens: int, *,
                      temperature: float = 0.0, top_k: int | None = None,
                      top_p: float | None = None, seed: int = 0,
                      eos_id: int | None = None) -> list[np.ndarray]:
        """Batched convenience wrapper: submit every prompt (request i is
        seeded ``seed + i``), run to completion, return the full
        sequences in submission order.  If a later submit raises (bad
        prompt i, queue full), the already-queued prompts 0..i-1 are
        CANCELLED before the error propagates — a failed batch must not
        leave orphans pinned in the queue forever.  Results go through
        :meth:`Request.result`, so a request that did not finish
        successfully (e.g. a persistent step failure) raises
        :class:`RequestFailed` instead of silently returning a truncated
        sequence."""
        handles = []
        try:
            for i, p in enumerate(prompts):
                handles.append(
                    self.submit(p, max_new_tokens, temperature=temperature,
                                top_k=top_k, top_p=top_p, seed=seed + i,
                                eos_id=eos_id))
        except Exception:
            for h in handles:
                self.cancel(h)
            raise
        self.run_until_complete()
        return [h.result() for h in handles]

    # -- scheduling ----------------------------------------------------

    def step(self) -> list[tuple[Request, int]]:
        """One scheduler iteration: expire deadlines, preempt
        lower-priority slots for waiting higher-priority work (tenancy
        only), admit queued requests into free slots, run at most one
        prefill chunk (the oldest admitted request still prefilling;
        highest tier first with tenancy on), then one batched decode
        step — or, with speculation on, one batched draft+verify
        window — for every model's decoding slots.  Returns the
        ``(request, token)`` pairs emitted.

        An exception escaping a device step is CONTAINED
        (:meth:`_contain_step_failure`): in-flight requests are requeued
        once (then retired with ``ERROR``), the arena is rebuilt, and
        the engine keeps serving — the one failure mode this layer
        forbids is a wedge.  A closed engine's step is a no-op."""
        emitted: list[tuple[Request, int]] = []
        if self._closed or self._quarantined:
            return emitted
        # Phase spans (tpudp.obs begin/end through ``_enter`` / ``_leave``,
        # children of ``step`` by enclosure on this one scheduler thread:
        # ``STEP_PHASES``): ``step``'s time in no child is the scheduler's
        # own.  A child an exception cuts short stays open in the ring —
        # the flight recorder's "where it was".  The exposed clock checks
        # for a drained device (``_poll``) at the end of admission and of
        # ``batch``, at the start of ``pages``, of each dispatch
        # (``_device``) and of each commit after a fetch.
        obs = self.obs
        if self._xtok >= 0:  # the device idled through the caller's code
            obs.piece(self._xtok, "caller")
        self._xphase = "step"
        in_step = obs.begin("step")
        if self.canary_every_s is not None:
            span = self._enter("canary")
            self._maybe_canary()
            self._leave(span)
            if self._quarantined:  # the canary just condemned this engine
                self._end_step(in_step)
                return emitted
        try:
            # Deadline expiry and admission sit INSIDE the containment
            # region: with prefix caching on, a deadline retirement can
            # publish KV blocks and admission runs block copies (which
            # donate the arena) — a failure (or a pending watchdog hang
            # surfacing in a guard) must requeue + rebuild like any
            # other step failure instead of escaping to the caller.
            # Cache off, neither touches device state and this changes
            # nothing.
            span = self._enter("admit")
            self._expire_deadlines()
            if self._sched is not None:
                self._preempt_for_priority()
            self._admit()
            slot = self._next_prefill_slot()
            if self._slots.count(None) < self.num_slots:
                self._poll()
            self._leave(span)
            if slot is not None:
                self._run_prefill_chunk(slot, emitted)
            # Fuse only on PURE-DECODE iterations: nothing queued (so
            # admission/preemption cannot be waiting on a slot a
            # mid-window retirement would free) and nothing prefilling
            # (a prompt's next chunk must not stall behind a window).
            # Deadlines do NOT gate fusing — expiry is detected at the
            # window edge, overshoot bounded by decode_fuse tokens.
            fuse = (self.decode_fuse > 1 and self.queue_depth == 0
                    and self._next_prefill_slot() is None)
            # One batched decode (or draft+verify) per model with
            # decoding slots — with no co-resident models registered
            # this is exactly the old single decode step.
            for ms in self._mstates.values():
                span = self._enter("batch")
                active = np.array(
                    [r is not None and r._nfill == r._fill.size
                     and r._ms is ms for r in self._slots])
                decoding = active.any()
                if decoding:
                    self._poll()  # the prefill chunk may have ended
                self._leave(span)
                if not decoding:
                    continue
                if self._paged:
                    # Back every table entry the step is about to
                    # write BEFORE dispatch (plain decode: one token;
                    # verify: the k+1 window; fused: the whole
                    # window).  Page pressure resolves here on the
                    # host — evict cold cache leaves, then vacate the
                    # most recent co-resident slot through the
                    # bit-exact resume path — so the device program
                    # only ever sees fully-backed tables.
                    span = self._enter("pages")
                    self._poll()
                    active = self._ensure_decode_pages(ms, active, fuse)
                    decoding = active.any()
                    self._leave(span)
                    if not decoding:
                        continue
                if self.speculate_k and not self._drafter_quarantined:
                    if self.speculate_tree is not None:
                        self._run_verify_tree(ms, active, emitted)
                    elif fuse and self._spec_fusable:
                        self._run_spec_fused(ms, active, emitted)
                    else:
                        self._run_verify(ms, active, emitted)
                elif fuse:
                    self._run_decode_fused(ms, active, emitted)
                else:
                    self._run_decode(ms, active, emitted)
        except Exception as exc:  # noqa: BLE001 — containment by design
            self._contain_step_failure(exc)
        self.stats["steps"] += 1
        if self.canary_every_s is not None:
            # Canary tokens are the engine's own probe traffic — they
            # live on the canary handle, never in the emitted pairs.
            emitted = [(r, t) for (r, t) in emitted
                       if not getattr(r, "_canary", False)]
        self._end_step(in_step)
        return emitted

    # -- the exposed clock (docs/OBSERVABILITY.md) ----------------------
    #
    # The ``exposed`` span is open exactly while the engine KNOWS none of
    # its device work is in flight: it opens when a blocking fetch of the
    # newest work has returned or a readiness check of it (``_poll``)
    # comes back true, and closes when the next work has been enqueued
    # (``_device`` returning, ``_enqueued``).  The device drained at some
    # moment before the engine learnt it, so the span is a lower bound
    # on the device's idle time.  Its time is charged in pieces to the
    # phase the host was in (``EXPOSED_PHASES``).

    def _enter(self, phase: str) -> int:
        """Open the span of one of ``step``'s phases (``STEP_PHASES``);
        the open ``exposed`` span's time so far goes to the phase the
        host leaves."""
        span = self.obs.begin(phase)
        if self._xtok >= 0:
            self.obs.piece(self._xtok, self._xphase)
        self._xphase = phase
        return span

    def _leave(self, span: int) -> None:
        """Close a phase's span: the host is back in ``step``'s own
        code."""
        if self._xtok >= 0:
            self.obs.piece(self._xtok, self._xphase)
        self._xphase = "step"
        self.obs.end(span)

    def _poll(self) -> None:
        """The readiness check at a phase boundary: if the output of the
        newest device work is ready, nothing of the engine's is in
        flight and ``exposed`` opens now.  ``is_ready()`` does not wait;
        with the recorder off it is never called."""
        if (self._xtok < 0 and self.obs.enabled
                and self._newest is not None and self._newest.is_ready()):
            self._xtok = self.obs.begin("exposed")

    def _enqueued(self, out) -> None:
        """Device work was just enqueued outside ``_device`` (an eager
        operation): ``exposed`` closes, its last piece going to the
        phase the host is in, and ``out`` — an output no later program
        donates, or None where there is none — is the newest work."""
        if self._xtok >= 0:
            self.obs.end(self._xtok, self._xphase)
            self._xtok = NO_SPAN
        if self.obs.enabled:
            self._newest = out

    def _end_step(self, in_step: int) -> None:
        """Close ``step``.  An engine left with no slot in use and nothing
        queued closes ``exposed`` too: with no work, idle is not
        exposed.  Otherwise the span runs on into the caller's code."""
        if self._xtok >= 0:
            if (self._slots.count(None) == self.num_slots
                    and not self.queue_depth):
                self.obs.end(self._xtok, "step")
                self._xtok = NO_SPAN
            else:
                self.obs.piece(self._xtok, "step")
        self._xphase = "caller"
        self.obs.end(in_step)

    def cancel(self, request: Request) -> bool:
        """Retire ``request`` immediately — queued or in flight — and
        free its slot for the next queued request (today's alternative is
        a disconnected client pinning a slot until ``max_new_tokens``).
        Tokens already emitted stay on the handle; the freed slot's stale
        KV needs no scrubbing (the arena's overwrite-before-visible rule
        covers recycled slots).  Returns False if the request already
        finished (completed or previously cancelled) or no longer
        belongs to this engine (``export_ticket`` detached it — the
        migrate-vs-cancel race: the request now lives in a ticket or on
        another host, so the caller cancels through its cluster-level
        handle instead), True otherwise."""
        if request.done:
            return False
        if request._slot is not None:
            self._retire(request._slot, FinishReason.CANCELLED)
            return True
        try:
            if self._sched is not None:
                self._sched.remove(request)
            else:
                self._queue.remove(request)
        except ValueError:
            return False  # migrated out: not this engine's to cancel
        self._finish(request, FinishReason.CANCELLED)
        return True

    def run_until_complete(self) -> None:
        """Drive the engine until every queue and every slot is empty.
        Stops early if a canary quarantine fires — a quarantined
        engine's step is a no-op, and its live requests are waiting to
        be MIGRATED out (``DisaggCluster.evacuate``), not finished
        here."""
        while self.queue_depth or any(r is not None for r in self._slots):
            if self._quarantined:
                return
            self.step()

    # -- cross-host migration hooks (tpudp/serve/disagg.py) ------------

    def _refuse_tickets(self, method: str) -> None:
        if self._two_programs:
            raise ValueError(
                f"Engine.{method}() is not served for the "
                f"{self._two_programs} family "
                f"({type(self.config).__name__}): the migration wire "
                f"format carries the K/V pages of one pool only"
                + (", not a slot's state" if self._retention else ""))

    def export_ticket(self, request: Request):
        """Detach a live request into a :class:`tpudp.serve.disagg.
        MigrationTicket` — the sender half of cross-host KV migration.

        An in-flight slot exports its chunk-prefilled prefix pages as
        host payloads (read BEFORE vacate, so tree nodes and other
        slots sharing those pages are untouched — their refs release
        symmetrically through the normal vacate path), publishes the
        prefix locally (the pages stay resident as evictable cache on
        the sender), then vacates through the one bit-exact carry-over
        path: emitted tokens and the per-slot PRNG chain ride the
        ticket, so the receiver continues the exact sampled sequence.
        A QUEUED request exports tokens-only (nothing prefilled yet).
        The source handle is left detached (not done — ``FinishReason``
        never grows a user-visible MIGRATED value; the disagg layer
        tracks the request through the ticket and the receiver's new
        handle).  Raises :class:`ValueError` for a finished request."""
        from tpudp.serve import disagg as _dg

        self._refuse_tickets("export_ticket")

        r = request
        if r.done:
            raise ValueError(f"request {r.id} already finished "
                             f"({r.finish_reason}); nothing to migrate")
        s = r._slot
        pages: list[dict] = []
        if s is None:
            if self._sched is not None:
                self._sched.remove(r)
            else:
                self._queue.remove(r)
            r._fill = np.concatenate([r.prompt,
                                      np.asarray(r.tokens, np.int32)])
            r._nfill = 0
        else:
            ms = r._ms
            if self._paged:
                n_blocks = (min(r._nfill, r._fill.size)
                            // self.prefill_chunk)
                for i in range(n_blocks):
                    page = int(ms.table[s, i])
                    if page >= 0:
                        pages.append(ms.pool.read_page(page))
                self._enqueued(None)  # the page reads ran on the device
            if ((self._paged or ms.prefix_cache is not None)
                    and self._accepting):
                self._publish_prefix(ms, s, r)
            self._vacate_slot(s)
        r.migrations += 1
        self.stats["migrated_out"] += 1
        self.obs.event("migrate_out", rid=r.id, slot=s, tenant=r.tenant,
                       tokens=len(r.tokens), pages=len(pages))
        if r.tenant is not None:
            self._sched.stats(r.tenant)["migrated_out"] += 1
        key = r._resume_key
        return _dg.MigrationTicket(
            rid=r.id, model=r._ms.name,
            prompt=np.asarray(r.prompt, np.int32),
            tokens=tuple(int(t) for t in r.tokens),
            max_new_tokens=r.max_new_tokens,
            temperature=r.temperature, top_k=r.top_k, top_p=r.top_p,
            seed=r.seed, eos_id=r.eos_id, deadline_s=r.deadline_s,
            tenant=r.tenant, migrations=r.migrations,
            preemptions=r.preemptions,
            draft_proposed=r.draft_proposed,
            draft_accepted=r.draft_accepted,
            resume_key=(None if key is None else np.asarray(key)),
            page_tokens=self.prefill_chunk, pages=tuple(pages))

    def admit_ticket(self, ticket) -> Request:
        """Admit a migrated request — the receiver half of cross-host
        KV migration.  Page payloads are written into freshly allocated
        pages of THIS host's pool and adopted into the prefix tree
        (``PageIndex.adopt`` — the tree takes ownership; a chunk some
        local request already published keeps the tree's page and the
        incoming duplicate is freed), so the resume's re-prefill
        collapses to table mappings plus the final chunk, exactly like
        a local pressure-vacate resume.  The request re-enters at the
        FRONT of its class (a migration is a resume, not a fresh
        arrival) carrying tokens + PRNG chain, which is what makes the
        continuation bit-identical to an unmigrated run.  The crc /
        wire-format checks live one layer up in
        ``tpudp.serve.disagg`` — this method trusts its arrays but
        re-validates geometry (model, vocab, lengths, chunk size) and
        raises :class:`ValueError` on mismatch."""
        self._refuse_tickets("admit_ticket")
        if not self._accepting:
            raise EngineClosed(
                "Engine.drain()/close() was called; the engine no "
                "longer accepts work")
        if ticket.model not in self._mstates:
            raise ValueError(
                f"ticket for model {ticket.model!r} but this engine "
                f"serves {sorted(k or 'default' for k in self._mstates)}")
        tname = None
        if self._sched is not None:
            tname = self._sched.resolve(ticket.tenant)
        elif ticket.tenant is not None:
            raise ValueError(
                f"ticket carries tenant {ticket.tenant!r} but this "
                f"engine has no tenant classes configured")
        ms = self._mstates[ticket.model]
        prompt = np.asarray(ticket.prompt, np.int32).reshape(-1)
        vocab = ms.config.vocab_size
        if prompt.size == 0 or prompt.min() < 0 or prompt.max() >= vocab:
            raise ValueError(f"ticket prompt ids must be in [0, {vocab})")
        total = prompt.size + ticket.max_new_tokens + self.speculate_k
        if total > self.max_len:
            raise ValueError(
                f"ticket prompt ({prompt.size}) + max_new_tokens "
                f"({ticket.max_new_tokens}) exceeds the arena max_len "
                f"({self.max_len})")
        if ticket.pages and ticket.page_tokens != self.prefill_chunk:
            raise ValueError(
                f"ticket pages hold {ticket.page_tokens} tokens but this "
                f"engine's prefill_chunk is {self.prefill_chunk}")
        r = Request(self, self._next_id, prompt, ticket.max_new_tokens,
                    float(ticket.temperature), int(ticket.top_k),
                    float(ticket.top_p), ticket.seed, ticket.eos_id,
                    deadline_s=ticket.deadline_s, tenant=tname)
        self._next_id += 1
        r._ms = ms
        r.tokens = [int(t) for t in ticket.tokens]
        r.token_times = [r.submit_time] * len(r.tokens)
        r.migrations = ticket.migrations
        r.preemptions = ticket.preemptions
        r.draft_proposed = ticket.draft_proposed
        r.draft_accepted = ticket.draft_accepted
        r._fill = np.concatenate([prompt,
                                  np.asarray(r.tokens, np.int32)])
        r._nfill = 0
        if ticket.resume_key is not None:
            r._resume_key = np.asarray(ticket.resume_key)
        adopted = []
        if self._paged and ticket.pages:
            for payload in ticket.pages:
                page = self._alloc_page(ms, protect=-1)
                if page is None:
                    break
                ms.pool.write_page(page, payload)
                adopted.append(page)
            self._enqueued(None)  # the page writes run on the device
            if adopted:
                ms.index.adopt(r._fill, adopted)
                for page in adopted:
                    ms.pool.release(page)
        self.stats["migrated_in"] += 1
        self.stats["migrated_in_pages"] += len(adopted)
        self.obs.event("migrate_in", rid=ticket.rid, new_rid=r.id,
                       tenant=tname, tokens=len(r.tokens),
                       pages=len(adopted),
                       resumed=ticket.resume_key is not None)
        if tname is not None:
            self._sched.stats(tname)["migrated_in"] += 1
        if self._sched is not None:
            self._sched.requeue_front(r)
        else:
            self._queue.appendleft(r)
        return r

    def drain(self) -> None:
        """Graceful shutdown: stop admission (``submit()`` raises
        :class:`EngineClosed` from now on), finish every queued and
        in-flight request — across every tenant class — then close.
        Idempotent; safe after :meth:`close`."""
        self._accepting = False
        self.run_until_complete()
        self._closed = True

    def close(self) -> None:
        """Immediate shutdown: stop admission, retire every in-flight
        request as ``CANCELLED`` (emitted tokens stay on the handles)
        and every queued request as ``SHED`` — walking EVERY per-tenant
        queue on a tenant-aware engine, so no handle in any class is
        left pending.  Idempotent."""
        self._accepting = False
        if self._sched is not None:
            for r in self._sched.drain_all():
                self._finish(r, FinishReason.SHED)
        while self._queue:
            self._finish(self._queue.popleft(), FinishReason.SHED)
        for s, r in enumerate(self._slots):
            if r is not None:
                self._retire(s, FinishReason.CANCELLED)
        self._closed = True

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def accepting(self) -> bool:
        """False once :meth:`drain`/:meth:`close` has begun."""
        return self._accepting

    @property
    def drafter_quarantined(self) -> bool:
        """True once the drafter has been permanently quarantined
        (``drafter_quarantine_reason`` says why); the engine then runs
        the plain decode program, outputs unchanged."""
        return self._drafter_quarantined

    @property
    def quarantined(self) -> bool:
        """True once a canary mismatch has condemned this engine
        (``quarantine_reason`` says why).  A quarantined engine stops
        admission and stepping; its live requests wait to be migrated
        out (``DisaggCluster.evacuate``)."""
        return self._quarantined

    @property
    def slots_in_use(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def queue_depth(self) -> int:
        """Requests submitted but not yet admitted to a slot (summed
        across every tenant class on a tenant-aware engine)."""
        if self._sched is not None:
            return self._sched.depth()
        return len(self._queue)

    @property
    def acceptance_rate(self) -> float | None:
        """Engine-wide accepted / proposed draft tokens (None before the
        drafter's first proposal — including whenever speculation is
        off)."""
        if not self.stats["draft_tokens"]:
            return None
        return self.stats["draft_accepted"] / self.stats["draft_tokens"]

    def metrics(self) -> dict:
        """One structured snapshot of everything the engine knows about
        itself: the host stats counters, queue/slot occupancy,
        per-tenant counters, and the span rollup from the obs ring.
        With the recorder on, ``stats`` also holds the phase seconds
        (``OBS_PHASE_SECONDS``), ``step_self_s`` (``step``'s time in none
        of ``STEP_PHASES``), ``exposed_s`` and ``exposed_dispatch_s``
        (the ``exposed`` span's time, and its part inside the dispatches
        of ``DEVICE_SPANS``), and ``exposed`` is that time by phase
        (``EXPOSED_PHASES``; it sums to ``exposed_s``).  An open
        ``exposed`` span is charged up to this read, so two reads'
        difference is the exposed time between them.  The serve bench's
        metric sidecar and the Prometheus exposition
        (``tpudp.obs.prometheus_text``) both render this dict."""
        if self._moe_pending:  # a prefill chunk no decode followed yet
            self._count_moe(jax.device_get(self._moe_pending))
        if self._xtok >= 0:
            self.obs.piece(self._xtok, self._xphase)
        spans = self.obs.summary()
        stats = dict(self.stats)
        # (how often the first-token tail engaged, and the eager updates
        # of the key chain the fused and speculative replays still make)
        for key in ("first_tokens_in_prefill", "eager_key_updates"):
            stats.setdefault(key, 0)
        parts = spans.get("exposed", {}).get("parts", {})
        if self.obs.enabled:
            for key, names in OBS_PHASE_SECONDS.items():
                stats[key] = sum(spans[n]["total_s"] for n in names
                                 if n in spans)
            stats["step_self_s"] = stats["step_s"] - sum(
                spans[n]["total_s"] for n in STEP_PHASES if n in spans)
            stats["exposed_s"] = spans.get("exposed", {}).get("total_s", 0.0)
            stats["exposed_dispatch_s"] = sum(parts.get(n, 0.0)
                                              for n in DEVICE_SPANS)
        out = {
            "stats": stats,
            "queue_depth": self.queue_depth,
            "slots_in_use": self.slots_in_use,
            "num_slots": self.num_slots,
            "spans": spans,
            "obs_counters": dict(self.obs.counters),
            "flight_dumps": self.flight.dumps,
        }
        if self.obs.enabled:
            out["exposed"] = {p: parts.get(p, 0.0) for p in EXPOSED_PHASES}
        if self.canary_every_s is not None or self._quarantined:
            out["canary"] = {
                "runs": self.stats["canary_runs"],
                "errors": self.stats["canary_errors"],
                "skipped": self.stats["canary_skipped"],
                "mismatch": self.stats["canary_mismatch"],
                "ref_pinned": self._canary_ref is not None,
                "quarantined": self._quarantined,
                "quarantine_reason": self.quarantine_reason,
            }
        if self._sched is not None:
            out["tenants"] = {name: dict(c)
                              for name, c in self.tenant_stats.items()}
        if self._paged:
            pools: list = []
            for ms in self._mstates.values():
                if ms.pool not in pools:
                    pools.append(ms.pool)
            out["page_pools"] = [
                {"num_pages": p.num_pages, "used_pages": p.used_pages,
                 "free_pages": p.free_pages,
                 "page_bytes": p.page_bytes(),
                 # (a windowed family's second pool; 0 pages otherwise)
                 "window_pages": p.window_pages,
                 "window_used_pages": p.window_used_pages}
                for p in pools]
            # The backend dispatch record: what was asked for, what it
            # resolved to, and the per-program-family impl actually
            # traced — a kernel engine's einsum fall-backs (features
            # the kernels don't cover) show up here, not silently.
            out["paged_attn"] = {
                "requested": self.paged_attn_requested,
                "resolved": self.paged_attn,
                "dispatch": dict(self.paged_attn_dispatch),
                "fallbacks": sorted(
                    f for f, impl in self.paged_attn_dispatch.items()
                    if self.paged_attn == "kernel" and impl != "kernel"),
                # the span of a windowed family's sliding layers
                "window": getattr(self.config, "sliding_window", None),
            }
        if self._retention:
            from tpudp.models.brumby import state_bytes

            for key in RETENTION_STATS:
                stats.setdefault(key, 0)
            out["retention_state_bytes"] = state_bytes(self.config,
                                                       self.num_slots)
            out["retention"] = {"requested": self.paged_attn_requested,
                                "resolved": self.paged_attn,
                                "fold_tokens": self.config.fold_tokens}
        if self.stats.get("draft_tokens"):
            out["acceptance_rate"] = self.acceptance_rate
        return out

    # -- internals -----------------------------------------------------

    def _pop_next(self) -> Request | None:
        """Next request to admit: plain FIFO without tenancy; highest
        priority then weighted stride (``tpudp.serve.tenancy``) with."""
        if self._sched is not None:
            return self._sched.pop_next()
        return self._queue.popleft() if self._queue else None

    def _admit(self) -> None:
        for s in range(self.num_slots):
            if self._slots[s] is not None:
                continue
            r = self._pop_next()
            if r is None:
                break
            r._slot = s
            r._order = self._admitted
            self._admitted += 1
            if r.admit_time is None:  # a resume keeps the first grant
                r.admit_time = time.perf_counter()
            self._slots[s] = r
            self._len[s] = 0
            self._temps[s] = r.temperature
            self._topk[s] = r.top_k
            self._topp[s] = r.top_p
            # A step-failure requeue (or a preemption) resumes the
            # request's saved PRNG chain (already advanced once per
            # committed token), so the retried request's remaining
            # draws are bit-identical to an uninterrupted run.  The key
            # waits on the host for the prefill program that ends the
            # fill: admission does no device work.
            self._pending_keys[s] = (
                np.asarray(r._resume_key, np.uint32)
                if r._resume_key is not None else _seed_key(r.seed))
            self.stats["admitted"] += 1
            self.obs.event(
                "admit", rid=r.id, slot=s, tenant=r.tenant,
                model=r._ms.name,
                priority=(self._priority_of(r)
                          if self._sched is not None else None),
                resumed=r._resume_key is not None,
                fill=int(r._fill.size))
            if r.tenant is not None:
                # A resume (preemption or step-failure requeue —
                # _resume_key set at vacate) is not a fresh grant: it
                # counts as "readmitted" so the fairness oracle
                # (measured admitted shares vs configured weights)
                # isn't inflated for whichever class absorbs the
                # preemptions.
                self._sched.stats(r.tenant)[
                    "readmitted" if r._resume_key is not None
                    else "admitted"] += 1
            if self._paged:
                self._admit_prefix_paged(r._ms, s, r)
            elif r._ms.prefix_cache is not None:
                self._admit_prefix(r._ms, s, r)

    def _admit_prefix(self, ms: _ModelState, s: int, r: Request) -> None:
        """Cache-hit admission: copy the longest cached block-aligned
        prefix of the request's fill into its slot and skip that much
        prefill.  Never copies the WHOLE fill — the final chunk is
        always prefilled so its last-token logits feed the request's
        first sampling event, exactly generate()'s prefill-then-sample
        order (and exactly what a cold run computes, so outputs stay
        bit-identical).  Each block rides one call of the ONE compiled
        block-copy program; hit blocks are pinned for the copies so the
        eviction scan can never free a block mid-reuse."""
        from tpudp.serve import prefix_cache as _pc

        cache = ms.prefix_cache
        self.stats["prefix_lookups"] += 1
        blocks = cache.lookup(r._fill)
        n_copy = min(len(blocks), (r._fill.size - 1) // self.prefill_chunk)
        hit = n_copy * self.prefill_chunk
        self.stats["prefix_hit_tokens"] += hit
        if not n_copy:
            return
        cache.pin(blocks[:n_copy])
        try:
            for i in range(n_copy):
                ms.cache = self._device(
                    "prefix_in", _pc.copy_block_in, ms.cache,
                    cache.pool, np.int32(blocks[i]), np.int32(s),
                    np.int32(i * self.prefill_chunk))
        finally:
            cache.unpin(blocks[:n_copy])
        r._nfill = hit
        self._len[s] = hit

    # -- paged attention internals (Engine(kv_pages=N)) ----------------

    def _admit_prefix_paged(self, ms: _ModelState, s: int,
                            r: Request) -> None:
        """Paged cache-hit admission: MAP the longest cached
        block-aligned prefix of the fill into the slot's table — a
        refcount bump per page, zero KV copies (vs the dense path's
        per-block ``copy_block_in`` calls).  The hit is capped one
        chunk short of the fill exactly like the dense path, so the
        final chunk always re-prefills: that re-prefill writes a FRESH
        private page — the copy-on-write at the divergence block —
        while the mapped shared pages are never written (the slot's
        first write position is at or past the page after the hit).

        Skipped for a family with sliding-window layers: a hit would
        need the window layers' K/V of the last matched block, and
        those pages are freed behind the window."""
        if ms.wtable is not None:
            return
        self.stats["prefix_lookups"] += 1
        nodes = ms.index.lookup(r._fill)
        n_map = min(len(nodes), (r._fill.size - 1) // self.prefill_chunk)
        hit = n_map * self.prefill_chunk
        self.stats["prefix_hit_tokens"] += hit
        if not n_map:
            return
        for i, node in enumerate(nodes[:n_map]):
            ms.index.pin(node)
            ms.pool.share(node.block)
            ms.table[s, i] = node.block
            ms.slot_nodes[s][node.block] = node
        r._nfill = hit
        self._len[s] = hit

    def _publish_prefix_paged(self, ms: _ModelState, s: int,
                              r: Request) -> None:
        """Paged retirement/preemption publish: TRANSFER the slot's
        full chunk-prefilled pages to the radix tree (insert-or-ref;
        ``PageIndex.adopt`` takes a pool reference per newly adopted
        page) — pure host-side metadata, no device call, so unlike the
        dense copy-out there is nothing to fault or flush.  Only pages
        the slot itself prefilled transfer as NEW nodes; pages mapped
        from an earlier hit are already the tree's (adopt just touches
        them), and a chunk another request published meanwhile keeps
        the tree's page (the slot's identical private duplicate drops
        at vacate).  Skipped, like the lookup, for a family with
        sliding-window layers."""
        if ms.wtable is not None:
            return
        n_blocks = min(r._nfill, r._fill.size) // self.prefill_chunk
        if not n_blocks:
            return
        pages = [int(ms.table[s, i]) for i in range(n_blocks)]
        if any(p < 0 for p in pages):  # never expected: prefill allocates
            return
        self.stats["prefix_published_blocks"] += ms.index.adopt(
            r._fill, pages)

    def _release_slot_pages(self, ms: _ModelState, s: int) -> None:
        """Drop every page reference slot ``s`` holds (the vacate /
        retire half of the refcount discipline): shared mappings unpin
        their tree node, every table entry releases its pool
        reference, and the table row clears.  Idempotent after a
        containment flush (the table is already -1)."""
        if not self._paged:
            return
        for pidx in range(self._max_pages):
            page = int(ms.table[s, pidx])
            if page < 0:
                continue
            node = ms.slot_nodes[s].pop(page, None)
            if node is not None:
                ms.index.unpin(node)
            ms.pool.release(page)
        ms.table[s] = -1
        ms.slot_nodes[s] = {}
        if ms.wtable is not None:
            for page in ms.wtable[s][ms.wtable[s] >= 0]:
                ms.pool.release_window(int(page))
            ms.wtable[s] = -1

    def _alloc_page(self, ms: _ModelState, protect: int) -> int | None:
        """One exclusive page for slot ``protect``, evicting cold tree
        leaves and — when the whole pool is live — VACATING the
        most-recently-admitted co-resident slot (lowest priority first
        under tenancy; the least sunk cost, so the oldest in-flight
        request always progresses) through the bit-exact resume path.
        Returns None only when slot ``protect`` alone cannot be
        satisfied, which the admission-time max_len<->pool validation
        rules out."""
        while True:
            page = ms.pool.alloc()
            if page is not None:
                return page
            if self._evict_index_page(ms.pool):
                continue
            victim = self._page_pressure_victim(ms.pool, protect)
            if victim is None:
                return None
            self._vacate_for_pages(victim)

    def _evict_index_page(self, pool) -> bool:
        """Evict the globally least-recently-touched unreferenced leaf
        across every index sharing ``pool`` (deterministic: the shared
        logical clock is per-index, ties broken by registration
        order)."""
        best = None
        for ms in self._mstates.values():
            if ms.pool is not pool or ms.index is None:
                continue
            for node in ms.index._by_block.values():
                if node.refs:
                    continue
                if best is None or node.stamp < best[1].stamp:
                    best = (ms.index, node)
        if best is None:
            return False
        index, node = best
        index.evict_node(node)
        return True

    def _page_pressure_victim(self, pool, protect: int) -> int | None:
        """The slot to vacate under page pressure: among slots whose
        model draws from ``pool`` (excluding ``protect``), the lowest
        priority, then the most recently admitted — preemption's
        least-sunk-cost rule, which guarantees the oldest request runs
        to completion and the engine always makes progress."""
        victims = [s for s, r in enumerate(self._slots)
                   if r is not None and s != protect
                   and r._ms.pool is pool]
        if not victims:
            return None
        if self._sched is not None:
            return max(victims,
                       key=lambda s: (-self._priority_of(self._slots[s]),
                                      self._slots[s]._order))
        return max(victims, key=lambda s: self._slots[s]._order)

    def _vacate_for_pages(self, s: int) -> None:
        """Evict slot ``s`` to free its pages: publish its prefilled
        prefix first (a host-side ownership transfer — the pages stay
        resident as evictable cache, so the resume usually collapses
        to table writes), then vacate through the shared carry-over
        path and requeue at the FRONT of its class, exactly like
        priority preemption — the request resumes bit-identically and
        the vacate is never user-visible."""
        r = self._slots[s]
        if self._accepting:
            self._publish_prefix(r._ms, s, r)
        self._vacate_slot(s)
        # Page pressure gets its OWN accounting at every level (it is
        # not priority preemption — the handle's ``preemptions`` and
        # stats["preempted"] keep meaning "lost the slot to
        # higher-priority work" on paged engines too).
        self.stats["page_pressure_vacates"] += 1
        self.obs.event("page_vacate", rid=r.id, slot=s, tenant=r.tenant,
                       tokens=len(r.tokens))
        if r.tenant is not None:
            self._sched.stats(r.tenant)["page_pressure_vacates"] += 1
        if self._sched is not None:
            self._sched.requeue_front(r)
        else:
            self._queue.appendleft(r)

    def _ensure_pages(self, ms: _ModelState, s: int, upto: int) -> bool:
        """Allocate slot ``s``'s table entries covering positions
        ``[0, upto)`` (lazily — a paged slot holds pages only as deep
        as it has actually written, the overcommit that multiplies
        capacity).  Returns False iff the slot itself was lost, which
        the pool-size validation precludes."""
        need = min((upto + self.prefill_chunk - 1) // self.prefill_chunk,
                   self._max_pages)
        for pidx in range(need):
            if ms.table[s, pidx] >= 0:
                continue
            page = self._alloc_page(ms, protect=s)
            if page is None:
                # Unreachable by construction (pool >= one max_len
                # request per geometry group, and every other holder is
                # evictable/vacatable) — but an unbacked table entry
                # must fail LOUDLY, not silently route this slot's
                # writes to the scratch page.
                self._retire(s, FinishReason.ERROR,
                             error=RuntimeError(
                                 f"page pool exhausted backing slot {s} "
                                 f"to position {upto} — kv_pages too "
                                 f"small for the admitted workload"))
                return False
            ms.table[s, pidx] = page
        if ms.wtable is not None:
            self._roll_window_pages(ms, s, upto, need)
        return True

    @staticmethod
    def _dead_window_pages(start: int, window: int, page_tokens: int) -> int:
        """How many leading logical pages no query at ``start`` or later
        can attend on a sliding layer: those whose last token ``pos -
        window`` has passed (a query at ``q`` sees keys ``> q -
        window``)."""
        return max(start - window + 1, 0) // page_tokens

    def _roll_window_pages(self, ms: _ModelState, s: int, upto: int,
                           need: int) -> None:
        """The window layers' half of :meth:`_ensure_pages` for the
        program about to run slot ``s`` from position ``self._len[s]``
        to ``upto``: release the window-table entries the window has
        passed (their pages go back to the window pool; the kernels skip
        a ``-1`` entry and the mask hides it anyway), back the entries
        the program writes, and count what the window layers' calls of
        this run must read and multiply (host arithmetic on lengths the
        scheduler holds: no device value is touched)."""
        chunk, window = self.prefill_chunk, ms.config.sliding_window
        start = int(self._len[s])
        row = ms.wtable[s]
        j = self._dead_window_pages(start, window, chunk) - 1
        while j >= 0 and row[j] >= 0:  # one page a crossing, as a rule
            ms.pool.release_window(int(row[j]))
            row[j] = -1
            self.stats["window_pages_freed"] += 1
            j -= 1
        for pidx in range(start // chunk, need):
            if row[pidx] < 0:
                row[pidx] = ms.pool.alloc_window()
        layers = ms.pool.pages.window.k.shape[0]  # the sliding layers
        first = max(start - window + 1, 0)  # oldest key the run attends
        short = min(upto, window - 1)  # queries below it see q + 1 keys
        pairs = max(upto - max(start, window - 1), 0) * window
        if short > start:
            pairs += (short * (short + 1) - start * (start + 1)) // 2
        self.stats["window_rows_read"] += layers * (upto - first)
        self.stats["window_pairs"] += layers * pairs

    def _ensure_decode_pages(self, ms: _ModelState, active,
                             fuse: bool):
        """Preallocate every active slot's pages for the step about to
        dispatch (one token for plain decode, the k+1 verify window,
        or the whole fused window) — page-pressure vacates happen HERE,
        on the host, before the device program runs, so the program
        itself only ever sees fully-backed tables.  Returns the active
        mask recomputed after any vacates."""
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            if r is None:
                continue
            # MIRROR THE DISPATCH ORDER below (speculation wins over
            # fusing): a live drafter runs the k+1 verify window even
            # on iterations where ``fuse`` is True, and backing only
            # the fused window's positions would route the window
            # tail's KV writes to the scratch page — silent corruption.
            if self.speculate_k and not self._drafter_quarantined:
                if fuse and self._spec_fusable:
                    # The fused spec window advances up to
                    # decode_fuse x (k+1) committed positions, and its
                    # LAST verify window's writes extend k speculative
                    # positions past the final committed length.
                    ahead = min(r.max_new_tokens - len(r.tokens),
                                self.decode_fuse
                                * (self.speculate_k + 1)) \
                        + self.speculate_k
                else:
                    ahead = self.speculate_k + 1
            elif fuse:
                ahead = min(r.max_new_tokens - len(r.tokens),
                            self.decode_fuse)
            else:
                ahead = 1
            self._ensure_pages(ms, s, int(self._len[s]) + ahead)
        return np.array(
            [r is not None and r._nfill == r._fill.size
             and r._ms is ms for r in self._slots])

    def check_paged(self) -> None:
        """Table<->pool<->tree consistency for the whole paged engine
        (the paged extension of ``PrefixCache.check``; tests call it
        after every mutation storm): every pool's internal invariants,
        every index's tree shape, and the cross-check that each
        allocated page's refcount equals its actual holders — one per
        owning tree node plus one per table entry mapping it."""
        if not self._paged:
            return
        pools = []
        for ms in self._mstates.values():
            if ms.pool not in pools:
                pools.append(ms.pool)
            ms.index.check()
            for s in range(self.num_slots):
                for page, node in ms.slot_nodes[s].items():
                    if ms.index._by_block.get(node.block) is not node:
                        raise RuntimeError(
                            f"slot {s} pins a node the index no longer "
                            f"holds (page {page})")
                    if page not in ms.table[s]:
                        raise RuntimeError(
                            f"slot {s} pins page {page} absent from its "
                            f"table row")
        for ms in self._mstates.values():
            if ms.wtable is not None:
                ms.pool.check_window(ms.wtable[ms.wtable >= 0].tolist())
        for pool in pools:
            expected: dict[int, int] = {}
            for ms in self._mstates.values():
                if ms.pool is not pool:
                    continue
                for page in ms.index.tree_refs():
                    expected[page] = expected.get(page, 0) + 1
                for s in range(self.num_slots):
                    for pidx in range(self._max_pages):
                        page = int(ms.table[s, pidx])
                        if page >= 0:
                            expected[page] = expected.get(page, 0) + 1
            pool.check(expected)

    def _publish_prefix(self, ms: _ModelState, s: int,
                        r: Request) -> None:
        """Retirement-time publish: insert the slot's block-aligned
        PREFILLED prefix into the pool (insert-or-ref) and copy the KV
        of any newly allocated blocks out of the arena.  Only
        chunk-prefilled positions qualify (``r._nfill``, never
        decode/verify-produced KV): every published block's contents
        are then the deterministic chunked-prefill function of its
        token prefix, which is what makes a later hit bit-identical to
        recomputation.  Publishing is an optimization, never
        load-bearing: any failure (including an injected device fault)
        flushes the cache — with a fresh pool buffer, since the failed
        call had the pool donated — and the retirement proceeds.  The
        ARENA is read-only in the copy-out program, so a publish
        failure never forces an arena rebuild.  In paged mode the
        publish is an ownership transfer instead
        (:meth:`_publish_prefix_paged`) — no device call at all."""
        if self._paged:
            self._publish_prefix_paged(ms, s, r)
            return
        from tpudp.serve import prefix_cache as _pc

        from tpudp.utils.watchdog import StepHangError

        cache = ms.prefix_cache
        n_blocks = min(r._nfill, r._fill.size) // self.prefill_chunk
        if not n_blocks:
            return
        try:
            new = cache.publish(r._fill, n_blocks)
            for block, start in new:
                cache.pool = self._device(
                    "prefix_out", _pc.copy_block_out, ms.cache,
                    cache.pool, np.int32(block), np.int32(s),
                    np.int32(start))
            self.stats["prefix_published_blocks"] += len(new)
        except StepHangError:
            # A pending watchdog hang surfaced in the publish guard: a
            # DEVICE-HEALTH signal, not a cache fault — don't charge it
            # to the cache.  Un-publish the blocks whose copies never
            # ran (flush) and re-raise so step()'s containment handles
            # it (acknowledge + arena rebuild); raised from a
            # user-called cancel()/close() the hang flag stays set, so
            # the next step's first device call re-raises and contains.
            cache.flush(reallocate=True)
            self.stats["prefix_flushes"] += 1
            raise
        except Exception as exc:  # noqa: BLE001 — publish is best-effort
            cache.flush(reallocate=True)
            self.stats["prefix_flushes"] += 1
            self.stats["prefix_publish_failures"] += 1
            self.last_step_error = exc

    def _finish(self, r: Request, reason: FinishReason,
                error: BaseException | None = None) -> None:
        r.done = True
        r.finish_reason = reason
        r.error = error
        self.stats[_FINISH_COUNTER[reason]] += 1
        ttft, queued, prefill_wait = r.ttft_split() or (None, None, None)
        self.obs.event("finish", rid=r.id, reason=reason.value,
                       tenant=r.tenant, tokens=len(r.tokens),
                       preemptions=r.preemptions, ttft_s=ttft,
                       ttft_queue_s=queued,
                       ttft_prefill_wait_s=prefill_wait)
        if r.tenant is not None:
            self._sched.stats(r.tenant)[_FINISH_COUNTER[reason]] += 1

    def _deadline_passed(self, r: Request, now: float) -> bool:
        waited = now - r.submit_time
        if r.deadline_s is not None and waited > r.deadline_s:
            return True
        return (r.ttft_deadline_s is not None and not r.tokens
                and waited > r.ttft_deadline_s)

    def _expire_deadlines(self) -> None:
        """Retire every queued/in-flight request whose wall-clock budget
        has expired (``FinishReason.DEADLINE``) — BEFORE admission, so a
        dead-on-arrival queued request never wastes a slot or a prefill
        chunk.  Emitted tokens stay on the handle; freed slots serve the
        next queued request this same step."""
        now = time.perf_counter()
        queued = (self._sched.queued() if self._sched is not None
                  else self._queue)
        for r in [r for r in queued if self._deadline_passed(r, now)]:
            if self._sched is not None:
                self._sched.remove(r)
            else:
                self._queue.remove(r)
            self._finish(r, FinishReason.DEADLINE)
        for s, r in enumerate(self._slots):
            if r is not None and self._deadline_passed(r, now):
                self._retire(s, FinishReason.DEADLINE)

    def _guard(self, timeout_s: float | None, name: str = "step"):
        """Scoped watchdog deadline (no-op without a watchdog);
        ``name`` labels the armed region in hang reports."""
        if self._watchdog is None:
            return contextlib.nullcontext()
        return self._watchdog.step(timeout_s, name=name)

    def _device(self, kind: str, fn, *args, guard_timeout_s=None,
                **kwargs):
        """Run one jitted step program behind the robustness seams: the
        fault-injection hook (``step_fault_hook(kind, index)``, raising
        to simulate a step failure) and the optional scoped watchdog
        deadline, so a wedged device call is detected from OUTSIDE the
        blocked call (``kill=True`` exits for the scheduler to restart;
        ``kill=False`` raises at the next call and is contained like any
        other step failure).  ``guard_timeout_s`` overrides the engine's
        flat per-call ``step_timeout_s`` for calls whose healthy
        duration is a known multiple of a single step (the fused window
        runs up to ``decode_fuse`` decode steps in one call — judging it
        by one step's budget would misdiagnose a healthy window as a
        wedge).  Remaining ``kwargs`` pass through to ``fn`` (the fused
        decode step's static ``n_steps``/``stream``).

        Every call rides an allocation-free obs span named ``kind`` —
        the one instrumentation point covering the whole device-call
        taxonomy (``DEVICE_SPANS``), and the region name the watchdog
        reports on a hang.  The call is asynchronous, so the span times
        the DISPATCH; the device's time shows where the host blocks on
        the result (the ``fetch`` / ``first_token_wait`` spans).  Outside
        the commit loop the call starts with the exposed clock's
        readiness check, and an open ``exposed`` span closes once the
        call returns: the program is enqueued.  The dispatch's share
        of the span is charged to ``kind``."""
        idx = self._device_calls
        self._device_calls += 1
        tok = self.obs.begin(kind)
        if self._xtok >= 0:
            self.obs.piece(self._xtok, self._xphase)
        elif self._xphase != "commit":  # no check inside the commit loop
            self._poll()
        try:
            with self._guard(guard_timeout_s
                             if guard_timeout_s is not None
                             else self._step_timeout_s, name=kind):
                if self.step_fault_hook is not None:
                    self.step_fault_hook(kind, idx)
                out = fn(*args, **kwargs)
                if self.obs.enabled:
                    self._newest = _readiness_probe(out)
            return out
        finally:
            if self._xtok >= 0:
                self.obs.end(self._xtok, kind)
                self._xtok = NO_SPAN
            self.obs.end(tok)

    def _contain_step_failure(self, exc: BaseException) -> None:
        """An exception escaped a device step: rebuild the arena (the
        failed call may have consumed the donated KV cache, so every
        slot's cached state is suspect) and requeue each in-flight
        request ONCE — with its emitted tokens and PRNG chain carried
        over, re-prefilling ``prompt + tokens`` continues the request
        bit-identically.  A request failing a second time retires with
        ``FinishReason.ERROR``.  Queued requests are untouched; the
        engine keeps serving."""
        self.stats["step_failures"] += 1
        self.last_step_error = exc
        self._enqueued(None)  # the rebuild below enqueues device work
        self.obs.event("containment", error=type(exc).__name__,
                       detail=str(exc)[:200])
        # Black box BEFORE the rebuild mutates state: the ring's tail is
        # the timeline that led here (the failing device call's span is
        # the most recent), which is what the post-mortem reads.
        self.flight.dump("step_failure", extra={
            "error": repr(exc)[:500],
            "slots_in_use": self.slots_in_use,
            "queue_depth": self.queue_depth,
        })
        if self._watchdog is not None:
            self._watchdog.acknowledge()  # handled; next scope may proceed
        self._moe_pending = []  # of the failed call, maybe: best effort
        rebuilt_pools: list = []
        for ms in self._mstates.values():
            if self._paged:
                # Paged rebuild: the failed call may have had the
                # (donated) shared pool in flight, so every page's
                # validity is unknown — reallocate each pool ONCE
                # (models share them), clear every table and radix
                # index, and let the requeued survivors re-prefill
                # into fresh pages (prefill is deterministic, so the
                # retry is bit-identical — the same oracle as the
                # dense arena rebuild).
                if ms.pool not in rebuilt_pools:
                    ms.pool.reallocate()
                    rebuilt_pools.append(ms.pool)
                ms.index.reset()
                ms.table[:] = -1
                if ms.wtable is not None:
                    ms.wtable[:] = -1
                ms.slot_nodes = [dict() for _ in range(self.num_slots)]
                self.stats["prefix_flushes"] += 1
            else:
                ms.cache = slot_arena(ms.config, self.num_slots,
                                      self.max_len)
            # A rebuilt arena invalidates the published blocks
            # wholesale: the failed call may have been a block copy
            # with either buffer donated, and after an arbitrary device
            # fault conservatism wins over proving which buffers
            # survived — the cache re-warms from the traffic,
            # correctness never depended on it.
            if ms.prefix_cache is not None:
                ms.prefix_cache.flush(reallocate=True)
                self.stats["prefix_flushes"] += 1
        survivors: list[Request] = []
        for s in sorted(
                (s for s, r in enumerate(self._slots) if r is not None),
                key=lambda s: self._slots[s]._order):
            r = self._vacate_slot(s)
            if r._requeued:
                self._finish(r, FinishReason.ERROR, error=exc)
            else:
                r._requeued = True
                survivors.append(r)
                self.stats["requeued"] += 1
        # Requeued work goes to the FRONT in admission order: it was
        # already accepted and partially served, and queue_limit never
        # applies to it (shedding admitted work would turn one transient
        # fault into data loss).
        if self._sched is not None:
            for r in reversed(survivors):
                self._sched.requeue_front(r)
        else:
            self._queue.extendleft(reversed(survivors))

    def _next_prefill_slot(self) -> int | None:
        # Tenancy orders prefill by priority first (a just-admitted or
        # just-resumed high-tier request must not wait behind a low-tier
        # prompt's remaining chunks — TTFT is the tier's SLO), then by
        # admission order; without tenants this is the original pure
        # FIFO.
        if self._sched is not None:
            pending = [(-self._priority_of(r), r._order, s)
                       for s, r in enumerate(self._slots)
                       if r is not None and r._nfill < r._fill.size]
            return min(pending)[2] if pending else None
        pending = [(r._order, s) for s, r in enumerate(self._slots)
                   if r is not None and r._nfill < r._fill.size]
        return min(pending)[1] if pending else None

    def _run_prefill_chunk(self, s: int, emitted) -> None:
        span = self._enter("chunk")
        r = self._slots[s]
        if r.first_chunk_time is None:
            r.first_chunk_time = time.perf_counter()
        ms = r._ms
        fill = r._fill
        start = r._nfill
        end = min(start + self.prefill_chunk, fill.size)
        buf = np.zeros((1, self.prefill_chunk), np.int32)
        buf[0, :end - start] = fill[start:end]
        # Back the chunk's page first (page-pressure vacates can only
        # hit OTHER slots — this one is protected), then run the paged
        # prefill against the slot's table row.
        if self._paged and not self._ensure_pages(ms, s, end):
            self._leave(span)
            return  # slot retired (defensive: pool exhausted)
        if self._paged:
            # (a COPY of the window row: the host frees its entries while
            # this program may still be in flight, and a backend may read
            # a numpy argument in place; the global row only ever grows)
            row = ms.table[s] if ms.wtable is None \
                else (ms.table[s], ms.wtable[s].copy())
        final = end == fill.size
        # A requeued/preempted request can have been vacated AFTER its
        # final commit — a hang surfacing in its retirement publish
        # interrupts _retire between the commit and _finish — so its
        # terminal condition already holds.  Its last chunk then samples
        # nothing: the resume must reproduce the retirement the
        # interrupted step was performing, not extend the stream.
        eos = (final and r.eos_id is not None and bool(r.tokens)
               and r.tokens[-1] == r.eos_id)
        spent = eos or (final and len(r.tokens) >= r.max_new_tokens)
        # Fill fully cached: the chunk's last-token logits are the
        # request's next sampling event (for a fresh request, the FIRST
        # — exactly generate()'s prefill-then-sample order; for a
        # requeued one, event ``len(tokens) + 1`` under the resumed key
        # chain), sampled inside the program with the slot's pending key.
        meta = _prefill_meta(
            start, end - start - 1, s,
            self._pending_keys[s] if final and not spent else None,
            self._temps[s], self._topk[s], self._topp[s])
        self._leave(span)
        if self._paged:
            tok, self._keys, ms.pool.pages, *routed = self._device(
                "prefill", ms.prefill_paged, ms.pool.pages, row,
                buf, meta, self._keys)
            # an expert family's counts of this run: fetched with the
            # next token fetch, never on their own
            self._moe_pending += routed
        else:
            tok, self._keys, ms.cache = self._device(
                "prefill", ms.prefill_step, ms.cache, buf, meta,
                self._keys)
            if self._retention:
                self.stats["retention_prefill_tokens"] += end - start
        r._nfill = end
        self._len[s] = end
        self.stats["prefill_chunks"] += 1
        if spent:
            self._retire(s, FinishReason.EOS if eos
                         else FinishReason.COMPLETE)
        elif final:
            # The sync that keeps this step's decode from being queued
            # behind the prompt's last chunk: the host waits here for
            # the chunk, and the token sampled at its end, to finish.
            span = self._enter("first_token_wait")
            # tpudp: lint-ok(host-sync): the FIRST-token commit — one
            # fetch per completed prefill, not per decoded token; the
            # decoded tokens ride decode_fuse windows
            # (_run_decode_fused) when fusing is on.
            tok = int(tok)
            self._leave(span)
            self._pending_keys[s] = None  # the chain is on the device now
            self.stats["first_tokens_in_prefill"] += 1
            span = self._enter("commit")
            self._poll()
            self._commit(s, tok, emitted)
            self._leave(span)

    def _count_moe(self, fetched) -> None:
        """Add the expert layers' fetched per-run counts
        (``moe.SERVE_MOE_COUNTERS``) to the host stats; the pending
        device values they were fetched from are done with."""
        from tpudp.models.moe import SERVE_MOE_COUNTERS

        self._moe_pending = []
        for vals in fetched:
            for key, val in zip(SERVE_MOE_COUNTERS, vals):
                self.stats[key] += int(val)

    def _count_retention(self, active) -> None:
        """A power-retention decode run's work, on the host (no fetch):
        slot-layers whose state it read, tail rows it attended (the fed
        token's included), slot-layers whose tail it folded."""
        layers, c = self.config.num_layers, self.config.fold_tokens
        at = self._len[active] % c
        self.stats["retention_state_reads"] += layers * int(active.sum())
        self.stats["retention_tail_rows"] += layers * int((at + 1).sum())
        self.stats["retention_folds"] += layers * int((at == c - 1).sum())

    def _count_decode_visits(self, ms: _ModelState, table, lengths,
                             active) -> None:
        """A decode run's paged-decode kernel work, on the host (no
        fetch), from the arguments of its dispatch, into the stats:
        ``decode_page_visits``, the grid steps of each layer kind's calls
        (``ops.paged_attention.decode_reach``'s entries, and one visit
        for a slot that reaches none) times the layers of that kind; and
        ``decode_rows_read``, ``pos + 1`` over the decoding slots (the
        cached tokens a full-attention layer reads).  Nothing unless the
        decode program runs the kernel."""
        if self.paged_attn_dispatch.get("decode_paged") != "kernel" or (
                ms.wtable is None and page_layout(ms.config) != "heads"):
            return
        from tpudp.ops.paged_attention import decode_reach

        if ms.wtable is None:
            kinds = ((table, None, ms.config.num_layers),)
        else:
            pages = ms.pool.pages
            kinds = ((table[0], None, pages.full.k.shape[0]),
                     (table[1], ms.config.sliding_window,
                      pages.window.k.shape[0]))
        for tbl, window, layers in kinds:
            reach = decode_reach(tbl, lengths, ms.pool.page_tokens, window,
                                 active, xp=np)
            visits = int(reach.sum()) + int((~reach.any(axis=1)).sum())
            self.stats["decode_page_visits"] += layers * visits
        self.stats["decode_rows_read"] += int((lengths[active] + 1).sum())

    def _run_decode(self, ms: _ModelState, active, emitted) -> None:
        table = ms.table
        if ms.wtable is not None:
            span = self._enter("batch")
            table = (table, ms.wtable.copy())  # as the prefill's row
            self.stats["window_pages_live"] += ms.pool.window_used_pages
            self._leave(span)
        if self._paged:
            ms.pool.pages, toks, self._keys, *routed = self._device(
                "decode", ms.decode_paged,
                ms.pool.pages, table, self._last, self._len, active,
                self._temps, self._topk, self._topp, self._keys)
            self._moe_pending += routed
            span = self._enter("batch")
            self._count_decode_visits(ms, table, self._len, active)
            self._leave(span)
        else:
            ms.cache, toks, self._keys = self._device(
                "decode", ms.decode_step,
                ms.cache, self._last, self._len, active, self._temps,
                self._topk, self._topp, self._keys)
            if self._retention:
                self._count_retention(active)
        span = self._enter("fetch")
        # tpudp: lint-ok(host-sync): the single-step path's per-token
        # fetch — Engine(decode_fuse=N) amortizes it to one fetch per
        # fused lax.while_loop window (_run_decode_fused); this path
        # remains for the host-intervention steps (admission, prefill,
        # speculation, preemption) the fused window falls back to.  An
        # expert family's counters (this run's and the prefill chunks'
        # since the last fetch) ride the same transfer.
        toks, *moe = jax.device_get((toks, *self._moe_pending))
        self._leave(span)
        span = self._enter("commit")
        self._poll()
        if moe:
            self._count_moe(moe)
        self.stats["decode_steps"] += 1
        self.stats["active_slot_steps"] += int(active.sum())
        for s in np.nonzero(active)[0]:
            self._len[s] += 1  # the fed token's KV landed this step
            self._commit(int(s), int(toks[s]), emitted)
        self._leave(span)

    def _run_decode_fused(self, ms: _ModelState, active, emitted) -> None:
        """One fused window: up to ``decode_fuse`` decode iterations in
        a single device program (``fused_decode_step``), then ONE fetch
        and a host-side replay of the window's commits through the same
        ``_commit`` path the single-step engine uses — EOS/budget
        retirement reasons, per-token timestamps, prefix-cache
        publishes, and stats all flow through unchanged.  The device
        already stopped each row at its EOS/budget, so the replay's own
        retirement checks agree with the loop predicate by
        construction; ``self._len``/``self._last`` advance per commit
        (mirroring ``_run_verify``) and ``self._keys`` takes the loop's
        carry, leaving the host state bit-identical to having run
        ``n_emit[s]`` single steps — which is why any later fall-back
        to the single-step path resumes exactly."""
        span = self._enter("batch")
        budgets = np.zeros(self.num_slots, np.int32)
        eos = np.full(self.num_slots, -1, np.int32)
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            budgets[s] = r.max_new_tokens - len(r.tokens)
            if r.eos_id is not None:
                eos[s] = r.eos_id
        # The window legitimately runs up to decode_fuse decode steps in
        # one device call, so its watchdog budget scales with the
        # window — a step_timeout_s tuned for single-step decode must
        # not misdiagnose a healthy window as a wedged call.
        budget_s = (self._step_timeout_s * self.decode_fuse
                    if self._step_timeout_s is not None else None)
        self._leave(span)
        if self._paged:
            ms.pool.pages, out, n_emit, keys, iters = self._device(
                "fused_decode", ms.fused_paged,
                ms.pool.pages, ms.table, self._last, self._len, active,
                self._temps, self._topk, self._topp, self._keys,
                budgets, eos, np.int32(self._ring_id),
                guard_timeout_s=budget_s,
                n_steps=self.decode_fuse, stream=self._fuse_stream)
        else:
            ms.cache, out, n_emit, keys, iters = self._device(
                "fused_decode", ms.fused_step,
                ms.cache, self._last, self._len, active, self._temps,
                self._topk, self._topp, self._keys, budgets, eos,
                np.int32(self._ring_id),
                guard_timeout_s=budget_s,
                n_steps=self.decode_fuse, stream=self._fuse_stream)
        span = self._enter("fetch")
        # tpudp: lint-ok(host-sync): the per-WINDOW fetch — one round
        # trip per up-to-decode_fuse-token window, the amortized
        # replacement for the single-step path's per-token fetch.
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)  # tpudp: lint-ok(host-sync): same fetch
        self._leave(span)
        span = self._enter("commit")
        self._poll()
        self.stats["fused_windows"] += 1
        self.stats["fused_steps"] += int(iters)  # tpudp: lint-ok(host-sync): same fetch
        # Each loop iteration is one batched decode over the arena, and
        # a row commits exactly once per iteration it was running — so
        # n_emit.sum() IS the window's active-slot-step count and
        # occupancy consumers keep working with fusing on
        # (active / (decode_steps + fused_steps) x num_slots).
        self.stats["active_slot_steps"] += int(n_emit.sum())
        # the window's iteration i ran the rows that emitted more than i
        for i in range(int(iters)):  # tpudp: lint-ok(host-sync): same fetch
            self._count_decode_visits(ms, ms.table, self._len + i,
                                      n_emit > i)
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            # Take the window-final key carry PER SLOT, just before that
            # slot's replay: the replay can raise only at a slot's OWN
            # retirement publish (after its last commit), so if
            # containment interrupts mid-replay every vacated slot's
            # chain still matches its committed tokens — already-replayed
            # slots carry the window chain, not-yet-replayed slots keep
            # their pre-window chain with zero window tokens.  A single
            # up-front `self._keys = keys` would skip an interrupted
            # later slot's chain ahead of its stream.
            self._keys = self._keys.at[s].set(keys[s])
            self._enqueued(self._keys)
            self.stats["eager_key_updates"] += 1
            for j in range(int(n_emit[s])):
                if self._slots[s] is not r:
                    break  # retired (EOS / budget / cancel) mid-replay
                self._len[s] += 1
                self._commit(int(s), int(out[s, j]), emitted)
        self._leave(span)

    def _quarantine_drafter(self, reason: str, r: Request | None = None,
                            proposed: int = 0) -> None:
        """Permanently disable a misbehaving drafter.  Drafts were only
        ever hints, so outputs are unchanged — the engine simply runs
        the plain decode program from the next step on (both programs
        are already warm; no recompile).  ``proposed`` tokens that came
        back before the fault are charged as proposed-and-rejected, so
        acceptance accounting stays truthful."""
        self._drafter_quarantined = True
        self.drafter_quarantine_reason = reason
        self.obs.event("drafter_quarantine", reason=reason[:200])
        self.stats["drafter_quarantined"] = 1
        if r is not None and proposed:
            r.draft_proposed += proposed
            self.stats["draft_tokens"] += proposed

    # -- serving canary (silent-corruption defense) --------------------

    def _maybe_canary(self) -> None:
        """Drive the canary lifecycle, one call per scheduler iteration
        (``canary_every_s`` set).  Harvest a finished canary first:
        compare its token stream against the pinned reference — the
        first clean completion pins it; greedy decode of a fixed prompt
        is deterministic, so ANY later byte difference is evidence of
        silent corruption and quarantines the engine.  Then launch the
        next canary once the cadence interval has elapsed.  Loud canary
        failures (deadline, containment ERROR) count as
        ``canary_errors``, not corruption — those fault classes already
        have their own detectors."""
        if self.canary_every_s is None or not self._accepting:
            return
        r = self._canary_active
        if r is not None:
            if r.finish_reason is None:
                return  # still decoding; one canary in flight at a time
            self._canary_active = None
            if r.finish_reason is not FinishReason.COMPLETE:
                self.stats["canary_errors"] += 1
            else:
                got = tuple(int(t) for t in r.tokens)
                self.stats["canary_runs"] += 1
                if self._canary_ref is None:
                    self._canary_ref = got
                    self.obs.event("canary_pin", tokens=len(got))
                elif got != self._canary_ref:
                    self._quarantine_canary(self._canary_ref, got)
                    return
        if time.monotonic() - self._canary_last < self.canary_every_s:
            return
        try:
            req = self.submit(self._canary_prompt, self._canary_new_tokens,
                              temperature=0.0, seed=0)
        except (QueueFull, ValueError):
            # Saturated (or tenancy without a default class): skip this
            # cadence tick rather than shed real traffic for a probe.
            self.stats["canary_skipped"] += 1
            self._canary_last = time.monotonic()
            return
        req._canary = True
        self._canary_active = req
        self._canary_last = time.monotonic()

    def _quarantine_canary(self, expected: tuple, got: tuple) -> None:
        """Canary mismatch == silent corruption somewhere under this
        engine: stop admission AND stop stepping, leaving live requests
        in place for ``DisaggCluster.evacuate`` to migrate out
        bit-exactly (the prefix-replay ticket protocol).  Unlike
        drafter quarantine (drafts are hints — outputs unchanged), this
        engine's OUTPUTS are no longer trustworthy, so it must not emit
        another token."""
        self._quarantined = True
        self._accepting = False
        self.stats["canary_mismatch"] += 1
        self.stats["quarantined"] = 1
        diff = next((i for i, (a, b) in enumerate(zip(expected, got))
                     if a != b), min(len(expected), len(got)))
        self.quarantine_reason = (
            f"canary token stream diverged from pinned reference at "
            f"token {diff}: expected {list(expected)}, got {list(got)}")
        self.obs.event("canary_quarantine", first_diff=diff,
                       expected=list(expected), got=list(got))
        self.flight.dump("canary_quarantine", extra={
            "expected": list(expected), "got": list(got),
            "first_diff": diff})

    def _gather_drafts(self, ms, active, k):
        """Host-side draft proposals for every decoding slot, behind the
        fault-isolation wall: a drafter that raises, returns non-integer
        or out-of-vocab tokens, or exceeds ``drafter_timeout_s`` per
        propose is quarantined and this step's proposals are discarded
        (returns None; the caller falls back to plain decode).  A buggy
        host-side drafter can therefore never corrupt or stall the
        stream.

        Each propose runs inside a scoped watchdog deadline too (when
        one is armed): a propose that BLOCKS outright — the one fault no
        host-side timing check can see from inside — is detected from
        outside like a wedged device step (``kill=True`` exits for the
        scheduler; ``kill=False`` surfaces as a StepHangError at the
        next guarded scope, which quarantines the drafter here)."""
        proposed = []
        budget = self.drafter_timeout_s
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            context = np.concatenate(
                [r.prompt, np.asarray(r.tokens, np.int32)])
            t0 = time.perf_counter()
            try:
                with self._guard(budget if budget is not None
                                 else self._step_timeout_s,
                                 name="draft_propose"):
                    raw = self.drafter.propose(context, k)
                draft = np.asarray(raw).reshape(-1)[:k]
            except Exception as exc:  # noqa: BLE001 — isolation by design
                self._quarantine_drafter(
                    f"propose() raised {type(exc).__name__}: {exc}")
                return None
            took = time.perf_counter() - t0
            if (self._watchdog is not None
                    and self._watchdog.acknowledge()):
                # The monitor fired WHILE propose was blocked (kill=True
                # would have exited the process; a propose that never
                # returns at all is exactly that case) — quarantine here
                # so the hang is charged to the drafter, not to the next
                # guarded device call.
                self._quarantine_drafter(
                    f"propose() exceeded the armed watchdog deadline "
                    f"({took:.4f}s elapsed)", r, int(draft.size))
                return None
            if draft.size and draft.dtype.kind not in "iu":
                self._quarantine_drafter(
                    f"propose() returned non-integer tokens "
                    f"(dtype {draft.dtype})", r, int(draft.size))
                return None
            if draft.size and (int(draft.min()) < 0
                               or int(draft.max())
                               >= ms.config.vocab_size):
                self._quarantine_drafter(
                    "propose() returned out-of-vocab token ids",
                    r, int(draft.size))
                return None
            if budget is not None and took > budget:
                self._quarantine_drafter(
                    f"propose() took {took:.4f}s "
                    f"(drafter_timeout_s={budget})", r, int(draft.size))
                return None
            if draft.size:
                proposed.append((int(s), draft.astype(np.int32)))
        return proposed

    def _run_verify(self, ms: _ModelState, active, emitted) -> None:
        """Draft host-side, verify device-side: up to ``speculate_k``
        proposed tokens per decoding slot ride the window with the row's
        last token; the accepted prefix (plus the verify forward's own
        next token) is committed in order.  EOS or an exhausted budget
        retires the row mid-window and the remaining emitted tokens are
        dropped — exactly the tokens sequential decode would never have
        produced.  Drafts are hints, never correctness inputs — and a
        drafter that violates even the hint contract (raise/malformed/
        slow) is quarantined by ``_gather_drafts``.

        A step where NO row drafted falls through to the plain decode
        step: the k+1-wide verify forward costs real extra FLOPs per
        window slot, and paying them to emit one token per row is pure
        loss.  Both programs still compile exactly once per geometry —
        the dispatch switches between two warm programs, it never
        creates a new one."""
        k = self.speculate_k
        span = self._enter("batch")
        proposed = self._gather_drafts(ms, active, k)
        if not proposed:  # nothing drafted, or the drafter just got cut
            self._leave(span)
            self._run_decode(ms, active, emitted)
            return
        tokens = np.zeros((self.num_slots, k + 1), np.int32)
        tokens[:, 0] = self._last
        n_draft = np.zeros(self.num_slots, np.int32)
        for s, draft in proposed:
            tokens[s, 1:1 + draft.size] = draft  # validated in-vocab
            n_draft[s] = draft.size
            self._slots[s].draft_proposed += int(draft.size)
        self._leave(span)
        if self._paged:
            ms.pool.pages, out, n_emit, self._keys = self._device(
                "verify", ms.verify_paged,
                ms.pool.pages, ms.table, tokens, self._len, active,
                n_draft, self._temps, self._topk, self._topp, self._keys)
        else:
            ms.cache, out, n_emit, self._keys = self._device(
                "verify", ms.verify_step,
                ms.cache, tokens, self._len, active, n_draft, self._temps,
                self._topk, self._topp, self._keys)
        span = self._enter("fetch")
        # tpudp: lint-ok(host-sync): the per-window verify fetch (one
        # round trip per k+1-token window, amortized over accepts) —
        # fusing the drafter into the device program removes it.
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)  # tpudp: lint-ok(host-sync): same fetch
        self._leave(span)
        span = self._enter("commit")
        self._poll()
        self.stats["verify_steps"] += 1
        self.stats["active_slot_steps"] += int(active.sum())
        self.stats["draft_tokens"] += int(n_draft.sum())
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            accepted = int(n_emit[s]) - 1
            r.draft_accepted += accepted
            self.stats["draft_accepted"] += accepted
            for j in range(int(n_emit[s])):
                if self._slots[s] is not r:
                    break  # retired (EOS / budget / cancel) mid-window
                # Each commit after the first lands because the PREVIOUS
                # emitted token's KV was written by this window; += 1
                # per commit advances the row past exactly those writes.
                self._len[s] += 1
                self._commit(s, int(out[s, j]), emitted)
        self._leave(span)

    def _run_spec_fused(self, ms: _ModelState, active, emitted) -> None:
        """One fused SPECULATIVE window: up to ``decode_fuse``
        draft→verify→accept iterations in a single device program
        (``fused_spec_step`` — the drafter runs ON DEVICE from each
        slot's token history), then ONE fetch and the same host replay
        seam as ``_run_decode_fused``: per-slot key carry committed
        just before that slot's replay, every token through the
        unchanged ``_commit`` path, acceptance accounting charged
        before replay like ``_run_verify``.  The device already cut
        each row at its EOS/budget, so replay retirement agrees with
        the loop predicate by construction — a later fall-back to
        host-drafted verify (or plain decode) resumes bit-exactly."""
        k = self.speculate_k
        span = self._enter("batch")
        budgets = np.zeros(self.num_slots, np.int32)
        eos = np.full(self.num_slots, -1, np.int32)
        hist = np.zeros((self.num_slots, self.max_len), np.int32)
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            budgets[s] = r.max_new_tokens - len(r.tokens)
            if r.eos_id is not None:
                eos[s] = r.eos_id
            ctx = np.concatenate(
                [r.prompt, np.asarray(r.tokens, np.int32)])
            hist[s, :ctx.size] = ctx  # fits: prompt+budget+k <= max_len
        # Each iteration runs k draft steps + a draft prefill + one
        # verify window, so the watchdog budget scales with both the
        # window and the draft work per window.
        budget_s = (self._step_timeout_s * self.decode_fuse * (k + 2)
                    if self._step_timeout_s is not None else None)
        self._leave(span)
        if self._paged:
            (ms.pool.pages, out, n_emit, n_win, n_acc, keys,
             iters) = self._device(
                "fused_spec", ms.fused_spec_paged,
                ms.pool.pages, ms.table, hist, self._last, self._len,
                active, self._temps, self._topk, self._topp, self._keys,
                budgets, eos, np.int32(self._ring_id),
                guard_timeout_s=budget_s, n_draft_k=k,
                n_steps=self.decode_fuse, stream=self._fuse_stream)
        else:
            ms.cache, out, n_emit, n_win, n_acc, keys, iters = self._device(
                "fused_spec", ms.fused_spec_step,
                ms.cache, hist, self._last, self._len, active,
                self._temps, self._topk, self._topp, self._keys,
                budgets, eos, np.int32(self._ring_id),
                guard_timeout_s=budget_s, n_draft_k=k,
                n_steps=self.decode_fuse, stream=self._fuse_stream)
        span = self._enter("fetch")
        # tpudp: lint-ok(host-sync): the per-PROGRAM fetch — one round
        # trip per up-to-decode_fuse speculative windows, replacing the
        # host-drafted path's per-window draft gather + verify fetch.
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)  # tpudp: lint-ok(host-sync): same fetch
        n_win = np.asarray(n_win)  # tpudp: lint-ok(host-sync): same fetch
        n_acc = np.asarray(n_acc)  # tpudp: lint-ok(host-sync): same fetch
        self._leave(span)
        span = self._enter("commit")
        self._poll()
        self.stats["fused_spec_windows"] += 1
        self.stats["fused_spec_steps"] += int(iters)  # tpudp: lint-ok(host-sync): same fetch
        # A row participates in one verify window per loop iteration it
        # was running — n_win.sum() is the window's active-slot-step
        # count (the occupancy denominator's fused-spec share).
        self.stats["active_slot_steps"] += int(n_win.sum())
        self.stats["draft_tokens"] += int(n_win.sum()) * k
        self.stats["draft_accepted"] += int(n_acc.sum())
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            r.draft_proposed += int(n_win[s]) * k
            r.draft_accepted += int(n_acc[s])
            # Per-slot key carry just before that slot's replay — the
            # containment-mid-replay argument of _run_decode_fused.
            self._keys = self._keys.at[s].set(keys[s])
            self._enqueued(self._keys)
            self.stats["eager_key_updates"] += 1
            for j in range(int(n_emit[s])):
                if self._slots[s] is not r:
                    break  # retired (EOS / budget / cancel) mid-replay
                self._len[s] += 1
                self._commit(int(s), int(out[s, j]), emitted)
        self._leave(span)

    def _gather_tree_drafts(self, ms, active, shape):
        """Host-side TREE proposals behind the same fault-isolation
        wall as ``_gather_drafts``: a drafter whose ``propose_tree``
        raises, returns a wrong-shaped or out-of-vocab array, or blows
        its time budget is quarantined and the step falls back (None).
        Rows where the drafter has no proposal (``propose_tree`` →
        None) simply run the no-candidate path in-window."""
        proposed = []
        budget = self.drafter_timeout_s
        T = shape.num_candidates
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            context = np.concatenate(
                [r.prompt, np.asarray(r.tokens, np.int32)])
            t0 = time.perf_counter()
            try:
                with self._guard(budget if budget is not None
                                 else self._step_timeout_s,
                                 name="draft_propose_tree"):
                    raw = self.drafter.propose_tree(context, shape)
            except Exception as exc:  # noqa: BLE001 — isolation by design
                self._quarantine_drafter(
                    f"propose_tree() raised {type(exc).__name__}: {exc}")
                return None
            took = time.perf_counter() - t0
            draft = (np.zeros(0, np.int32) if raw is None
                     else np.asarray(raw).reshape(-1))
            if (self._watchdog is not None
                    and self._watchdog.acknowledge()):
                self._quarantine_drafter(
                    f"propose_tree() exceeded the armed watchdog "
                    f"deadline ({took:.4f}s elapsed)", r,
                    int(draft.size))
                return None
            if raw is None:
                continue
            if draft.size != T or draft.dtype.kind not in "iu":
                self._quarantine_drafter(
                    f"propose_tree() returned a malformed candidate "
                    f"array (size {draft.size}, dtype {draft.dtype}; "
                    f"shape {shape.name!r} wants {T} int tokens)",
                    r, int(draft.size))
                return None
            if int(draft.min()) < 0 or int(draft.max()) >= \
                    ms.config.vocab_size:
                self._quarantine_drafter(
                    "propose_tree() returned out-of-vocab token ids",
                    r, int(draft.size))
                return None
            if budget is not None and took > budget:
                self._quarantine_drafter(
                    f"propose_tree() took {took:.4f}s "
                    f"(drafter_timeout_s={budget})", r, int(draft.size))
                return None
            proposed.append((int(s), draft.astype(np.int32)))
        return proposed

    def _run_verify_tree(self, ms: _ModelState, active, emitted) -> None:
        """Draft a TREE host-side, verify device-side in one tree-masked
        forward (``Engine(speculate_tree=shape)``): candidate branches
        ride the window with each row's last token, the accepted
        root-to-leaf path (plus the bonus token) commits in order
        through the ``_run_verify`` replay seam.  Rows without a
        proposal run the no-candidate path (one plain-decode-equivalent
        token); a step where NOTHING drafted falls through to the plain
        decode step like ``_run_verify`` does."""
        shape = self.speculate_tree
        span = self._enter("batch")
        proposed = self._gather_tree_drafts(ms, active, shape)
        if not proposed:  # nothing drafted, or the drafter just got cut
            self._leave(span)
            self._run_decode(ms, active, emitted)
            return
        tokens = np.zeros((self.num_slots, shape.num_candidates + 1),
                          np.int32)
        tokens[:, 0] = self._last
        n_cand = np.zeros(self.num_slots, np.int32)
        for s, draft in proposed:
            tokens[s, 1:] = draft  # validated in-vocab, exactly T wide
            n_cand[s] = draft.size
            self._slots[s].draft_proposed += int(draft.size)
        self._leave(span)
        if self._paged:
            ms.pool.pages, out, n_emit, self._keys = self._device(
                "tree_verify", ms.tree_paged,
                ms.pool.pages, ms.table, tokens, self._len, active,
                n_cand, self._temps, self._topk, self._topp, self._keys,
                parents=shape.parents)
        else:
            ms.cache, out, n_emit, self._keys = self._device(
                "tree_verify", ms.tree_step,
                ms.cache, tokens, self._len, active, n_cand,
                self._temps, self._topk, self._topp, self._keys,
                parents=shape.parents)
        span = self._enter("fetch")
        # tpudp: lint-ok(host-sync): the per-window verify fetch — the
        # tree twin of _run_verify's, one round trip per tree window.
        out = np.asarray(out)
        n_emit = np.asarray(n_emit)  # tpudp: lint-ok(host-sync): same fetch
        self._leave(span)
        span = self._enter("commit")
        self._poll()
        self.stats["tree_verify_steps"] += 1
        self.stats["active_slot_steps"] += int(active.sum())
        self.stats["draft_tokens"] += int(n_cand.sum())
        for s in np.nonzero(active)[0]:
            r = self._slots[s]
            accepted = int(n_emit[s]) - 1
            r.draft_accepted += accepted
            self.stats["draft_accepted"] += accepted
            for j in range(int(n_emit[s])):
                if self._slots[s] is not r:
                    break  # retired (EOS / budget / cancel) mid-window
                self._len[s] += 1
                self._commit(s, int(out[s, j]), emitted)
        self._leave(span)

    def _commit(self, s: int, tok: int, emitted) -> None:
        r = self._slots[s]
        if self.token_fault_hook is not None:
            # The silent-corruption seam (tpudp.serve.faults): a flipped
            # token committed here conditions every later decode step of
            # this slot — exactly the downstream signature corrupted
            # logits would produce.
            tok = int(self.token_fault_hook(s, tok, r))
        r.tokens.append(tok)
        r.token_times.append(time.perf_counter())
        self._last[s] = tok
        emitted.append((r, tok))
        self.stats["tokens"] += 1
        if len(r.tokens) == 1 and self.obs.enabled:
            # The request's first token ever (a requeue or a preemption
            # resume re-prefills with its tokens kept, so it never comes
            # back here): where its time to first token went.
            split = r.ttft_split()
            if split is not None:  # None: adopted from another host
                self.stats["first_tokens"] += 1
                self.stats["ttft_s"] += split[0]
                self.stats["ttft_queue_s"] += split[1]
                self.stats["ttft_prefill_wait_s"] += split[2]
        if r.tenant is not None:
            self._sched.stats(r.tenant)["tokens"] += 1
        if r.eos_id is not None and tok == r.eos_id:
            self._retire(s, FinishReason.EOS)
        elif len(r.tokens) >= r.max_new_tokens:
            self._retire(s, FinishReason.COMPLETE)

    def _priority_of(self, r: Request) -> int:
        return self._sched.cls(r.tenant).priority

    def _preempt_for_priority(self) -> None:
        """Evict lower-priority in-flight work when higher-priority
        requests would otherwise wait.  For each queued request in
        priority order (a snapshot — requests evicted below re-enter
        their queues but never count as waiters this pass): consume a
        free slot if one exists, otherwise evict the lowest-priority
        in-flight slot whose priority is STRICTLY below the waiter's
        (most recently admitted among equals — the least sunk cost).
        Stops the moment no strictly-lower victim remains, so equal
        priorities never preempt each other and the scan is bounded by
        min(queued, num_slots) evictions per step."""
        waiting = self._sched.waiting_by_priority()
        if not waiting:
            return
        free = sum(r is None for r in self._slots)
        for pri, count in waiting:
            for _ in range(count):
                if free:
                    free -= 1
                    continue
                victims = [s for s, r in enumerate(self._slots)
                           if r is not None and self._priority_of(r) < pri]
                if not victims:
                    return
                self._preempt_slot(max(
                    victims,
                    key=lambda s: (-self._priority_of(self._slots[s]),
                                   self._slots[s]._order)))
                # the freed slot is spoken for by this waiter

    def _preempt_slot(self, s: int) -> None:
        """Evict slot ``s`` for higher-priority work via the SAME
        carry-over path as step-failure requeue: emitted tokens and the
        per-slot PRNG chain ride along, the request re-enters the FRONT
        of its class queue, and on re-admission it re-prefills
        ``prompt + tokens`` under the saved chain — continuing
        bit-identically, which is why ``FinishReason.PREEMPTED`` never
        reaches a handle.  Unlike containment, nothing failed: the
        arena stays live (the vacated row's stale KV is covered by
        overwrite-before-visible, like any recycled slot), the requeue
        budget is untouched (preemption must be repeatable without
        burning the fault budget), and the prefilled prefix is
        published first when caching is on, so the resume's re-prefill
        collapses to block copies plus the final chunk."""
        r = self._slots[s]
        if ((self._paged or r._ms.prefix_cache is not None)
                and self._accepting):
            self._publish_prefix(r._ms, s, r)
        self._vacate_slot(s)
        r.preemptions += 1
        self.obs.event("preempt", rid=r.id, slot=s, tenant=r.tenant,
                       tokens=len(r.tokens))
        self.stats["preempted"] += 1
        self._sched.stats(r.tenant)["preempted"] += 1
        self._sched.requeue_front(r)

    def _vacate_slot(self, s: int) -> Request:
        """Clear slot ``s``'s per-slot state and prepare its request
        for a bit-identical resume: the per-slot PRNG chain as of the
        last COMMITTED token (:meth:`_slot_key`) is saved on the handle,
        and the refill becomes ``prompt + tokens``.  The one carry-over
        path shared by step-failure requeue and preemption: both resume
        under the same contract, so a new per-slot array added to one
        must by construction be cleared for the other."""
        r = self._slots[s]
        self._release_slot_pages(r._ms, s)
        key = self._slot_key(s)
        self._pending_keys[s] = None
        self._slots[s] = None
        self._len[s] = 0
        self._temps[s] = 0.0
        self._topk[s] = 0
        self._topp[s] = 1.0
        r._slot = None
        r._resume_key = key
        r._nfill = 0
        r._fill = np.concatenate([r.prompt,
                                  np.asarray(r.tokens, np.int32)])
        return r

    def _slot_key(self, s: int) -> np.ndarray:
        """Slot ``s``'s PRNG key chain as of its last committed token:
        the host's pending key until the slot's first sample, the
        device's chain after it (an eager slice and a fetch: the keys
        array is never donated, so between steps it holds that chain)."""
        key = self._pending_keys[s]
        if key is None:
            key = self._keys[s]
            self._enqueued(key)
            key = np.asarray(key)
        return key

    def _retire(self, s: int, reason: FinishReason,
                error: BaseException | None = None) -> None:
        r = self._slots[s]
        # Publish BEFORE the slot state is cleared (the copy-out reads
        # the slot's arena rows).  Every retirement reason qualifies:
        # the prefilled prefix is valid KV regardless of why the
        # request stopped (a cancelled/expired request's re-usable
        # prefix is exactly as good as a completed one's).  Skipped
        # once drain()/close() has begun — device copies to warm a pool
        # no future request can ever read would only slow shutdown.
        if ((self._paged or r._ms.prefix_cache is not None)
                and self._accepting):
            self._publish_prefix(r._ms, s, r)
        self._release_slot_pages(r._ms, s)
        r._slot = None
        self._slots[s] = None
        self._pending_keys[s] = None
        self._len[s] = 0  # slot recycled; the next prefill overwrites from 0
        # Reset sampling params too: a stale temperature/top-k on an
        # EMPTY slot would keep tripping the sampling op's any-sampled /
        # any-truncated lax.cond gates, making every later all-greedy
        # step pay the RNG + vocab-sort cost the gates exist to skip.
        self._temps[s] = 0.0
        self._topk[s] = 0
        self._topp[s] = 1.0
        self._finish(r, reason, error)
