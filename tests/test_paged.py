"""True paged attention (``Engine(kv_pages=N)``): the paged engine's
contract.

Four properties everything rests on:

  1. BIT-IDENTITY — paged reads ≡ dense reads: greedy outputs through
     the block-table indirection are bit-identical to ``generate()``
     (and to the dense engine) for hit/miss/sampled/speculative/
     multi-tenant-preempted/fused-window traffic, including
     step-failure containment rebuilds and page-pressure vacates.
  2. ZERO-COPY REUSE — a cache hit is a table write (refcount bump on
     the radix tree's pages), never a ``copy_block_in`` call; publish
     is an ownership transfer, never a ``copy_block_out`` call; the
     divergence block is copy-on-write (re-prefilled into a fresh
     private page — shared pages are never written).
  3. OFF-SWITCH EQUIVALENCE — ``kv_pages=0`` (the default) is
     byte-for-byte the dense engine: no paged program ever traced, no
     paged stats keys, no pool allocated.
  4. TABLE↔POOL CONSISTENCY — every allocated page's refcount equals
     its actual holders (tree nodes + table mappings);
     ``Engine.check_paged()`` holds through arbitrary churn,
     preemption, pressure vacates, and containment.

Plus the capacity story the ledger pins: the committed
``tools/trace_lock.json`` budget must show a 2-model paged engine's
peak live bytes below the dense 2-arena baseline.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import generate
from tpudp.models.gpt2 import gpt2_small
from tpudp.serve import TRACE_COUNTS, Engine, NgramDrafter, TenantClass
from tpudp.serve.prefix_cache import PageIndex, PagePool
from tpudp.train import init_state, make_optimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=61, max_seq_len=96, num_layers=2, num_heads=2,
            d_model=32)


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt2_small(**TINY)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


def _reference(model, params, prompt, n):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                               n))[0, prompt.size:]


def _assert_parity(model, params, prompt, n, handle):
    np.testing.assert_array_equal(_reference(model, params, prompt, n),
                                  np.asarray(handle.tokens))


# ---------------------------------------------------------------------------
# PagePool / PageIndex unit tests (no engine, no device work)
# ---------------------------------------------------------------------------


def _tiny_pool(num_pages=4, page_tokens=4, kv_dtype=None):
    cfg = gpt2_small(vocab_size=31, max_seq_len=32, num_layers=1,
                     num_heads=1, d_model=8).config
    return PagePool(cfg, num_pages, page_tokens, kv_dtype)


def test_pool_refcount_discipline():
    pool = _tiny_pool(num_pages=3)
    a = pool.alloc()
    b = pool.alloc()
    assert (a, b) == (0, 1)  # deterministic ascending allocation
    assert pool.used_pages == 2 and pool.free_pages == 1
    pool.share(a)               # second holder
    pool.release(a)             # first holder gone, page still live
    assert pool.used_pages == 2
    pool.release(a)             # last holder gone -> free again
    assert pool.used_pages == 1
    pool.check({b: 1})
    c = pool.alloc()
    d = pool.alloc()
    assert c is not None and d is not None and pool.alloc() is None
    pool.check({b: 1, c: 1, d: 1})
    with pytest.raises(RuntimeError, match="disagree"):
        pool.check({b: 2, c: 1, d: 1})
    pool.reallocate()
    assert pool.free_pages == 3
    pool.check({})


def test_pool_validation_and_scratch():
    with pytest.raises(ValueError, match="num_pages"):
        _tiny_pool(num_pages=0)
    with pytest.raises(ValueError, match="kv_dtype"):
        _tiny_pool(kv_dtype="fp8")
    pool = _tiny_pool(num_pages=2, kv_dtype="int8")
    # buffer carries num_pages + 1 (the scratch page) in every payload
    assert pool.pages.k.shape[1] == 3
    assert pool.pages.k_scale.shape[1] == 3
    assert pool.scratch == 2


def test_index_adopt_lookup_evict():
    pool = _tiny_pool(num_pages=3)
    idx = PageIndex(pool)
    seq = np.arange(12, dtype=np.int32)
    # a "slot" owns three pages (rc=1 each) and publishes them
    pages = [pool.alloc() for _ in range(3)]
    assert idx.adopt(seq, pages) == 3       # tree takes its own refs
    for p in pages:
        pool.release(p)                     # the slot vacates
    assert pool.used_pages == 3             # tree keeps them alive
    nodes = idx.lookup(seq)
    assert [n.block for n in nodes] == pages
    assert idx.lookup(seq[:7]) == nodes[:1]  # block-aligned prefix only
    # re-adopting allocates nothing new
    assert idx.adopt(seq, pages) == 0
    idx.check()
    # pinned nodes are never evicted; leaves evict LRU back to the pool
    idx.pin(nodes[2])
    assert not idx.evict_one()   # leaf pinned, interiors ref'd by children
    idx.unpin(nodes[2])
    assert idx.evict_one() and pool.used_pages == 2
    idx.check()
    idx.flush()
    assert pool.used_pages == 0
    pool.check({})


# ---------------------------------------------------------------------------
# Off-switch + validation
# ---------------------------------------------------------------------------


def test_paged_off_is_byte_identical_default(model_and_params):
    """kv_pages=0 (the default) is byte-for-byte the dense engine: no
    paged program ever traced, no paged stats keys, no pool."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 61, size=n).astype(np.int32)
               for n in (5, 19)]
    before = {k: TRACE_COUNTS[k] for k in
              ("decode_paged", "verify_paged", "prefill_paged",
               "fused_decode_paged")}
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8)
    assert eng.page_pool is None and eng.page_index is None
    outs = eng.generate_many(prompts, 5)
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(
            np.concatenate([p, _reference(model, params, p, 5)]), o)
    assert not any(k.startswith(("prefix", "page")) for k in eng.stats), \
        eng.stats
    for k, v in before.items():
        assert TRACE_COUNTS[k] == v, f"{k} traced with paging off"


def test_paged_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="kv_pages"):
        Engine(model, params, kv_pages=-1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        Engine(model, params, kv_pages=8, prefix_cache_blocks=8)
    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(model, params, kv_dtype="int8")  # requires kv_pages
    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(model, params, kv_pages=8, kv_dtype="fp8")
    with pytest.raises(ValueError, match="raise kv_pages"):
        # 48-token max_len needs 6 chunk-8 pages; 4 can't hold one request
        Engine(model, params, max_len=48, prefill_chunk=8, kv_pages=4)


# ---------------------------------------------------------------------------
# Bit-exact parity: the tentpole oracle
# ---------------------------------------------------------------------------


def test_paged_greedy_parity_hit_and_miss(model_and_params):
    """Paged reads ≡ dense reads: cold (miss) and warm (table-write
    hit) admissions both match generate() bit-for-bit, with ZERO block
    copies either way."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 61, size=20).astype(np.int32)
    p1 = np.concatenate([shared, rng.integers(0, 61, size=3)
                         .astype(np.int32)])
    p2 = np.concatenate([shared, rng.integers(0, 61, size=5)
                         .astype(np.int32)])
    in_before = TRACE_COUNTS["prefix_block_in"]
    out_before = TRACE_COUNTS["prefix_block_out"]
    eng = Engine(model, params, num_slots=1, max_len=48, prefill_chunk=8,
                 kv_pages=12)
    h1 = eng.submit(p1, 6)
    eng.run_until_complete()
    assert eng.stats["prefix_hit_tokens"] == 0  # cold
    h2 = eng.submit(p2, 6)
    eng.run_until_complete()
    _assert_parity(model, params, p1, 6, h1)
    _assert_parity(model, params, p2, 6, h2)
    assert eng.stats["prefix_hit_tokens"] == 16  # both published blocks
    # zero-copy reuse: the dense copy programs never ran
    assert TRACE_COUNTS["prefix_block_in"] == in_before
    assert TRACE_COUNTS["prefix_block_out"] == out_before
    eng.check_paged()


def test_paged_sampled_parity(model_and_params):
    """A seeded sampled request draws identical tokens through the
    paged indirection (hit or miss) as through the dense arena."""
    model, params = model_and_params
    rng = np.random.default_rng(2)
    p = rng.integers(0, 61, size=20).astype(np.int32)

    def tokens_of(kv_pages, prewarm):
        eng = Engine(model, params, num_slots=1, max_len=48,
                     prefill_chunk=8, kv_pages=kv_pages)
        if prewarm:
            eng.submit(p, 2)
            eng.run_until_complete()
        h = eng.submit(p, 8, temperature=0.9, top_k=12, top_p=0.9, seed=7)
        eng.run_until_complete()
        return list(h.tokens)

    dense = tokens_of(0, False)
    assert tokens_of(12, False) == dense   # paged, miss
    assert tokens_of(12, True) == dense    # paged, table-write hit


# Demoted to slow (PR 20 durations audit): spec-over-paged parity is
# covered fast by tests/test_spec_fused.py::test_fused_spec_paged_parity
# and the tests/test_speculate.py parity suite.
@pytest.mark.slow
def test_paged_speculation_parity(model_and_params):
    """Speculative verify windows read/write through the tables (the
    window may cross a page boundary — the host preallocates) and stay
    bit-identical to generate()."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 61, size=20).astype(np.int32)
    eng = Engine(model, params, num_slots=2, max_len=64, prefill_chunk=8,
                 kv_pages=16, speculate_k=2, drafter=NgramDrafter())
    prompts, handles = [], []
    for i in range(3):
        p = np.concatenate([shared, rng.integers(0, 61, size=2 + i)
                            .astype(np.int32)])
        prompts.append(p)
        handles.append(eng.submit(p, 8))
        eng.run_until_complete()
    assert eng.stats["prefix_hit_tokens"] > 0
    for p, h in zip(prompts, handles):
        _assert_parity(model, params, p, 8, h)
    eng.check_paged()


def test_paged_fused_decode_parity(model_and_params):
    """The fused lax.while_loop program with the page indirection in
    its body commits bit-identically to the single-step paged engine
    and to generate()."""
    model, params = model_and_params
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 61, size=9 + 3 * i).astype(np.int32)
               for i in range(3)]
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=16, decode_fuse=4)
    handles = [eng.submit(p, 6) for p in prompts]
    eng.run_until_complete()
    assert eng.stats["fused_windows"] > 0
    for p, h in zip(prompts, handles):
        _assert_parity(model, params, p, 6, h)
    eng.check_paged()


def test_paged_speculation_with_fusing_enabled_parity(model_and_params):
    """REGRESSION (review finding): with BOTH speculate_k > 0 and
    decode_fuse > 1, the dispatch runs the k+1 verify window even on
    iterations where the fuse flag is set — page preallocation must
    mirror that order.  The pre-fix engine backed only the fused
    window's positions, routed the verify tail's KV writes to the
    scratch page, and silently diverged from generate()."""
    model, params = model_and_params
    rng = np.random.default_rng(12)
    # repetitive prompts lock the n-gram drafter on -> real k+1 windows
    prompts = [np.tile(rng.integers(0, 61, size=4),
                       8)[:26 + i].astype(np.int32) for i in range(3)]
    eng = Engine(model, params, num_slots=2, max_len=64, prefill_chunk=8,
                 kv_pages=16, speculate_k=3, drafter=NgramDrafter(),
                 decode_fuse=2)
    handles = [eng.submit(p, 8) for p in prompts]
    eng.run_until_complete()
    assert eng.stats["draft_tokens"] > 0  # windows actually ran
    for p, h in zip(prompts, handles):
        _assert_parity(model, params, p, 8, h)
    eng.check_paged()


def test_paged_compile_once_across_churn(model_and_params):
    """The static-shape invariant extends to paging: after warmup,
    hit/miss admissions, publishes, evictions, and slot churn never
    re-trace the paged programs (values flow through tables — shapes
    never change)."""
    model, params = model_and_params
    rng = np.random.default_rng(5)
    # A geometry no other test uses (jit caches are global).
    eng = Engine(model, params, num_slots=3, max_len=40, prefill_chunk=8,
                 kv_pages=15)
    warm = rng.integers(0, 61, size=12).astype(np.int32)
    eng.submit(warm, 2)
    eng.run_until_complete()   # miss -> prefill_paged + decode_paged
    eng.submit(warm, 2)
    eng.run_until_complete()   # hit admission
    base = {k: TRACE_COUNTS[k] for k in ("decode_paged", "prefill_paged")}
    assert all(v > 0 for v in base.values())
    shared = rng.integers(0, 61, size=17).astype(np.int32)
    for i in range(6):
        tail = rng.integers(0, 61, size=1 + i % 3).astype(np.int32)
        eng.submit(np.concatenate([shared[:8 + 4 * (i % 2)], tail]), 2)
        if i % 2:
            eng.run_until_complete()
    eng.run_until_complete()
    for k, v in base.items():
        assert TRACE_COUNTS[k] == v, f"{k} re-traced under churn"
    eng.check_paged()


# ---------------------------------------------------------------------------
# COW under churn: divergence, preemption, pressure, containment
# ---------------------------------------------------------------------------


def test_cow_divergence_preempt_resume_bit_exact(model_and_params):
    """Satellite oracle: two slots MAP the same prefix pages (real
    sharing — equal table entries, refcount > 1), diverge into private
    pages past the divergence block, one is preempted by
    higher-priority work and resumes bit-exactly; refcounts and
    check_paged() hold at every scheduler step."""
    model, params = model_and_params
    rng = np.random.default_rng(6)
    shared = rng.integers(0, 61, size=24).astype(np.int32)
    pa = np.concatenate([shared, rng.integers(0, 61, size=3)
                         .astype(np.int32)])
    pb = np.concatenate([shared, rng.integers(0, 61, size=5)
                         .astype(np.int32)])
    hi_p = rng.integers(0, 61, size=9).astype(np.int32)
    eng = Engine(model, params, num_slots=2, max_len=64, prefill_chunk=8,
                 kv_pages=24,
                 tenants={"lo": TenantClass(priority=0),
                          "hi": TenantClass(priority=1)})
    # Warm the tree so BOTH measured admissions map shared pages.
    warm = eng.submit(np.concatenate(
        [shared, rng.integers(0, 61, size=1).astype(np.int32)]), 2,
        tenant="lo")
    eng.run_until_complete()
    ha = eng.submit(pa, 8, tenant="lo")
    hb = eng.submit(pb, 8, tenant="lo")
    eng.step()
    eng.check_paged()
    ms = eng._mstates[None]
    # Both slots share the prefix pages by TABLE (copy-on-write: the
    # shared entries are identical page ids, pinned not copied).
    sa, sb = ha._slot, hb._slot
    assert sa is not None and sb is not None
    shared_pages = min(len(shared) // 8, (pa.size - 1) // 8)
    for i in range(min(shared_pages, (pb.size - 1) // 8)):
        assert ms.table[sa, i] == ms.table[sb, i] >= 0
    # ...and diverge into DIFFERENT private pages past the prefix.
    while ha._slot is not None and not ha.tokens:
        eng.step()
        eng.check_paged()
    div = shared_pages  # first page past the block-aligned hit
    if ms.table[sa, div] >= 0 and ms.table[sb, div] >= 0:
        assert ms.table[sa, div] != ms.table[sb, div]
    # Preempt: the high-priority request evicts one lo slot.
    hc = eng.submit(hi_p, 4, tenant="hi")
    while not hc.done:
        eng.step()
        eng.check_paged()
    eng.run_until_complete()
    assert eng.stats["preempted"] >= 1
    _assert_parity(model, params, pa, 8, ha)
    _assert_parity(model, params, pb, 8, hb)
    _assert_parity(model, params, hi_p, 4, hc)
    _assert_parity(model, params, warm.prompt, 2, warm)
    eng.check_paged()


def test_page_pressure_vacates_and_oldest_survives(model_and_params):
    """A pool sized for ONE max-length request under 3 co-resident
    slots: page pressure vacates the most recently admitted slot (the
    oldest always progresses), vacated requests resume bit-exactly,
    and the run ends clean."""
    model, params = model_and_params
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 61, size=9 + 3 * i).astype(np.int32)
               for i in range(5)]
    eng = Engine(model, params, num_slots=3, max_len=48, prefill_chunk=8,
                 kv_pages=6)   # exactly one request's worst case
    handles = [eng.submit(p, 6) for p in prompts]
    eng.run_until_complete()
    assert eng.stats["page_pressure_vacates"] > 0
    for p, h in zip(prompts, handles):
        _assert_parity(model, params, p, 6, h)
    assert eng.slots_in_use == 0 and eng.queue_depth == 0
    eng.check_paged()


def test_paged_containment_rebuilds_pool_tables_and_tree(
        model_and_params):
    """A contained device-step failure rebuilds the ENTIRE paged state
    — pool buffer, block tables, radix tree — and the requeued
    survivors re-prefill into fresh pages bit-identically (the paged
    mirror of the dense arena-rebuild oracle), fused windows
    included."""
    class _FailFirstFused:
        def __init__(self):
            self.fired = 0

        def __call__(self, kind, index):
            if kind == "fused_decode" and not self.fired:
                self.fired = 1
                raise RuntimeError("injected fused-window fault")

    model, params = model_and_params
    rng = np.random.default_rng(8)
    shared = rng.integers(0, 61, size=20).astype(np.int32)
    p1 = np.concatenate([shared, rng.integers(0, 61, size=3)
                         .astype(np.int32)])
    p2 = np.concatenate([shared, rng.integers(0, 61, size=4)
                         .astype(np.int32)])
    eng = Engine(model, params, num_slots=1, max_len=48, prefill_chunk=8,
                 kv_pages=12, decode_fuse=4)
    h1 = eng.submit(p1, 6)
    eng.run_until_complete()      # warm: p1's pages published
    assert eng.page_pool.used_pages > 0
    # fire exactly once, on the first fused window h2 dispatches
    hook = _FailFirstFused()
    eng.step_fault_hook = hook
    h2 = eng.submit(p2, 6)        # hits, then faults mid-window
    eng.run_until_complete()
    assert hook.fired and eng.stats["step_failures"] == 1
    assert eng.stats["prefix_flushes"] >= 1
    _assert_parity(model, params, p1, 6, h1)
    _assert_parity(model, params, p2, 6, h2)   # requeued, bit-identical
    eng.step_fault_hook = None
    h3 = eng.submit(p1, 6)        # tree re-warms from p2's requeue
    eng.run_until_complete()
    assert h3.tokens == h1.tokens
    eng.check_paged()


def test_paged_multi_model_one_pool_idle_tenant_zero_pages(
        model_and_params):
    """Co-resident models of one KV geometry share ONE PagePool; an
    idle tenant holds zero pages (vs a full dense arena), each model
    keeps its own radix tree, and per-model outputs match each model's
    own generate()."""
    import jax

    model, params = model_and_params
    m2 = gpt2_small(**TINY)
    p2 = m2.init(jax.random.PRNGKey(9), jnp.zeros((1, 8), jnp.int32),
                 train=False)["params"]
    rng = np.random.default_rng(9)
    pa = rng.integers(0, 61, size=12).astype(np.int32)
    pb = rng.integers(0, 61, size=14).astype(np.int32)
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=12,
                 tenants={"default": TenantClass(priority=0),
                          "b": TenantClass(priority=0, model="m2")},
                 models={"m2": (m2, p2)})
    msa, msb = eng._mstates[None], eng._mstates["m2"]
    assert msa.pool is msb.pool          # one shared pool
    assert msa.index is not msb.index    # per-model trees
    ha = eng.submit(pa, 5)
    eng.run_until_complete()
    # model B never ran: its table holds no pages (the dense engine
    # would have reserved a full (num_slots, max_len) arena for it)
    assert (msb.table < 0).all()
    hb = eng.submit(pb, 5, tenant="b")
    eng.run_until_complete()
    np.testing.assert_array_equal(_reference(model, params, pa, 5),
                                  np.asarray(ha.tokens))
    np.testing.assert_array_equal(_reference(m2, p2, pb, 5),
                                  np.asarray(hb.tokens))
    eng.check_paged()


def test_paged_llama_gqa_parity():
    """The LLaMA family decodes through the same paged indirection
    (pages allocate at GQA width — kv_heads, not num_heads) and stays
    bit-identical to its own generate(), fused windows and table-write
    hits included."""
    import jax

    from tpudp.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig(vocab_size=61, max_seq_len=96, num_layers=2,
                      num_heads=4, num_kv_heads=2, d_model=32)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 61, size=20).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 61, size=3 + i)
                               .astype(np.int32)]) for i in range(3)]
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=16, decode_fuse=4)
    # pages allocate at KV width — the GQA memory saving holds for the
    # pool exactly as it did for the dense arena
    # (a token's row is its kv_heads heads of head_dim side by side)
    assert cfg.kv_heads == 2
    assert eng.page_pool.pages.k.shape[-1] == 2 * (32 // 4)
    handles = [eng.submit(p, 6) for p in prompts]
    eng.run_until_complete()
    assert eng.stats["prefix_hit_tokens"] > 0
    for p, h in zip(prompts, handles):
        _assert_parity(model, params, p, 6, h)
    eng.check_paged()


# ---------------------------------------------------------------------------
# int8 page mode
# ---------------------------------------------------------------------------


def test_int8_pages_table_exact_payload_tolerance(model_and_params):
    """kv_dtype='int8' keeps the INDIRECTION exact — identical block
    tables and allocation order vs fp pages for the same traffic —
    while page payloads dequantize to the fp values within the
    symmetric-absmax quantization bound (half the bytes per token).

    The gather-free write path quantizes each token's K/V AT THE WRITE
    (write-before-attend), so in-chunk attention reads the same
    dequantized values every later decode step will — self-consistent,
    unlike the old gather path's quantize-at-scatter (which let a
    chunk's own forward read unquantized in-window K/V).  The pure
    quantization bound therefore holds exactly at LAYER 0, whose block
    input is the embedding (no attention upstream); deeper layers
    compound the quantized-attention drift through the residual stream
    and carry the looser bound."""
    from tpudp.models.generate import gather_pages

    model, params = model_and_params
    rng = np.random.default_rng(10)
    p = rng.integers(0, 61, size=13).astype(np.int32)

    def run(kv_dtype):
        eng = Engine(model, params, num_slots=1, max_len=48,
                     prefill_chunk=8, kv_pages=12, kv_dtype=kv_dtype)
        h = eng.submit(p, 4)
        # Stop at the FIRST token: it rides the prefill sample, so at
        # this point every allocated page holds pure (teacher-forced)
        # prompt KV, written exactly once — the comparison is then a
        # pure quantization-error measurement.
        while not h.tokens:
            eng.step()
        ms = eng._mstates[None]
        tables = ms.table.copy()
        view = np.asarray(gather_pages(
            eng.config, ms.pool.pages, jnp.asarray(tables)).k)
        eng.close()
        return tables, view

    t_fp, v_fp = run(None)
    t_i8, v_i8 = run("int8")
    # exact table-indirection equality: same block ids, same order
    np.testing.assert_array_equal(t_fp, t_i8)
    fp = v_fp[:, 0, :p.size]
    i8 = v_i8[:, 0, :p.size]
    amax = np.abs(fp).max(axis=-1, keepdims=True)
    err = np.abs(fp - i8)
    # LAYER 0's pages are a pure quantization measurement (its k/v are
    # projections of the embedding — no quantized attention upstream):
    # error <= scale/2 = amax/254 per head vector (0.51/127 leaves
    # fp-rounding slack)
    assert np.all(err[0] <= amax[0] / 127.0 * 0.51 + 1e-6)
    # deeper layers ATTEND over already-quantized pages, so their error
    # compounds through the residual stream — bounded, but looser
    assert np.all(err <= 0.02 * amax + 1e-3)


def test_int8_pages_double_capacity_per_byte():
    """The int8 pool stores >= 1.9x the tokens per byte of the fp32
    pool at the same page geometry (payload halves; the per-vector
    scale is the only overhead)."""
    fp = _tiny_pool(num_pages=4, page_tokens=4)
    q = _tiny_pool(num_pages=4, page_tokens=4, kv_dtype="int8")
    assert fp.page_bytes() >= 1.9 * q.page_bytes()


# ---------------------------------------------------------------------------
# The committed budget ledger: the HBM capacity claim
# ---------------------------------------------------------------------------


def test_budget_ledger_paged_below_dense_two_arena_baseline():
    """The committed trace_lock budget must state the capacity win: a
    2-model paged engine — ONE shared pool, each model dispatching the
    pinned paged decode program — stays below the dense 2-arena
    baseline (two models each running the dense decode program over
    their own arena) in BOTH peak live bytes and per-call argument
    bytes, at the audit's smoke geometry where the pool is smaller
    than one dense arena by construction (programs.SERVE['pages'])."""
    with open(os.path.join(ROOT, "tools", "trace_lock.json")) as f:
        lock = json.load(f)
    progs = lock["programs"]

    def budget(prefix):
        names = [n for n in progs if n.startswith(prefix + "@")]
        assert names, f"{prefix} missing from the lock"
        return progs[names[0]]["budget"]

    dense = budget("serve.decode_step")
    paged = budget("serve.decode_paged")
    # 2-model paged: one pool shared across both models' dispatches —
    # the per-call peak is ONE paged program's; the dense 2-arena
    # baseline holds both arenas live.
    assert paged["peak_live_bytes"] < 2 * dense["peak_live_bytes"]
    # and the persistent KV state itself (the program's arguments:
    # pool+table vs arena) is smaller than a single dense arena's
    assert paged["arg_bytes"] < dense["arg_bytes"]
    # every paged program carries a ledger
    for prefix in ("serve.decode_paged", "serve.verify_paged",
                   "serve.prefill_paged", "serve.fused_decode_paged"):
        assert budget(prefix)["peak_live_bytes"] > 0
