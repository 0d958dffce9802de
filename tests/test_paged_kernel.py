"""Gather-free paged attention: the backends behind the one op.

Four contracts on top of test_paged.py's traffic matrix (which now runs
entirely through the gather-free einsum default):

  1. BACKEND EQUIVALENCE — the gather-free einsum engine is bit-
     identical to the kept ``paged_attn='gather'`` baseline (PR 13's
     gather→dense→scatter path) for greedy and sampled traffic: the
     perf rework changed WHERE bytes move, never a value.
  2. SINGLE-PAGE COMMITTED WRITE — a single-token decode step writes
     exactly ONE token row of exactly ONE real page
     (``write_token_pages``), never a page unroll, never the view
     scatter; inactive/unmapped writes route to the scratch page.
  3. KERNEL ORACLE — every Pallas serving kernel (interpret mode on
     the CPU host) matches the gather-based oracle within fp tolerance
     across FRAGMENTED tables: shared prefix pages mapped by several
     slots, a copy-on-write divergence page, unmapped ``-1`` tail
     entries clamping to scratch — and dequantizes int8 pages
     in-kernel within the quantization bound.  The matrix covers the
     paged-decode kernel (``cur == 1``), the flash-window kernel on
     both the k+1 verify shape (vector ``pos``) and the prefill-chunk
     shape (scalar ``pos``, causal in-chunk), and the tree-verify
     kernel (ancestor-or-self window mask, strict ``< pos0`` cache
     visibility); engine-level token-equality pins cover verify,
     fused-decode, fused-spec, and tree traffic plus the per-backend
     default resolution and the int8-tree einsum fallback.
  4. LEDGER DELTA — the committed trace-lock budgets sit STRICTLY below
     the PR 13 gather-based peak-live values (the committed proof the
     gather is gone), pinned against the historical numbers; and every
     kernel program's committed peak sits STRICTLY below its einsum
     twin's (the whole-hot-path memory claim), pinned the same way.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from tpudp.models.generate import (_quantize_kv, generate,
                                   write_token_pages)
from tpudp.models.gpt2 import gpt2_small
from tpudp.ops.paged_attention import paged_attention
from tpudp.serve import TRACE_COUNTS, Engine
from tpudp.train import init_state, make_optimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(vocab_size=61, max_seq_len=96, num_layers=2, num_heads=2,
            d_model=32)

#: The gather-based (PR 13, ``paged_attn='gather'``) peak_live_bytes at
#: the audit smoke geometry (s2m32p6) — the baseline the gather-free
#: rework must beat.  The ledger is a liveness sweep over the JAXPR, so it
#: is only comparable within one jax version: these are the kept gather
#: build's programs re-derived under jax 0.9.0 when the lock moved to it
#: (PR 21; under 0.4.37 PR 13 committed 205_446 / 209_550 / 184_888 /
#: 205_510).
PR13_GATHER_PEAK_LIVE = {
    "serve.decode_paged": 205_446,
    "serve.verify_paged": 227_006,
    "serve.prefill_paged": 202_320,
    "serve.fused_decode_paged": 205_510,
    "serve.fused_decode_paged_stream": 205_510,
}


@pytest.fixture(scope="module")
def model_and_params():
    model = gpt2_small(**TINY)
    state = init_state(model, make_optimizer(), input_shape=(1, 8))
    return model, state.params


def _reference(model, params, prompt, n):
    return np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                               n))[0, prompt.size:]


# ---------------------------------------------------------------------------
# 1. gather vs gather-free backend equivalence
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~6s; gather≡einsum engine equality now runs in the
# fast tier via test_bench_smoke.py::test_serve_paged_traffic_rows_parse
# (three-engine einsum/gather/kernel parity on fragmented tables, warm
# admission included) plus the op-level oracle tests above; the sampled
# path keeps test_paged.py::test_paged_sampled_parity and the sampled
# legs of _run_traffic below (fast-tier margin, r4 #8)
def test_gather_and_einsum_engines_bit_identical(model_and_params):
    """The gather-free default ≡ the kept gather baseline ≡ generate()
    for greedy AND seeded-sampled traffic with a warm (table-write hit)
    admission in the mix — the rework moved bytes, not values."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 61, size=16).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 61, size=3 + i)
                               .astype(np.int32)]) for i in range(3)]

    def run(paged_attn):
        eng = Engine(model, params, num_slots=2, max_len=48,
                     prefill_chunk=8, kv_pages=12, paged_attn=paged_attn)
        greedy = [eng.submit(p, 5) for p in prompts]
        eng.run_until_complete()
        sampled = eng.submit(prompts[0], 6, temperature=0.9, top_k=12,
                             seed=7)
        eng.run_until_complete()
        return [h.tokens for h in greedy] + [sampled.tokens]

    free = run("einsum")
    assert run("gather") == free
    for p, toks in zip(prompts, free[:3]):
        np.testing.assert_array_equal(_reference(model, params, p, 5),
                                      np.asarray(toks))


def test_paged_attn_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="paged_attn"):
        Engine(model, params, kv_pages=12, paged_attn="flash")
    with pytest.raises(ValueError, match="requires kv_pages"):
        Engine(model, params, paged_attn="gather")
    # The kernel hot path now covers fused decode and speculative
    # verify — these used to raise "single-step decode only"; today
    # they build and dispatch kernel programs across the board.
    eng = Engine(model, params, kv_pages=12, paged_attn="kernel",
                 decode_fuse=4, speculate_k=2)
    assert eng.paged_attn == "kernel"
    assert set(eng.paged_attn_dispatch.values()) == {"kernel"}


def test_paged_attn_default_resolution(model_and_params):
    """``paged_attn=None`` (the new default) resolves per backend: CPU
    hosts silently land on the bit-exact einsum path, the request is
    recorded, and dense engines carry no paged dispatch state at all."""
    import jax

    model, params = model_and_params
    assert jax.default_backend() == "cpu"  # tier-1 runs JAX_PLATFORMS=cpu
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=12)
    assert eng.paged_attn_requested is None
    assert eng.paged_attn == "einsum"
    m = eng.metrics()
    assert m["paged_attn"]["requested"] is None
    assert m["paged_attn"]["resolved"] == "einsum"
    assert m["paged_attn"]["fallbacks"] == []
    # dense engine: no paged arena, no paged_attn dispatch surface
    dense = Engine(model, params, num_slots=2, max_len=48,
                   prefill_chunk=8)
    assert "paged_attn" not in dense.metrics()
    # an explicit einsum request on a dense engine stays allowed (it is
    # the resolved default everywhere), any other impl still demands
    # pages to exist
    Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
           paged_attn="einsum")


def test_kernel_int8_tree_fallback_visible_in_metrics(model_and_params,
                                                      monkeypatch):
    """The one per-program einsum fallback in the kernel default: int8
    pools keep tree-verify on the bit-exact einsum path (the tree
    kernel's in-kernel dequant is fp-only).  An AUTO-resolved kernel
    engine surfaces exactly that dispatch decision in its metrics; an
    EXPLICIT ``paged_attn='kernel'`` that cannot be honoured raises —
    never a silent einsum."""
    import jax

    model, params = model_and_params
    kw = dict(num_slots=2, max_len=48, prefill_chunk=8, kv_pages=12,
              kv_dtype="int8", speculate_k=2, speculate_tree="fork2x2")
    with pytest.raises(ValueError, match="cannot be honoured"):
        Engine(model, params, paged_attn="kernel", **kw)
    # the auto resolution as an accelerator backend sees it (construction
    # only — nothing is dispatched under the patched backend name)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = Engine(model, params, **kw)
    m = eng.metrics()["paged_attn"]
    assert m["requested"] is None
    assert m["resolved"] == "kernel"
    assert m["dispatch"]["tree_verify_paged"] == "einsum"
    assert m["fallbacks"] == ["tree_verify_paged"]
    # every other family stays kernel
    others = {f: i for f, i in m["dispatch"].items()
              if f != "tree_verify_paged"}
    assert set(others.values()) == {"kernel"}


# ---------------------------------------------------------------------------
# 2. the single-page committed write
# ---------------------------------------------------------------------------


def test_write_token_pages_touches_one_token_row_only():
    """Unit pin of the write path: one committed token writes exactly
    one token row of exactly the page containing ``pos`` — every other
    byte of the pool (other pages AND the rest of that page) is
    untouched.  The old ``scatter_pages`` unroll rewrote the whole
    page from the gathered view; sentinel values prove the gather-free
    write never even reads those rows."""
    T, kv, dh = 8, 2, 4
    pages = (jnp.full((5, T, kv * dh), 7.0, jnp.float32),
             jnp.full((5, T, kv * dh), 7.0, jnp.float32))
    table = jnp.asarray([[2, 3, -1]], jnp.int32)
    k_new = jnp.ones((1, 1, kv, dh), jnp.float32) * 1.5
    v_new = jnp.ones((1, 1, kv, dh), jnp.float32) * 2.5
    # pos 13 -> page index 1 (table: page id 3), offset 5
    out_k, out_v = write_token_pages(
        pages, k_new, v_new, table, jnp.asarray([13], jnp.int32),
        jnp.ones((1,), bool))
    ok, ov = np.asarray(out_k), np.asarray(out_v)
    np.testing.assert_array_equal(ok[3, 5], 1.5 * np.ones(kv * dh))
    np.testing.assert_array_equal(ov[3, 5], 2.5 * np.ones(kv * dh))
    untouched_k = ok.copy()
    untouched_k[3, 5] = 7.0
    np.testing.assert_array_equal(untouched_k, 7.0 * np.ones_like(ok))
    # inactive rows and unmapped pages route to the trailing scratch
    sk, _ = write_token_pages(pages, k_new, v_new, table,
                              jnp.asarray([13], jnp.int32),
                              jnp.zeros((1,), bool))
    sk = np.asarray(sk)
    assert (sk[:4] == 7.0).all() and (sk[4, 5] == 1.5).all()
    uk, _ = write_token_pages(pages, k_new, v_new, table,
                              jnp.asarray([18], jnp.int32),  # page 2: -1
                              jnp.ones((1,), bool))
    uk = np.asarray(uk)
    assert (uk[:4] == 7.0).all() and (uk[4, 2] == 1.5).all()


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("T, kv, dh", [(8, 2, 4), (16, 3, 24), (128, 2, 64)])
def test_pool_rows_read_back_head_by_head_equal_the_dense_arena(T, kv, dh,
                                                                kv_dtype):
    """The stored form of a ``heads`` page (``generate.KVPages``): a token
    is one row of ``kv * dh`` values, KV head ``h`` at lanes ``[h * dh,
    (h + 1) * dh)``.  A pool written through ``write_token_pages`` (a
    page-aligned chunk as ONE page write, then decode tokens as single
    rows, two slots at different depths) is read back head by head
    against the dense arena written with the same values by
    ``update_cache_rows``: equal bit for bit (int8: the arena's rows
    quantised per head), whatever the row's width is against the 128
    lanes."""
    from tpudp.models.generate import update_cache_rows

    rng = np.random.default_rng(T + kv + dh)
    S, M, P = 2, 3, 7
    table = jnp.asarray([[4, 1, 6], [2, 5, -1]], jnp.int32)
    live = jnp.ones((S,), bool)
    dt = jnp.float32
    if kv_dtype == "int8":
        pages = (jnp.zeros((P + 1, T, kv * dh), jnp.int8),) * 2 + (
            jnp.ones((P + 1, T, kv), jnp.float32),) * 2
    else:
        pages = (jnp.zeros((P + 1, T, kv * dh), dt),) * 2
    arena_k = jnp.zeros((S, M * T, kv, dh), dt)
    arena_v = jnp.zeros((S, M * T, kv, dh), dt)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), dt)
    # a page-aligned prefill chunk a slot (scalar position, batch 1)
    for s, start in ((0, T), (1, 0)):
        k_new, v_new = draw(1, T, kv, dh), draw(1, T, kv, dh)
        pages = write_token_pages(pages, k_new, v_new, table[s:s + 1],
                                  jnp.int32(start), live[:1])
        arena_k = arena_k.at[s, start:start + T].set(k_new[0])
        arena_v = arena_v.at[s, start:start + T].set(v_new[0])
    # decode tokens: both slots at their own depths, a 2-token window
    # that crosses a page boundary in slot 0
    pos = jnp.asarray([2 * T - 1, T + 3], jnp.int32)
    k_new, v_new = draw(S, 2, kv, dh), draw(S, 2, kv, dh)
    pages = write_token_pages(pages, k_new, v_new, table, pos, live)
    arena_k = update_cache_rows(arena_k, k_new, pos)
    arena_v = update_cache_rows(arena_v, v_new, pos)

    written = {0: range(T, 2 * T + 1), 1: list(range(T)) + [T + 3, T + 4]}
    for buf, scale, arena in ((0, 2, arena_k), (1, 3, arena_v)):
        rows = np.asarray(pages[buf])
        assert rows.shape == (P + 1, T, kv * dh)
        for s, positions in written.items():
            for p in positions:
                page, off = int(table[s, p // T]), p % T
                for h in range(kv):
                    got = rows[page, off, h * dh:(h + 1) * dh]
                    want = np.asarray(arena[s, p, h])
                    if kv_dtype == "int8":
                        q8, sc = _quantize_kv(jnp.asarray(want))
                        np.testing.assert_array_equal(got, np.asarray(q8))
                        np.testing.assert_array_equal(
                            np.asarray(pages[scale])[page, off, h],
                            np.asarray(sc))
                    else:
                        np.testing.assert_array_equal(got, want)
    # and through the block table the flat rows ARE the arena's rows
    if kv_dtype is None:
        from tpudp.ops.paged_attention import page_tiles

        kt, vt = page_tiles(pages, table, dt, dh)  # (S, M, T, kv, dh)
        for s, positions in written.items():
            idx = np.asarray(list(positions))
            np.testing.assert_array_equal(
                np.asarray(kt.reshape(S, M * T, kv, dh))[s, idx],
                np.asarray(arena_k)[s, idx])
            np.testing.assert_array_equal(
                np.asarray(vt.reshape(S, M * T, kv, dh))[s, idx],
                np.asarray(arena_v)[s, idx])


def test_engine_decode_step_writes_exactly_one_page(model_and_params):
    """Engine-level pin of the same contract: across one pure-decode
    step, the only real pages whose bytes changed are the pages
    containing each active slot's committed position — one per slot —
    and within each only the one token row at ``pos % page_tokens``."""
    model, params = model_and_params
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 61, size=9 + 2 * i).astype(np.int32)
               for i in range(2)]
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=12)
    handles = [eng.submit(p, 6) for p in prompts]
    while not all(h.tokens for h in handles):  # prefills + first tokens
        eng.step()
    ms = eng._mstates[None]
    lens = eng._len.copy()
    before = np.asarray(ms.pool.pages.k).copy()
    eng.step()  # one pure decode step (queue empty, nothing prefilling)
    assert eng.stats["decode_steps"] >= 1
    after = np.asarray(ms.pool.pages.k)
    n_pages = ms.pool.num_pages
    changed = {p for p in range(n_pages + 1)
               if not np.array_equal(before[:, p], after[:, p])}
    expected = {int(ms.table[s, lens[s] // 8])
                for s in range(2) if lens[s] > 0}
    assert changed - {n_pages} == expected, (changed, expected)
    for s in range(2):
        if lens[s] == 0:
            continue
        page, off = int(ms.table[s, lens[s] // 8]), int(lens[s] % 8)
        rows = {t for t in range(8)
                if not np.array_equal(before[:, page, t],
                                      after[:, page, t])}
        assert rows == {off}, (s, rows, off)
    eng.run_until_complete()
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(_reference(model, params, p, 6),
                                      np.asarray(h.tokens))


# ---------------------------------------------------------------------------
# 3. the Pallas kernel vs the gather-based oracle
# ---------------------------------------------------------------------------


def _fragmented_fixture(kv_dtype=None, seed=2, cur=1, scalar_pos=None,
                        geometry=(8, 4, 2, 16)):
    """A pool + tables shaped like real COW traffic: slots 0 and 1 MAP
    THE SAME prefix pages (shared system prompt), diverge into private
    pages, and leave ``-1`` tail entries (clamping to scratch); slot 2
    is shallower.  ``cur`` widens the query window (the verify / prefill
    kernels' multi-token shape); ``scalar_pos`` swaps the per-slot depth
    vector for the prefill chunk's shared scalar depth; ``geometry`` is
    ``(page_tokens, heads, kv_heads, head_dim)``.  The pages are in the
    pool's stored form: a token's KV heads side by side in one row
    (``generate.KVPages`` / ``Int8Pages``).  Returns (pages tuple, table,
    pos, q, cfg-ish dims)."""
    rng = np.random.default_rng(seed)
    T, H, KV, DH = geometry
    S, M, P = 3, 4, 8
    kf = jnp.asarray(rng.standard_normal((P + 1, T, KV, DH)), jnp.float32)
    vf = jnp.asarray(rng.standard_normal((P + 1, T, KV, DH)), jnp.float32)
    rows = lambda x: x.reshape(P + 1, T, KV * DH)  # noqa: E731
    if kv_dtype == "int8":
        k8, ks = _quantize_kv(kf)
        v8, vs = _quantize_kv(vf)
        pages = (rows(k8), rows(v8), ks, vs)
    else:
        pages = (rows(kf), rows(vf))
    table = jnp.asarray(np.array([
        [0, 1, 2, -1],   # shared pages 0,1 + private divergence page 2
        [0, 1, 3, 4],    # same prefix, different COW page, one deeper
        [5, -1, -1, -1],  # shallow slot
    ], np.int32))
    # slot depths in pages of 8 at the default geometry: 2.1, 3.3, 0.5
    pos = (jnp.int32(scalar_pos) if scalar_pos is not None
           else jnp.asarray([2 * T + 1, 3 * T + 2, T // 2], jnp.int32))
    q = jnp.asarray(rng.standard_normal((S, cur, H, DH)), jnp.float32)
    return pages, table, pos, q, (S, M, T, H, KV, DH, P)


def _gather_oracle(pages, table, pos, q, dims):
    """gather_pages' math (one layer) + the dense grouped einsums —
    PR 13's exact gather→dense path, spelled as the oracle.  Window
    position ``j`` attends keys ``<= pos + j`` (the engine's
    write-before-attend contract), which covers decode (``cur == 1``)
    and the k+1 verify window (vector ``pos``: one contraction per
    position, like the dense vector-pos path) and the prefill chunk
    (scalar ``pos``: one batched contraction, like the dense
    scalar-pos path)."""
    import jax

    S, M, T, H, KV, DH, P = dims
    cur = q.shape[1]
    scalar = jnp.ndim(pos) == 0
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (S,))
    # exactly gather_pages' per-layer semantics: -1 clamps to scratch,
    # int8 dequantizes after the gather
    tbl = jnp.where(table >= 0, table, P)

    def grab(i):
        g = pages[i][tbl].reshape(S, M, T, KV, DH)  # rows back into heads
        if len(pages) == 4:
            g = (g.astype(jnp.float32)
                 * pages[i + 2][tbl][..., None]).astype(jnp.float32)
        return g.reshape(S, M * T, KV, DH)

    kc, vc = grab(0), grab(1)  # (S, M*T, KV, DH)
    G = H // KV
    qg = q.reshape(S, cur, KV, G, DH)
    scale = DH ** -0.5

    if scalar:
        # The dense scalar-pos (prefill) path is ONE batched contraction
        # over the whole window, and that is the math the einsum backend
        # must equal bitwise.  (Until jax 0.9.0 XLA:CPU happened to give
        # the per-position vmap below the same reduction; it no longer
        # does — 4e-7 apart — so the oracle mirrors the real dense form.)
        lg = jnp.einsum("bqkgd,bmkd->bkgqm", qg, kc) * scale
        vis = jnp.arange(M * T)[None, :] \
            <= (pos[0] + jnp.arange(cur))[:, None]
        lg = jnp.where(vis[None, None, None], lg, jnp.finfo(lg.dtype).min)
        pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
        return jnp.einsum("bkgqm,bmkd->bqkgd", pr, vc).reshape(
            S, cur, H, DH)

    def _attend(qj, pj):
        lg = jnp.einsum("bkgd,bmkd->bkgm", qj, kc) * scale
        vis = jnp.arange(M * T)[None, None, None, :] \
            <= pj[:, None, None, None]
        lg = jnp.where(vis, lg, jnp.finfo(lg.dtype).min)
        pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
        return jnp.einsum("bkgm,bmkd->bkgd", pr, vc)

    q_pos = pos[:, None] + jnp.arange(cur)
    out = jax.vmap(_attend, in_axes=(1, 1), out_axes=1)(qg, q_pos)
    return out.reshape(S, cur, H, DH)


def test_kernel_matches_gather_oracle_on_fragmented_tables():
    """Interpret-mode Pallas kernel vs the gather-based oracle across a
    fragmented table set (shared prefix pages, COW divergence pages,
    -1 scratch tails): online softmax vs the XLA chain agree within fp
    tolerance, and the exact einsum backend agrees BITWISE."""
    pages, table, pos, q, dims = _fragmented_fixture()
    oracle = np.asarray(_gather_oracle(pages, table, pos, q, dims))
    einsum = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True))
    np.testing.assert_array_equal(oracle, einsum)  # bit-exact backend
    kernel = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True,
        impl="kernel", interpret=True))
    np.testing.assert_allclose(oracle, kernel, rtol=2e-6, atol=2e-6)


def test_kernel_int8_in_kernel_dequant_tolerance():
    """int8 pages dequantize IN-KERNEL to the same values the einsum
    path dequantizes on gather: kernel ≈ int8 einsum within fp
    tolerance, and both track the fp oracle within the quantization
    bound."""
    pages8, table, pos, q, dims = _fragmented_fixture(kv_dtype="int8")
    pages_fp, *_ = _fragmented_fixture()
    fp_oracle = np.asarray(_gather_oracle(pages_fp, table, pos, q, dims))
    einsum8 = np.asarray(paged_attention(
        q, pages8, table, pos, dtype=jnp.float32, grouped=True))
    kernel8 = np.asarray(paged_attention(
        q, pages8, table, pos, dtype=jnp.float32, grouped=True,
        impl="kernel", interpret=True))
    np.testing.assert_allclose(einsum8, kernel8, rtol=2e-6, atol=2e-6)
    # quantization-level agreement with the fp math (loose by design)
    np.testing.assert_allclose(fp_oracle, kernel8, atol=0.05)
    assert np.max(np.abs(fp_oracle - kernel8)) > 0  # really quantized


#: (page_tokens, heads, kv_heads, head_dim): a GQA row of 72 values (no
#: multiple of the 128 lanes), pages of 16 and of 128 tokens, a row of
#: exactly one lane tile, and MHA (groups of one) at the cell's head size.
PAGE_GEOMETRIES = {"gqa_row72_t16": (16, 6, 3, 24),
                   "gqa_row128_t128": (128, 8, 2, 64),
                   "mha_row256_t16": (16, 4, 4, 64)}


@pytest.mark.parametrize("window", ["decode", "verify", "prefill"])
@pytest.mark.parametrize("geometry", list(PAGE_GEOMETRIES))
def test_kernels_find_a_head_inside_the_row_at_any_geometry(geometry,
                                                            window):
    """The three window shapes of the paged kernels over the flat page
    row, at geometries the default fixture does not reach: a head is a
    lane slice ``[h * dh, (h + 1) * dh)`` of the row whether the row is
    under, exactly, or over the 128 lanes, for 16- and 128-token pages,
    grouped (GQA) and not.  einsum ≡ oracle bitwise, kernel within fp
    tolerance."""
    T = PAGE_GEOMETRIES[geometry][0]
    cur, scalar = {"decode": (1, None), "verify": (3, None),
                   "prefill": (T, 2 * T)}[window]
    pages, table, pos, q, dims = _fragmented_fixture(
        cur=cur, scalar_pos=scalar, geometry=PAGE_GEOMETRIES[geometry])
    if scalar is not None:  # the window's page is mapped in every slot
        table = table.at[2].set(jnp.asarray([5, 6, 7, -1], jnp.int32))
    oracle = np.asarray(_gather_oracle(pages, table, pos, q, dims))
    einsum = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True))
    np.testing.assert_array_equal(oracle, einsum)
    kernel = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True,
        impl="kernel", interpret=True))
    np.testing.assert_allclose(oracle, kernel, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_verify_window_kernel_matches_gather_oracle(kv_dtype):
    """The flash-window kernel on the k+1 VERIFY shape (multi-token
    window, per-slot depth vector) vs the gather-based oracle on
    fragmented tables: per-row visibility ``k_pos <= pos + j`` agrees
    within fp tolerance; the fp einsum backend agrees with the oracle
    BITWISE (it is the engine's auto-fallback, so the fallback must be
    provably exact)."""
    pages, table, pos, q, dims = _fragmented_fixture(
        kv_dtype=kv_dtype, cur=3)
    oracle = np.asarray(_gather_oracle(pages, table, pos, q, dims))
    einsum = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True))
    if kv_dtype is None:
        np.testing.assert_array_equal(oracle, einsum)
    else:
        np.testing.assert_allclose(oracle, einsum, rtol=2e-6, atol=2e-6)
    kernel = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True,
        impl="kernel", interpret=True))
    np.testing.assert_allclose(oracle, kernel, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_prefill_chunk_kernel_matches_gather_oracle(kv_dtype):
    """The flash-prefill kernel shape — a page-wide chunk at a shared
    SCALAR depth, causal in-chunk masking — vs the same gather oracle.
    Every slot's window page is mapped (the engine preallocates pages
    under the window before dispatch; on a violating table the einsum
    path attends scratch garbage while the kernel skips the page, so
    the contract only defines mapped-window traffic), while ``-1``
    tails BEYOND the visibility edge stay in the table — masked
    garbage on both sides, so they must agree there too."""
    pages, table, pos, q, dims = _fragmented_fixture(
        kv_dtype=kv_dtype, cur=8, scalar_pos=16)
    table = table.at[2].set(jnp.asarray([5, 6, 7, -1], jnp.int32))
    oracle = np.asarray(_gather_oracle(pages, table, pos, q, dims))
    einsum = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True))
    if kv_dtype is None:
        np.testing.assert_array_equal(oracle, einsum)
    else:
        np.testing.assert_allclose(oracle, einsum, rtol=2e-6, atol=2e-6)
    kernel = np.asarray(paged_attention(
        q, pages, table, pos, dtype=jnp.float32, grouped=True,
        impl="kernel", interpret=True))
    np.testing.assert_allclose(oracle, kernel, rtol=2e-6, atol=2e-6)


def test_tree_kernel_matches_masked_dense_oracle():
    """The tree-verify kernel vs a dense masked reference on fragmented
    tables: cache visibility is STRICT ``< pos0`` (node 0 re-attends
    its own position from the window, not the pages) and in-window
    visibility is ancestor-or-self; the window K/V never touch the
    pool."""
    import jax

    from tpudp.ops.paged_attention import tree_paged_attention

    rng = np.random.default_rng(5)
    pages, table, pos0, _, dims = _fragmented_fixture()
    S, M, T, H, KV, DH, P = dims
    parents = (-1, 0, 1, 0, 3)
    t1 = len(parents)
    anc = np.zeros((t1, t1), np.int32)
    for j in range(t1):
        c = j
        while c != -1:
            anc[j, c] = 1
            c = parents[c]
    q = jnp.asarray(rng.standard_normal((S, t1, H, DH)), jnp.float32)
    wk = jnp.asarray(rng.standard_normal((S, t1, KV, DH)), jnp.float32)
    wv = jnp.asarray(rng.standard_normal((S, t1, KV, DH)), jnp.float32)

    tbl = jnp.where(table >= 0, table, P)
    kc = pages[0][tbl].reshape(S, M * T, KV, DH)
    vc = pages[1][tbl].reshape(S, M * T, KV, DH)
    kk = jnp.concatenate([kc, wk], axis=1)
    vv = jnp.concatenate([vc, wv], axis=1)
    G = H // KV
    qg = q.reshape(S, t1, KV, G, DH)
    lg = jnp.einsum("bjkgd,btkd->bjkgt", qg, kk) * (DH ** -0.5)
    cache_vis = jnp.arange(M * T)[None, :] < pos0[:, None]
    vis = jnp.concatenate(
        [jnp.broadcast_to(cache_vis[:, None], (S, t1, M * T)),
         jnp.broadcast_to((jnp.asarray(anc) > 0)[None], (S, t1, t1))],
        axis=2)
    lg = jnp.where(vis[:, :, None, None], lg, -1e30)
    pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
    ref = jnp.einsum("bjkgt,btkd->bjkgd", pr, vv).reshape(S, t1, H, DH)

    out = tree_paged_attention(q, pages, table, pos0, wk, wv,
                               tuple(map(tuple, anc)), dtype=jnp.float32,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-6, atol=2e-6)


def test_kernel_engine_decode_end_to_end(model_and_params):
    """Engine(paged_attn='kernel'): the single-token decode program
    dispatches the Pallas kernel (its OWN trace-count key — the pinned
    ``decode_paged_kernel`` program), prefill chunks run the
    flash-prefill kernel (``prefill_paged_kernel``), and greedy outputs
    match generate() on this geometry (the tiny model's argmax gaps
    dwarf the kernel's fp tolerance; the contract is tolerance-bounded,
    not bit-exact — exactly flash's)."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 61, size=9 + 3 * i).astype(np.int32)
               for i in range(2)]
    before_kernel = TRACE_COUNTS["decode_paged_kernel"]
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=12, paged_attn="kernel")
    handles = [eng.submit(p, 5) for p in prompts]
    eng.run_until_complete()
    assert TRACE_COUNTS["decode_paged_kernel"] > before_kernel
    for p, h in zip(prompts, handles):
        np.testing.assert_array_equal(_reference(model, params, p, 5),
                                      np.asarray(h.tokens))
    eng.check_paged()


def _run_traffic(model, params, paged_attn, **engine_kw):
    """One engine's worth of mixed traffic: greedy with a shared-prefix
    admission pattern, then a seeded-sampled request — the matrix the
    kernel-vs-einsum token-equality pins run over."""
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 61, size=16).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, 61, size=3 + i)
                               .astype(np.int32)]) for i in range(3)]
    eng = Engine(model, params, num_slots=2, max_len=48, prefill_chunk=8,
                 kv_pages=12, paged_attn=paged_attn, **engine_kw)
    greedy = [eng.submit(p, 5) for p in prompts]
    eng.run_until_complete()
    sampled = eng.submit(prompts[0], 6, temperature=0.9, top_k=12, seed=7)
    eng.run_until_complete()
    return [h.tokens for h in greedy] + [sampled.tokens]


def test_kernel_engine_verify_window_matches_einsum(model_and_params):
    """Engine(speculate_k=2, paged_attn='kernel'): the k+1 verify
    window runs the flash-window kernel (its own pinned
    ``verify_paged_kernel`` program) and greedy AND seeded-sampled
    tokens match the einsum twin exactly on this geometry."""
    model, params = model_and_params
    before = TRACE_COUNTS["verify_paged_kernel"]
    kern = _run_traffic(model, params, "kernel", speculate_k=2)
    assert TRACE_COUNTS["verify_paged_kernel"] > before
    assert _run_traffic(model, params, "einsum", speculate_k=2) == kern


def test_kernel_engine_fused_decode_matches_einsum(model_and_params):
    """Engine(decode_fuse=4, paged_attn='kernel'): every iteration of
    the fused ``lax.while_loop`` dispatches the paged-decode kernel
    (``fused_decode_paged_kernel``) and tokens match the einsum twin
    for greedy and sampled traffic."""
    model, params = model_and_params
    before = TRACE_COUNTS["fused_decode_paged_kernel"]
    kern = _run_traffic(model, params, "kernel", decode_fuse=4)
    assert TRACE_COUNTS["fused_decode_paged_kernel"] > before
    assert _run_traffic(model, params, "einsum", decode_fuse=4) == kern


@pytest.mark.slow
def test_kernel_engine_fused_spec_and_tree_match_einsum(model_and_params):
    """The remaining two kernel programs end-to-end (slow tier: each
    build compiles a draft model alongside the target): the fused
    speculative window (``fused_spec_paged_kernel``) and the static
    tree verify (``tree_verify_paged_kernel``) match their einsum
    twins token-for-token."""
    from tpudp.models.gpt2 import gpt2_small as _small
    from tpudp.serve.speculate import DraftModelDrafter

    model, params = model_and_params
    draft = _small(vocab_size=61, max_seq_len=96, num_layers=1,
                   num_heads=2, d_model=16)
    dparams = init_state(draft, make_optimizer(),
                         input_shape=(1, 8)).params

    def drafter():
        return DraftModelDrafter(draft, dparams)

    before = TRACE_COUNTS["fused_spec_paged_kernel"]
    kern = _run_traffic(model, params, "kernel", speculate_k=2,
                        decode_fuse=4, drafter=drafter())
    assert TRACE_COUNTS["fused_spec_paged_kernel"] > before
    assert _run_traffic(model, params, "einsum", speculate_k=2,
                        decode_fuse=4, drafter=drafter()) == kern

    before = TRACE_COUNTS["tree_verify_paged_kernel"]
    kern = _run_traffic(model, params, "kernel", speculate_k=2,
                        speculate_tree="fork2x2")
    assert TRACE_COUNTS["tree_verify_paged_kernel"] > before
    assert _run_traffic(model, params, "einsum", speculate_k=2,
                        speculate_tree="fork2x2") == kern


# ---------------------------------------------------------------------------
# 4. the committed ledger delta: the proof the gather is gone
# ---------------------------------------------------------------------------


def test_budget_ledger_strictly_below_pr13_gather_values():
    """The committed trace-lock budgets must sit STRICTLY below the
    PR 13 gather-based peak-live values for every paged program — the
    committed, reviewable proof that the per-step dense-view
    gather/scatter no longer exists in the traced hot paths."""
    with open(os.path.join(ROOT, "tools", "trace_lock.json")) as f:
        progs = json.load(f)["programs"]
    for prefix, pr13_peak in PR13_GATHER_PEAK_LIVE.items():
        names = [n for n in progs if n.startswith(prefix + "@")]
        assert names, f"{prefix} missing from the lock"
        now = progs[names[0]]["budget"]["peak_live_bytes"]
        assert 0 < now < pr13_peak, (
            f"{prefix}: peak_live_bytes {now} not strictly below the "
            f"PR 13 gather-based {pr13_peak}")
    # the kernel twin is pinned with a ledger of its own
    names = [n for n in progs
             if n.startswith("serve.decode_paged_kernel@")]
    assert names and progs[names[0]]["budget"]["peak_live_bytes"] > 0


#: The einsum twins' committed peak_live_bytes at the audit smoke
#: geometry (s2m32p6...) — the bar the kernel programs are held against.
#: Hardcoded like the gather pins above: regenerating the lock cannot
#: silently weaken the claim.  Re-derived deliberately under jax 0.9.0
#: (PR 21: the same engine code traces to different jaxprs than under
#: 0.4.37, where the pins read 178_806 / 181_934 / 174_665 / 193_206 /
#: 241_362 / 212_188).  ONE program lost the claim in that move: the
#: prefill kernel program's static peak now sits 1.6% ABOVE its einsum
#: twin's (194_132 vs 191_032), so it is pinned to that value instead of
#: asserted below — the static ledger was the only evidence behind the
#: kernel default (PR 17); real peak memory is the chip's to say
#: (ROADMAP S5).  Re-derived again by PR 35 (pages stored as flat token
#: rows): the einsum twins gather rows and split them back into heads,
#: and the static ledger counts that reshape as a second live tile set
#: (decode 193_142 -> 200_814, verify 196_270 -> 202_382, fused decode
#: 193_206 -> 200_878; XLA lowers it to a bitcast); every kernel program's
#: own value stayed as it was, to the byte.
EINSUM_TWIN_PEAK_LIVE = {
    "serve.decode_paged_kernel": ("serve.decode_paged", 200_814),
    "serve.verify_paged_kernel": ("serve.verify_paged", 202_382),
    "serve.prefill_paged_kernel": ("serve.prefill_paged", 191_032),
    "serve.fused_decode_paged_kernel": ("serve.fused_decode_paged",
                                        200_878),
    "serve.fused_spec_paged_kernel": ("serve.fused_spec_paged", 241_362),
    "serve.tree_verify_paged_kernel": ("serve.tree_verify_paged",
                                       212_188),
}
#: Kernel programs NOT below their einsum twin, pinned to their value.
KERNEL_PEAK_ABOVE_TWIN = {"serve.prefill_paged_kernel": 194_132}


def test_kernel_programs_peak_live_strictly_below_einsum_twins():
    """Every kernel program's committed peak_live_bytes sits STRICTLY
    below its einsum twin's (but for the pinned prefill exception above)
    — both the twin's live lock row and the hardcoded value above (so
    neither side of the comparison can drift without this test
    noticing).  This is the whole-hot-path memory claim: whole-pool
    committed writes + BlockSpec layer indexing mean the kernel builds
    never materialize a per-layer page slice, an attention score tile,
    or the einsum path's softmax intermediates at XLA level."""
    with open(os.path.join(ROOT, "tools", "trace_lock.json")) as f:
        progs = json.load(f)["programs"]

    def peak(prefix):
        names = [n for n in progs if n.startswith(prefix + "@")]
        assert names, f"{prefix} missing from the lock"
        return progs[names[0]]["budget"]["peak_live_bytes"]

    for kern, (eins, pinned) in EINSUM_TWIN_PEAK_LIVE.items():
        kp, ep = peak(kern), peak(eins)
        assert ep == pinned, (
            f"{eins}: committed peak_live_bytes {ep} drifted from the "
            f"pinned {pinned} — re-derive the pin (and the claim) "
            f"deliberately, not by regenerating the lock")
        if kern in KERNEL_PEAK_ABOVE_TWIN:
            assert kp == KERNEL_PEAK_ABOVE_TWIN[kern], (kern, kp)
            continue
        assert 0 < kp < ep, (
            f"{kern}: peak_live_bytes {kp} not strictly below the "
            f"einsum twin's {ep}")
