"""The trace-audit program registry: which jitted step programs are
pinned, and at which CPU smoke geometries.

Every program the serve/train hot loops dispatch is registered here
with a builder that reconstructs the EXACT argument shapes/dtypes the
runtime passes, at a geometry small enough to trace in milliseconds on
the CPU backend.  ``python -m tpudp.analysis audit`` traces each one
with ``jax.make_jaxpr`` (trace only — nothing compiles or runs),
fingerprints the jaxpr, and diffs against ``tools/trace_lock.json``.

If the runtime changes a program's argument shapes or its body, the
audit fails and names the program — that is the point: a trace change
in a pinned hot path must be an explicit, reviewed event
(``audit --update`` + a committed lockfile diff), never a silent
recompile/new-transfer regression discovered on the pod.

Geometries are deliberately tiny and FIXED (they are part of the lock
identity); they only need to exercise the same code paths the smoke
tests pin, not realistic sizes.

Heavy imports (jax, the models) happen inside the builders so the lint
half of the package stays stdlib-importable.
"""

from __future__ import annotations

#: Files whose edits can change a registered trace.  Their sha256
#: digests ride in the lockfile: `audit.sources_stale` compares them
#: with the standard library alone, so a stale lock is reported without
#: a jax import, and the tier-1 audit test requires them fresh so
#: `audit --update` provenance can't rot.
AUDIT_SOURCES = (
    "tpudp/serve/engine.py",
    "tpudp/serve/prefix_cache.py",
    "tpudp/serve/speculate.py",
    "tpudp/models/generate.py",
    "tpudp/models/gpt2.py",
    "tpudp/models/llama.py",
    "tpudp/models/pangu.py",
    "tpudp/models/laguna.py",
    "tpudp/models/moe.py",
    "tpudp/ops/sampling.py",
    "tpudp/ops/attention.py",
    "tpudp/ops/paged_attention.py",
    "tpudp/ops/losses.py",
    "tpudp/train.py",
    "tpudp/parallel/sync.py",
    "tpudp/parallel/ring.py",
    "tpudp/parallel/pipeline.py",
    "tpudp/parallel/schedule.py",
    "tpudp/analysis/programs.py",
)

#: Which registered program covers each TRACE_COUNTS key the serve
#: layer can bump.  tests/test_analysis.py derives the key set from the
#: actual ``TRACE_COUNTS[...] += 1`` sites by AST, so a new jit that
#: satisfies the unregistered-jit rule (it bumps a counter) but skips
#: this registry fails the suite instead of dodging the trace lock.
TRACE_COUNTER_PROGRAMS = {
    "decode_step": "serve.decode_step",
    "verify_step": "serve.verify_step",
    "prefill_chunk": "serve.prefill_chunk",
    "sample_row": "serve.sample_row",
    "fused_decode": "serve.fused_decode",
    "decode_paged": "serve.decode_paged",
    "decode_paged_kernel": "serve.decode_paged_kernel",
    "decode_paged_latent": "serve.decode_paged_latent",
    "decode_paged_latent_kernel": "serve.decode_paged_latent_kernel",
    "decode_paged_windowed": "serve.decode_paged_windowed",
    "verify_paged": "serve.verify_paged",
    "verify_paged_kernel": "serve.verify_paged_kernel",
    "prefill_paged": "serve.prefill_paged",
    "prefill_paged_kernel": "serve.prefill_paged_kernel",
    "prefill_paged_latent": "serve.prefill_paged_latent",
    "prefill_paged_latent_kernel": "serve.prefill_paged_latent_kernel",
    "prefill_paged_windowed": "serve.prefill_paged_windowed",
    "fused_decode_paged": "serve.fused_decode_paged",
    "fused_decode_paged_kernel": "serve.fused_decode_paged_kernel",
    "fused_spec_decode": "serve.fused_spec_decode",
    "fused_spec_paged": "serve.fused_spec_paged",
    "fused_spec_paged_kernel": "serve.fused_spec_paged_kernel",
    "tree_verify": "serve.tree_verify",
    "tree_verify_paged": "serve.tree_verify_paged",
    "tree_verify_paged_kernel": "serve.tree_verify_paged_kernel",
    "prefix_block_in": "prefix.copy_block_in",
    "prefix_block_out": "prefix.copy_block_out",
    "draft_model": "serve.draft_model",
}

#: Donated ARGUMENT positions per program (name before the ``@``),
#: mirroring the runtime ``donate_argnums`` at each build site.  The
#: serve programs take the weights first (the fused speculative ones
#: the draft weights second), never donated, so their positions sit
#: one (two) past the bound call-site positions the use-after-donation
#: rule tables in rules.py record.  The budget pass
#: (tpudp/analysis/budget.py) uses these for its donation-aware
#: peak-live-bytes sweep: a donated buffer's storage is reusable after
#: its last read, a non-donated one is resident for the whole call.
PROGRAM_DONATIONS = {
    "serve.decode_step": (1, 9),
    "serve.verify_step": (1, 10),
    "serve.prefill_chunk": (1,),
    "serve.fused_decode": (1, 12),
    "serve.fused_decode_stream": (1, 12),
    # Paged twins (Engine(kv_pages=N)): the shared page POOL donates in
    # place of the dense arena; the block table is host-authoritative
    # and never donated.  The kernel twins (Engine(paged_attn='kernel')
    # — the TPU default) share their einsum twins' signatures and
    # donation facts program-for-program.
    "serve.decode_paged": (1, 10),
    "serve.decode_paged_kernel": (1, 10),
    # the latent-attention expert family's two programs (LatentPages pool)
    "serve.decode_paged_latent": (1, 10),
    "serve.prefill_paged_latent": (1,),
    # ... and their twins through the latent_attn Mosaic call
    "serve.decode_paged_latent_kernel": (1, 10),
    "serve.prefill_paged_latent_kernel": (1,),
    # the window-and-full-attention expert family's two (WindowedPages:
    # both pools donate together; the two tables never)
    "serve.decode_paged_windowed": (1, 10),
    "serve.prefill_paged_windowed": (1,),
    "serve.verify_paged": (1, 11),
    "serve.verify_paged_kernel": (1, 11),
    "serve.prefill_paged": (1,),
    "serve.prefill_paged_kernel": (1,),
    "serve.fused_decode_paged": (1, 13),
    "serve.fused_decode_paged_stream": (1, 13),
    "serve.fused_decode_paged_kernel": (1, 13),
    # On-device speculation (Engine(speculate_k=k, decode_fuse=N,
    # drafter=DraftModelDrafter(...))): the fused draft→verify→accept
    # while_loop donates the target arena/pool and the counters — the
    # draft model's KV arena is carry-local scratch, never an argument.
    # The tree-verify window donates like verify_step (its paged twin's
    # accepted-path commit is what makes rejected branches zero-write).
    "serve.fused_spec_decode": (2, 14),
    "serve.fused_spec_decode_stream": (2, 14),
    "serve.fused_spec_paged": (2, 15),
    "serve.fused_spec_paged_stream": (2, 15),
    "serve.fused_spec_paged_kernel": (2, 15),
    "serve.tree_verify": (1, 10),
    "serve.tree_verify_paged": (1, 11),
    "serve.tree_verify_paged_kernel": (1, 11),
    "serve.sample_row": (),
    "serve.draft_model": (),
    "prefix.copy_block_in": (0,),
    "prefix.copy_block_out": (1,),
    "train.step_single": (0,),
    "train.step_dp_allreduce": (0,),
    "train.step_dp_ring": (0,),
    # SDC-fingerprint twins donate identically: the fingerprint reads
    # the post-update params/opt_state VALUES before the donated input
    # buffers are reused — same aliasing facts, two extra u32 words.
    "train.step_single_sdc": (0,),
    "train.step_dp_allreduce_sdc": (0,),
    "train.eval_step": (),
    # MPMD pipeline steps (tpudp/parallel/schedule.py): the TrainState
    # (params + flat-sharded optimizer shards) donates, like every train
    # step; tokens/targets are host-fed each call.  The budget ledger
    # pins each geometry's per-stage ppermute sequence and peak_live.
    "train.pp_1f1b": (0,),
    "train.pp_1f1b_int": (0,),
    "train.pp_eval": (),
}

# Serve smoke geometry: 2 slots x 32 arena positions, chunk 8, k=3,
# fused window 4 — the same scale tests/test_serve.py exercises.
# "pages" is the PAGED twin's pool budget: 6 real pages (48 tokens)
# + 1 scratch page — deliberately BELOW the 2x32 = 64 tokens of one
# dense arena, so the committed budget ledger states the capacity
# claim at the smoke geometry: a paged engine serving the SAME slots
# persists fewer KV bytes than one dense arena, and a 2-model paged
# engine (one shared pool) persists far less than two (see
# tests/test_paged.py's ledger assertion).
SERVE = dict(vocab=64, seq=64, layers=2, heads=2, d_model=32,
             slots=2, max_len=32, chunk=8, k=3, blocks=4, fuse=4,
             pages=6)
# Draft-model smoke geometry for the fused speculative programs: a
# 1-layer model whose max_seq_len covers max_len + k (the Engine
# eligibility bound `dcfg.max_seq_len >= max_len + speculate_k`), its
# weights the fused program's second argument, next to the target's.
DRAFT = dict(vocab=64, seq=64, layers=1, heads=2, d_model=16)
# Tree-verify smoke shape: fork2x2 (last token at node 0 → two branches
# of depth 2) — the smallest registered shape whose attention mask
# actually diverges (node 3 must NOT see nodes 1/2), matching
# tpudp.serve.speculate.TREE_SHAPES["fork2x2"].
TREE_PARENTS = (-1, 0, 1, 0, 3)
# Train smoke geometry: a tiny conv-free net over 8x8x3 inputs on the
# 8-virtual-device CPU mesh the tier-1 suite runs on.
TRAIN = dict(input=(8, 8, 3), classes=4, batch=8, devices=8)
# Pipeline smoke geometry (tpudp/parallel/schedule.py): the tiny GPT-2
# tests/test_schedule.py drives, on PP x DP sub-meshes of the same 8
# virtual devices.  Each (pp, dp, interleave) triple is its own pinned
# program — geometry is part of the unrolled schedule's compile key, so
# each gets its own ppermute sequence and budget ledger in the lock.
PIPELINE = dict(vocab=64, seq=32, layers=4, heads=2, d_model=32,
                batch=8, t=16, micro=2,
                geometries=((2, 2, 1), (4, 2, 1), (2, 2, 2)))


def _tiny_lm():
    import jax
    import jax.numpy as jnp

    from tpudp.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(vocab_size=SERVE["vocab"], max_seq_len=SERVE["seq"],
                     num_layers=SERVE["layers"], num_heads=SERVE["heads"],
                     d_model=SERVE["d_model"])
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    return cfg, params


def _tiny_draft():
    import jax
    import jax.numpy as jnp

    from tpudp.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config(vocab_size=DRAFT["vocab"], max_seq_len=DRAFT["seq"],
                     num_layers=DRAFT["layers"], num_heads=DRAFT["heads"],
                     d_model=DRAFT["d_model"])
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    return cfg, params


def _serve_args():
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.generate import KVCache

    s, m, k = SERVE["slots"], SERVE["max_len"], SERVE["k"]
    cfg, params = _tiny_lm()
    cache = KVCache.zeros(cfg, s, m)
    host = dict(
        last=np.zeros(s, np.int32), lens=np.zeros(s, np.int32),
        active=np.zeros(s, bool), temps=np.zeros(s, np.float32),
        topk=np.zeros(s, np.int32), topp=np.ones(s, np.float32),
        keys=jnp.zeros((s, 2), jnp.uint32),
        window=np.zeros((s, k + 1), np.int32),
        ndraft=np.zeros(s, np.int32),
        hist=np.zeros((s, m), np.int32),
        tree=np.zeros((s, len(TREE_PARENTS)), np.int32),
        chunk=np.zeros((1, SERVE["chunk"]), np.int32),
        budgets=np.zeros(s, np.int32),
        eos=np.full(s, -1, np.int32),
        # OBS_DEVICE_COUNTERS accumulator (tpudp.obs zero-sync device
        # counters) — the shape the engine passes every decode/verify/
        # fused call.
        counts=jnp.zeros((5,), jnp.float32),
    )
    return cfg, params, cache, host


def build_programs() -> dict:
    """name → (fn, args): every pinned program, ready for
    ``jax.make_jaxpr(fn)(*args)``.  Insertion order is the lockfile
    order."""
    import numpy as np

    from tpudp.models.generate import KVCache, page_type

    programs: dict[str, tuple] = {}

    # -- serve step programs (weights-first jits, engine.py) -----------
    from tpudp.serve import engine as _engine

    cfg, params, cache, h = _serve_args()
    dcfg, dparams = _tiny_draft()
    (decode, verify, prefill, fused, fused_spec, tree_verify,
     decode_paged, verify_paged, prefill_paged, fused_paged,
     fused_spec_paged, tree_paged) = _engine._build_steps(
        cfg, "einsum", dcfg)
    geo = f"s{SERVE['slots']}m{SERVE['max_len']}"
    programs[f"serve.decode_step@{geo}"] = (
        decode, (params, cache, h["last"], h["lens"], h["active"], h["temps"],
                 h["topk"], h["topp"], h["keys"], h["counts"]))
    programs[f"serve.verify_step@{geo}k{SERVE['k']}"] = (
        verify, (params, cache, h["window"], h["lens"], h["active"], h["ndraft"],
                 h["temps"], h["topk"], h["topp"], h["keys"],
                 h["counts"]))
    programs[f"serve.prefill_chunk@{geo}c{SERVE['chunk']}"] = (
        prefill, (params, cache, np.int32(0), h["chunk"], np.int32(0),
                  np.int32(SERVE["chunk"] - 1)))
    # Fused decode window, both variants: the stream twin pins the
    # ordered io_callback in its host-callback census, so ANY change to
    # the callback count inside the loop (a new host round trip — the
    # exact regression this program exists to prevent) fails the audit
    # naming the program.
    fused_args = (params, cache, h["last"], h["lens"], h["active"], h["temps"],
                  h["topk"], h["topp"], h["keys"], h["budgets"], h["eos"],
                  np.int32(-1), h["counts"])
    import functools

    programs[f"serve.fused_decode@{geo}n{SERVE['fuse']}"] = (
        functools.partial(fused, n_steps=SERVE["fuse"], stream=False),
        fused_args)
    programs[f"serve.fused_decode_stream@{geo}n{SERVE['fuse']}"] = (
        functools.partial(fused, n_steps=SERVE["fuse"], stream=True),
        fused_args)
    # On-device speculation (ISSUE 16): the fused draft→verify→accept
    # while_loop — both models' weights riding in, the slot histories
    # in, k+1-wide verify windows and per-slot PRNG chains advanced
    # in-carry.  Pinned in BOTH stream variants like the plain fused
    # window: a new host callback inside the speculative loop (the
    # regression class this whole program deletes) fails the audit by
    # name.
    spec_args = (params, dparams, cache, h["hist"], h["last"], h["lens"], h["active"],
                 h["temps"], h["topk"], h["topp"], h["keys"],
                 h["budgets"], h["eos"], np.int32(-1), h["counts"])
    sgeo = f"{geo}k{SERVE['k']}n{SERVE['fuse']}"
    programs[f"serve.fused_spec_decode@{sgeo}"] = (
        functools.partial(fused_spec, n_draft_k=SERVE["k"],
                          n_steps=SERVE["fuse"], stream=False), spec_args)
    programs[f"serve.fused_spec_decode_stream@{sgeo}"] = (
        functools.partial(fused_spec, n_draft_k=SERVE["k"],
                          n_steps=SERVE["fuse"], stream=True), spec_args)
    # The speculative TREE window (Engine(speculate_tree=...)): one
    # tree-masked forward over fork2x2's five nodes, accepted-path-only
    # commit.  The parents tuple is static (part of the compile key and
    # the lock identity, like n_steps on the fused window).
    tgeo = f"{geo}t{len(TREE_PARENTS)}"
    tree_args = (params, cache, h["tree"], h["lens"], h["active"], h["ndraft"],
                 h["temps"], h["topk"], h["topp"], h["keys"], h["counts"])
    programs[f"serve.tree_verify@{tgeo}"] = (
        functools.partial(tree_verify, parents=TREE_PARENTS), tree_args)
    # Paged twins (Engine(kv_pages=N)): same math read through per-slot
    # block tables into ONE shared page pool (+1 trailing scratch page)
    # — since the gather-free rework, THROUGH the table inside the
    # attention contraction (tpudp.ops.paged_attention), with the new
    # token's K/V committed straight into its page.  Pinning them locks
    # the indirection — a new host transfer or callback inside the
    # paged hot loop fails the audit by name — and gives the budget
    # pass the paged programs' peak_live_bytes for the capacity ledger
    # (tests pin the gather-free values strictly below the PR 13
    # gather-based ones).
    n_pages = SERVE["pages"]
    pool = page_type(cfg).zeros(cfg, n_pages + 1, SERVE["chunk"])
    table = np.zeros((SERVE["slots"], SERVE["max_len"] // SERVE["chunk"]),
                     np.int32)
    pgeo2 = f"{geo}p{n_pages}"
    programs[f"serve.decode_paged@{pgeo2}"] = (
        decode_paged, (params, pool, table, h["last"], h["lens"], h["active"],
                       h["temps"], h["topk"], h["topp"], h["keys"],
                       h["counts"]))
    programs[f"serve.verify_paged@{pgeo2}k{SERVE['k']}"] = (
        verify_paged, (params, pool, table, h["window"], h["lens"], h["active"],
                       h["ndraft"], h["temps"], h["topk"], h["topp"],
                       h["keys"], h["counts"]))
    programs[f"serve.prefill_paged@{pgeo2}c{SERVE['chunk']}"] = (
        prefill_paged, (params, pool, table[0], h["chunk"], np.int32(0),
                        np.int32(SERVE["chunk"] - 1)))
    # Both stream variants, like the dense fused window: the stream
    # twin pins the ordered io_callback in its census, so a host
    # round-trip change inside the PAGED loop fails the audit by name
    # too (kv_pages + fuse_stream is a legal engine configuration).
    fused_paged_args = (
        params, pool, table, h["last"], h["lens"], h["active"], h["temps"],
        h["topk"], h["topp"], h["keys"], h["budgets"], h["eos"],
        np.int32(-1), h["counts"])
    programs[f"serve.fused_decode_paged@{pgeo2}n{SERVE['fuse']}"] = (
        functools.partial(fused_paged, n_steps=SERVE["fuse"], stream=False),
        fused_paged_args)
    programs[f"serve.fused_decode_paged_stream@{pgeo2}n{SERVE['fuse']}"] = (
        functools.partial(fused_paged, n_steps=SERVE["fuse"], stream=True),
        fused_paged_args)
    # Paged speculative twins: same fused draft/verify/accept carry and
    # tree-verify math through the block-table indirection — the tree
    # twin's accepted-path commit is the zero-write-on-reject claim the
    # byte-diff test pins, so its trace (and any new transfer in it) is
    # locked here.
    spec_paged_args = (
        params, dparams, pool, table, h["hist"], h["last"], h["lens"], h["active"],
        h["temps"], h["topk"], h["topp"], h["keys"], h["budgets"],
        h["eos"], np.int32(-1), h["counts"])
    programs[f"serve.fused_spec_paged@{pgeo2}k{SERVE['k']}n{SERVE['fuse']}"] = (
        functools.partial(fused_spec_paged, n_draft_k=SERVE["k"],
                          n_steps=SERVE["fuse"], stream=False),
        spec_paged_args)
    programs[f"serve.fused_spec_paged_stream@{pgeo2}k{SERVE['k']}n{SERVE['fuse']}"] = (
        functools.partial(fused_spec_paged, n_draft_k=SERVE["k"],
                          n_steps=SERVE["fuse"], stream=True),
        spec_paged_args)
    programs[f"serve.tree_verify_paged@{pgeo2}t{len(TREE_PARENTS)}"] = (
        functools.partial(tree_paged, parents=TREE_PARENTS),
        (params, pool, table, h["tree"], h["lens"], h["active"], h["ndraft"],
         h["temps"], h["topk"], h["topp"], h["keys"], h["counts"]))
    # The Pallas kernel twins (Engine(paged_attn='kernel') — the TPU
    # default): same signatures/donations as their einsum twins
    # program-for-program, but the attention contractions run the
    # hot-path kernels — the paged-decode kernel, the flash-window
    # verify/prefill kernel, kernels dispatched inside the fused loop
    # bodies, and the tree-verify kernel — each with the block table as
    # scalar prefetch.  Pinned so a kernel-body change (or a new
    # callback/transfer around one) is a named, reviewed event like
    # every other hot-path trace.  The audit captures on forced CPU, so
    # the kernels trace in interpret mode — host-independent like the
    # rest of the lock.
    (_, verify_k, prefill_k, fused_k, fused_spec_k,
     tree_k) = _engine._build_steps(cfg, "kernel", dcfg)[6:]
    decode_paged_kernel = _engine._build_steps(cfg, "kernel")[6]
    programs[f"serve.decode_paged_kernel@{pgeo2}"] = (
        decode_paged_kernel,
        (params, pool, table, h["last"], h["lens"], h["active"], h["temps"],
         h["topk"], h["topp"], h["keys"], h["counts"]))
    programs[f"serve.verify_paged_kernel@{pgeo2}k{SERVE['k']}"] = (
        verify_k, (params, pool, table, h["window"], h["lens"], h["active"],
                   h["ndraft"], h["temps"], h["topk"], h["topp"],
                   h["keys"], h["counts"]))
    programs[f"serve.prefill_paged_kernel@{pgeo2}c{SERVE['chunk']}"] = (
        prefill_k, (params, pool, table[0], h["chunk"], np.int32(0),
                    np.int32(SERVE["chunk"] - 1)))
    programs[f"serve.fused_decode_paged_kernel@{pgeo2}n{SERVE['fuse']}"] = (
        functools.partial(fused_k, n_steps=SERVE["fuse"], stream=False),
        fused_paged_args)
    programs[
        f"serve.fused_spec_paged_kernel@{pgeo2}k{SERVE['k']}n{SERVE['fuse']}"
    ] = (functools.partial(fused_spec_k, n_draft_k=SERVE["k"],
                           n_steps=SERVE["fuse"], stream=False),
         spec_paged_args)
    programs[f"serve.tree_verify_paged_kernel@{pgeo2}t{len(TREE_PARENTS)}"] = (
        functools.partial(tree_k, parents=TREE_PARENTS),
        (params, pool, table, h["tree"], h["lens"], h["active"], h["ndraft"],
         h["temps"], h["topk"], h["topp"], h["keys"], h["counts"]))

    # The latent-attention expert family (tpudp/models/pangu.py): its two
    # programs over a LatentPages pool, at the same smoke geometry.  The
    # widths are no multiples of 128, so the expert layer traces its
    # plain loop (the kernels have lowering tests of their own).
    import jax
    import jax.numpy as jnp

    from tpudp.models.generate import LatentPages
    from tpudp.models.pangu import Pangu, PanguConfig

    lcfg = PanguConfig(vocab_size=SERVE["vocab"],
                       max_position_embeddings=SERVE["seq"],
                       num_experts_routed=8)
    lparams = Pangu(lcfg).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    lsteps = _engine._build_steps(lcfg, "einsum")
    lpool = LatentPages.zeros(lcfg, n_pages + 1, SERVE["chunk"])
    programs[f"serve.decode_paged_latent@{pgeo2}"] = (
        lsteps[6], (lparams, lpool, table, h["last"], h["lens"], h["active"],
                    h["temps"], h["topk"], h["topp"], h["keys"],
                    h["counts"]))
    programs[f"serve.prefill_paged_latent@{pgeo2}c{SERVE['chunk']}"] = (
        lsteps[8], (lparams, lpool, table[0], h["chunk"], np.int32(0),
                    np.int32(SERVE["chunk"] - 1)))
    # ... and their kernel twins (Engine(paged_attn='kernel'), the default
    # on an accelerator): absorbed attention as the latent_attn Mosaic
    # call, traced in interpret mode like the other kernel programs.
    lksteps = _engine._build_steps(lcfg, "kernel")
    programs[f"serve.decode_paged_latent_kernel@{pgeo2}"] = (
        lksteps[6], programs[f"serve.decode_paged_latent@{pgeo2}"][1])
    programs[
        f"serve.prefill_paged_latent_kernel@{pgeo2}c{SERVE['chunk']}"] = (
        lksteps[8],
        programs[f"serve.prefill_paged_latent@{pgeo2}c{SERVE['chunk']}"][1])

    # The window-and-full-attention expert family (tpudp/models/laguna.py):
    # its two programs over a WindowedPages pool and the pair of tables,
    # at the same smoke geometry (einsum: the interpreted kernels have
    # tests of their own).
    from tpudp.models.generate import WindowedPages
    from tpudp.models.laguna import Laguna, LagunaConfig

    wcfg = LagunaConfig(vocab_size=SERVE["vocab"],
                        max_position_embeddings=SERVE["seq"],
                        sliding_window=2 * SERVE["chunk"])
    wparams = Laguna(wcfg).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]
    wsteps = _engine._build_steps(wcfg, "einsum")
    wpool = WindowedPages.zeros(wcfg, n_pages + 1, SERVE["chunk"],
                                3 * SERVE["slots"] + 1)
    programs[f"serve.decode_paged_windowed@{pgeo2}"] = (
        wsteps[6], (wparams, wpool, (table, table), h["last"], h["lens"],
                    h["active"], h["temps"], h["topk"], h["topp"],
                    h["keys"], h["counts"]))
    programs[f"serve.prefill_paged_windowed@{pgeo2}c{SERVE['chunk']}"] = (
        wsteps[8], (wparams, wpool, (table[0], table[0]), h["chunk"],
                    np.int32(0), np.int32(SERVE["chunk"] - 1)))

    programs["serve.sample_row@v%d" % SERVE["vocab"]] = (
        _engine._sample_row,
        (np.zeros((1, SERVE["vocab"]), np.float32), np.float32(0.0),
         np.int32(0), np.float32(1.0), h["keys"][0]))

    # -- prefix-cache block copies (prefix_cache.py) -------------------
    from tpudp.serve import prefix_cache as _prefix

    pool = KVCache.zeros(cfg, SERVE["blocks"], SERVE["chunk"])
    pgeo = f"{geo}b{SERVE['blocks']}"
    programs[f"prefix.copy_block_in@{pgeo}"] = (
        _prefix.copy_block_in,
        (cache, pool, np.int32(0), np.int32(0), np.int32(0)))
    programs[f"prefix.copy_block_out@{pgeo}"] = (
        _prefix.copy_block_out,
        (cache, pool, np.int32(0), np.int32(0), np.int32(0)))

    # -- speculative drafter program (speculate.py) --------------------
    from tpudp.serve.speculate import _draft_greedy

    ctx = 16
    programs[f"serve.draft_model@ctx{ctx}k{SERVE['k']}"] = (
        lambda p, t, n: _draft_greedy(cfg, p, t, n, SERVE["k"]),
        (params, np.zeros((1, ctx), np.int32), np.int32(8)))

    # -- train/eval step programs (train.py) ---------------------------
    import flax.linen as nn
    import jax.numpy as jnp

    from tpudp.mesh import make_mesh
    from tpudp.train import (init_state, make_eval_step, make_optimizer,
                             make_train_step)

    class _TinyNet(nn.Module):
        """Minimal image classifier — enough structure for the fused
        fwd+loss+bwd+sync+update step to have its real shape."""

        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16, name="fc1")(x))
            return nn.Dense(TRAIN["classes"], name="fc2")(x)

    model = _TinyNet()
    tx = make_optimizer()
    state = init_state(model, tx, input_shape=(1, *TRAIN["input"]))
    b = TRAIN["batch"]
    images = jnp.zeros((b, *TRAIN["input"]), jnp.float32)
    labels = jnp.zeros((b,), jnp.int32)
    weights = jnp.ones((b,), jnp.float32)

    programs["train.step_single@tiny"] = (
        make_train_step(model, tx, None), (state, images, labels))
    mesh = make_mesh(TRAIN["devices"])
    for sync in ("allreduce", "ring"):
        programs[f"train.step_dp_{sync}@mesh{TRAIN['devices']}"] = (
            make_train_step(model, tx, mesh, sync), (state, images, labels))
    programs[f"train.eval_step@mesh{TRAIN['devices']}"] = (
        make_eval_step(model, mesh), (state, images, labels, weights))
    # SDC-fingerprint twins (tpudp/sdc.py): the SAME fused step with
    # the TrainState's ``sdc_fp`` slot allocated (init_state(
    # track_sdc=True)) — the u32 checksum of the post-update params +
    # optimizer bits rides the step, structure-gated at trace time.
    # Pinned separately so growth in the corruption detector's traced
    # footprint is a lockfile diff, not silent drift.
    sdc_state = init_state(model, tx, input_shape=(1, *TRAIN["input"]),
                           track_sdc=True)
    programs["train.step_single_sdc@tiny"] = (
        make_train_step(model, tx, None), (sdc_state, images, labels))
    programs[f"train.step_dp_allreduce_sdc@mesh{TRAIN['devices']}"] = (
        make_train_step(model, tx, mesh, "allreduce"),
        (sdc_state, images, labels))

    # -- MPMD pipeline programs (parallel/schedule.py) ------------------
    import jax

    from tpudp.mesh import make_mesh_nd
    from tpudp.models.gpt2 import gpt2_small
    from tpudp.parallel.schedule import (make_pipeline_eval_step,
                                         make_pipeline_train_step)

    lm = gpt2_small(vocab_size=PIPELINE["vocab"],
                    max_seq_len=PIPELINE["seq"],
                    num_layers=PIPELINE["layers"],
                    num_heads=PIPELINE["heads"],
                    d_model=PIPELINE["d_model"])
    lm_tx = make_optimizer(learning_rate=0.01)
    lm_state = init_state(lm, lm_tx, input_shape=(1, 8))
    toks = jnp.zeros((PIPELINE["batch"], PIPELINE["t"]), jnp.int32)
    lm_w = jnp.ones((PIPELINE["batch"],), jnp.float32)
    eval_geo = None
    for pp, dp, il in PIPELINE["geometries"]:
        pp_mesh = make_mesh_nd({"data": dp, "pipe": pp},
                               devices=jax.devices()[: dp * pp])
        pp_state, pp_step = make_pipeline_train_step(
            lm, lm_tx, pp_mesh, lm_state,
            n_microbatches=PIPELINE["micro"], interleave=il)
        fam = "train.pp_1f1b_int" if il > 1 else "train.pp_1f1b"
        geo = (f"pp{pp}dp{dp}m{PIPELINE['micro']}"
               + (f"v{il}" if il > 1 else "")
               + f"L{PIPELINE['layers']}")
        programs[f"{fam}@{geo}"] = (pp_step, (pp_state, toks, toks))
        if eval_geo is None:
            # Eval twin once, at the first (smallest) geometry: the
            # forward-only tick program shares its transport with the
            # train program, so one pin covers the family.
            eval_geo = (make_pipeline_eval_step(
                lm, pp_mesh, pp_state, n_microbatches=PIPELINE["micro"],
                interleave=il), (pp_state, toks, toks, lm_w))
            programs[f"train.pp_eval@{geo}"] = eval_geo
    return programs
