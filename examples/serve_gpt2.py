"""Serve concurrent GPT-2 generation requests — the tpudp.serve demo.

Runs the continuous-batching engine (slot KV arena + chunked prefill +
streaming decode; docs/SERVING.md) over a handful of requests with mixed
prompt lengths and sampling params, STREAMING the first request's tokens
as they land while the others decode in the same jitted step.  The
engine's greedy outputs are bit-identical to per-request
``tpudp.models.generate.generate`` (tests/test_serve.py referees), so
this demo is about throughput and interleaving, not different text.

  # Random-init demo (no checkpoint needed; zero-egress friendly):
  python examples/serve_gpt2.py --layers 2 --d-model 64 --vocab 256 \
      --seq-len 128 --requests 6 --num-slots 3 --platform cpu

  # Speculative decoding: n-gram prompt-lookup drafting, up to N+1
  # tokens per forward, outputs bit-identical (greedy) either way:
  python examples/serve_gpt2.py --speculate-k 4 --platform cpu

  # Prefix caching: requests sharing a prompt prefix copy cached KV
  # blocks instead of re-prefilling (outputs bit-identical either way):
  python examples/serve_gpt2.py --prefix-cache-blocks 64 --platform cpu

  # True paged attention: slots read KV through per-slot block tables
  # into one shared refcounted page pool — a shared-prefix hit is a
  # TABLE WRITE, not a copy (outputs bit-identical either way):
  python examples/serve_gpt2.py --paged 64 --platform cpu

  # Multi-tenant tiers: 2 high-priority requests ride over 6 low ones;
  # the high tier preempts low in-flight slots, every preempted request
  # resumes and finishes bit-identically (first listed = highest tier):
  python examples/serve_gpt2.py --tenants high:2,low:6 --platform cpu

  # Fused on-device decode loop: pure-decode steps run up to N decode
  # iterations in ONE lax.while_loop program — one host round trip per
  # window instead of per token (outputs bit-identical either way):
  python examples/serve_gpt2.py --decode-fuse 8 --platform cpu

  # Restore a train_gpt2.py checkpoint (params-only, like generate_gpt2):
  python examples/serve_gpt2.py --checkpoint-dir ckpt --layers 4 ...

Benchmark-grade numbers (Poisson arrivals, latency percentiles, the
sequential-generate() baseline) live in benchmarks/serve_bench.py; this
script is the minimal serving UX.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=None,
                   help="attention heads (default d_model//64); with "
                        "--checkpoint-dir it MUST match the training run")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32")
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="restore params from the newest step_N checkpoint "
                        "(random-init demo without it, loudly labeled)")
    p.add_argument("--requests", type=int, default=6,
                   help="number of generation requests to submit")
    p.add_argument("--num-slots", type=int, default=3,
                   help="engine slots = max concurrent in-flight requests")
    p.add_argument("--prefill-chunk", type=int, default=16)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 samples (per-request seeds)")
    p.add_argument("--speculate-k", type=int, default=0,
                   help="speculative decoding: draft up to K tokens per "
                        "step via n-gram prompt lookup and verify them "
                        "in one forward (0 = off; output is identical "
                        "either way for greedy decoding)")
    p.add_argument("--prefix-cache-blocks", type=int, default=0,
                   help="prefix caching: pool this many KV blocks so "
                        "requests sharing a prompt prefix copy cached "
                        "blocks instead of re-prefilling (0 = off; "
                        "output is identical either way)")
    p.add_argument("--paged", type=int, default=0, metavar="KV_PAGES",
                   help="true paged attention: replace the dense slot "
                        "arena with this many shared KV pool pages read "
                        "through per-slot block tables — prefix hits "
                        "become table writes with copy-on-write at the "
                        "divergence block (0 = off; output is identical "
                        "either way; mutually exclusive with "
                        "--prefix-cache-blocks)")
    p.add_argument("--kv-dtype", choices=["int8"], default=None,
                   help="with --paged: store page payloads quantized "
                        "int8 (~2x tokens per pool byte; outputs then "
                        "match within quantization tolerance, not "
                        "bit-exactly)")
    p.add_argument("--tenants", type=str, default=None,
                   help="multi-tenant demo: comma-separated name:count "
                        "pairs (e.g. high:2,low:6); each name becomes a "
                        "TenantClass, FIRST LISTED = HIGHEST priority, "
                        "and that many requests submit into it — the "
                        "high tier preempts low in-flight slots and "
                        "every preempted request resumes bit-identically "
                        "(overrides --requests)")
    p.add_argument("--decode-fuse", type=int, default=1,
                   help="fused on-device decode loop: run up to N decode "
                        "steps per host dispatch through one "
                        "lax.while_loop program on pure-decode scheduler "
                        "iterations (1 = off; output is identical either "
                        "way)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", type=str, default=None)
    args = p.parse_args()

    tenant_spec: list[tuple[str, int]] = []
    if args.tenants:
        for part in args.tenants.split(","):
            try:
                name, count = part.split(":")
                count = int(count)
            except ValueError:
                raise SystemExit(
                    f"error: --tenants wants name:count pairs "
                    f"(e.g. high:2,low:6), got {part!r}") from None
            if not name or count < 1:
                raise SystemExit(f"error: bad --tenants entry {part!r}")
            tenant_spec.append((name, count))
        if len({n for n, _ in tenant_spec}) != len(tenant_spec):
            raise SystemExit("error: duplicate tenant name in --tenants")

    if args.temperature < 0:
        raise SystemExit(f"error: --temperature must be >= 0 (got "
                         f"{args.temperature})")
    if args.requests < 1:
        raise SystemExit("error: --requests must be >= 1")
    if args.speculate_k < 0:
        raise SystemExit(f"error: --speculate-k must be >= 0 (got "
                         f"{args.speculate_k})")
    if args.prefix_cache_blocks < 0:
        raise SystemExit(f"error: --prefix-cache-blocks must be >= 0 "
                         f"(got {args.prefix_cache_blocks})")
    if args.paged < 0:
        raise SystemExit(f"error: --paged must be >= 0 (got {args.paged})")
    if args.kv_dtype and not args.paged:
        raise SystemExit("error: --kv-dtype requires --paged")
    if args.decode_fuse < 1:
        raise SystemExit(f"error: --decode-fuse must be >= 1 "
                         f"(got {args.decode_fuse})")

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.gpt2 import GPT2, GPT2Config
    from tpudp.serve import Engine, TenantClass

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    cfg = GPT2Config(
        vocab_size=args.vocab,
        max_seq_len=args.seq_len,
        num_layers=args.layers,
        num_heads=args.heads or max(args.d_model // 64, 1),
        d_model=args.d_model,
        dtype=dtype,
    )
    model = GPT2(cfg)
    if args.checkpoint_dir:
        from tpudp.utils.checkpoint import latest_step_dir, restore_params

        latest = latest_step_dir(args.checkpoint_dir)
        if not latest:
            raise SystemExit(
                f"error: no step_N checkpoint under "
                f"{args.checkpoint_dir!r} — drop --checkpoint-dir for an "
                "explicit random-init demo")
        params = restore_params(latest)
        print(f"[serve] restored params from {latest}")
    else:
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, min(args.seq_len, 16)),
                                      jnp.int32))["params"]
        print("[serve] RANDOM-INIT weights (no --checkpoint-dir): output "
              "demonstrates the serving path, not a trained model")

    import math

    # A chunk that divides --seq-len, so the Engine's round-down of the
    # arena never strands positions the flags say exist (same guard as
    # generate_gpt2.py --concurrent).
    # First listed --tenants class gets the highest priority tier.
    tenants = ({name: TenantClass(priority=len(tenant_spec) - 1 - i)
                for i, (name, _) in enumerate(tenant_spec)}
               if tenant_spec else None)
    engine = Engine(model, params, num_slots=args.num_slots,
                    prefill_chunk=math.gcd(args.prefill_chunk,
                                           args.seq_len),
                    speculate_k=args.speculate_k,
                    prefix_cache_blocks=args.prefix_cache_blocks,
                    kv_pages=args.paged, kv_dtype=args.kv_dtype,
                    decode_fuse=args.decode_fuse,
                    tenants=tenants)

    # Mixed-length prompts from the training examples' deterministic
    # corpus draw (same generator family as train_gpt2.py).
    rng = np.random.default_rng(args.seed)
    base = rng.integers(0, args.vocab, size=4096)
    # Without --tenants: --requests unclassed submits (tenant=None).
    # With it: the LOW tiers submit first and grab the slots, then the
    # higher tiers arrive and preempt — the demo shows the eviction.
    plan = ([(None, args.requests)] if not tenant_spec
            else list(reversed(tenant_spec)))
    handles = []
    t0 = time.perf_counter()
    i = 0
    for tname, count in plan:
        for _ in range(count):
            plen = 4 + (3 * i) % 13
            prompt = base[i * 16:i * 16 + plen].astype(np.int32)
            handles.append(engine.submit(
                prompt, args.max_new_tokens,
                temperature=args.temperature, seed=args.seed + i,
                tenant=tname))
            i += 1
        if tname is not None:
            engine.step()  # let this tier occupy slots before the next
    # Stream request 0 token by token (iterating drives the engine — the
    # other requests decode in the same batched step).
    streamed = []
    for tok in handles[0]:
        streamed.append(tok)
    print(f"[serve] request 0 streamed tokens: {streamed}")
    engine.run_until_complete()
    dt = time.perf_counter() - t0

    for i, h in enumerate(handles):
        tier = f", tenant={h.tenant}" if h.tenant is not None else ""
        pre = f", preempted x{h.preemptions}" if h.preemptions else ""
        print(f"[serve] request {i} (prompt {h.prompt.size} toks{tier}"
              f"{pre}): {h.tokens}")
    if tenants:
        for name, st in engine.tenant_stats.items():
            print(f"[serve] tenant {name}: submitted={st['submitted']} "
                  f"preempted={st['preempted']} tokens={st['tokens']}")
    total = sum(len(h.tokens) for h in handles)
    # Every fused loop iteration is one batched decode over the arena
    # (fused_steps counts them; 0 with --decode-fuse 1), so occupancy
    # stays meaningful when fusing replaces single decode steps.
    batched_steps = (engine.stats["decode_steps"]
                     + engine.stats["verify_steps"]
                     + engine.stats["fused_steps"])
    occ = (engine.stats["active_slot_steps"]
           / max(batched_steps * args.num_slots, 1))
    spec = ""
    if args.speculate_k:
        rate = engine.acceptance_rate
        spec = (f" | verify steps={engine.stats['verify_steps']} "
                f"draft acceptance="
                f"{'n/a' if rate is None else f'{rate:.2f}'}")
    if args.prefix_cache_blocks:
        spec += (f" | prefix hit tokens="
                 f"{engine.stats['prefix_hit_tokens']} "
                 f"(pool {engine.prefix_cache.used_blocks}"
                 f"/{args.prefix_cache_blocks} blocks)")
    if args.paged:
        pool = engine.page_pool
        spec += (f" | paged: hit tokens="
                 f"{engine.stats['prefix_hit_tokens']} via table "
                 f"writes, pool {pool.used_pages}/{pool.num_pages} "
                 f"pages ({engine.stats['page_pressure_vacates']} "
                 f"pressure vacates)")
    if args.decode_fuse > 1:
        spec += (f" | fused windows={engine.stats['fused_windows']} "
                 f"({engine.stats['fused_steps']} on-device decode "
                 f"steps — one host dispatch per window)")
    print(f"[serve] {len(handles)} requests, {total} tokens in {dt:.3f}s "
          f"({total / dt:.1f} tokens/sec incl. compile) | "
          f"decode steps={engine.stats['decode_steps']} "
          f"prefill chunks={engine.stats['prefill_chunks']} "
          f"slot occupancy={occ:.2f}{spec}")


if __name__ == "__main__":
    main()
