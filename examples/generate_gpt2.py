"""Generate text from a tpudp GPT-2 — the user-facing decode CLI.

Completes the inference surface around tpudp.models.generate (KV-cached
prefill+decode compiled as one program; tests/test_generate.py pins exact
greedy parity with the training forward): checkpoint restore, greedy /
temperature / top-k / top-p sampling, and beam search from one script.
The reference has no inference path at all (SURVEY.md — training scripts
only); this is a beyond-parity capability.

  # Greedy, random-init demo (no checkpoint needed; zero-egress friendly):
  python examples/generate_gpt2.py --layers 2 --d-model 64 --vocab 256 \
      --seq-len 128 --max-new-tokens 16 --platform cpu

  # Restore the newest checkpoint an examples/train run saved, sample:
  python examples/generate_gpt2.py --checkpoint-dir ckpt --layers 4 ... \
      --temperature 0.8 --top-p 0.9 --seed 7

  # Beam search:
  python examples/generate_gpt2.py ... --beam 4
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--family", default="gpt2", choices=["gpt2", "llama"],
                   help="decoder family of the (checkpointed) model; must "
                        "match the training run's --family")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA KV-head count (llama family; default = "
                        "--heads).  With --checkpoint-dir it is validated "
                        "against the checkpoint's wk projection width")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=None,
                   help="attention heads (default d_model//64).  With "
                        "--checkpoint-dir this MUST match the training "
                        "run: the head count is not recoverable from the "
                        "fused QKV params, and a wrong value reshapes "
                        "attention silently into garbage")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--dtype", choices=["float32", "bfloat16"],
                   default="float32")
    p.add_argument("--checkpoint-dir", type=str, default=None,
                   help="restore params from the newest step_N checkpoint "
                        "(as saved by the Part CLIs / Trainer); without it "
                        "the model is random-init (structure demo only, "
                        "loudly labeled)")
    p.add_argument("--prompt-ids", type=str, default=None,
                   help="comma-separated int token ids; default: first 8 "
                        "tokens of the training examples' synthetic corpus")
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy argmax; >0 samples at this temperature")
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0,
                   help="PRNG seed for temperature sampling")
    p.add_argument("--beam", type=int, default=None, metavar="W",
                   help="beam-search decode with width W instead of "
                        "greedy/sampling (mutually exclusive with "
                        "--temperature/--top-k/--top-p)")
    p.add_argument("--concurrent", type=int, default=None, metavar="N",
                   help="serve N copies of the request concurrently "
                        "through the tpudp.serve continuous-batching "
                        "engine (one slot each; sampled runs use seeds "
                        "seed..seed+N-1, greedy runs produce N identical "
                        "outputs — the engine-parity demo) and report "
                        "aggregate tokens/sec")
    p.add_argument("--platform", type=str, default=None)
    args = p.parse_args()

    if args.beam is not None and (args.temperature != 0.0
                                  or args.top_k is not None
                                  or args.top_p is not None):
        raise SystemExit("error: --beam is deterministic max-probability "
                         "search; drop --temperature/--top-k/--top-p")
    if args.concurrent is not None and args.beam is not None:
        raise SystemExit("error: --concurrent serves greedy/sampling "
                         "requests through the batching engine; beam "
                         "search decodes one request at a time — drop "
                         "one of --concurrent/--beam")
    if args.concurrent is not None and args.concurrent < 1:
        raise SystemExit(f"error: --concurrent must be >= 1 (got "
                         f"{args.concurrent})")
    if args.temperature < 0:
        raise SystemExit(f"error: --temperature must be >= 0 (got "
                         f"{args.temperature}); negative values would "
                         "sample an inverted distribution")
    if (args.top_k is not None or args.top_p is not None) \
            and args.temperature == 0.0:
        raise SystemExit("error: --top-k/--top-p shape the SAMPLING "
                         "distribution; set --temperature > 0 (greedy "
                         "argmax ignores them)")

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.gpt2 import GPT2, GPT2Config

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.family == "llama":
        from tpudp.models.llama import Llama, LlamaConfig

        try:
            cfg = LlamaConfig(
                vocab_size=args.vocab,
                max_seq_len=args.seq_len,
                num_layers=args.layers,
                num_heads=args.heads or max(args.d_model // 64, 1),
                num_kv_heads=args.kv_heads,
                d_model=args.d_model,
                dtype=dtype,
            )
        except ValueError as e:
            # LlamaConfig validates head/GQA geometry itself; surface it
            # as the CLI's error UX, not a traceback.
            raise SystemExit(f"error: {e}") from None
        model = Llama(cfg)
    else:
        if args.kv_heads is not None:
            raise SystemExit("error: --kv-heads (GQA) is a llama-family "
                             "option")
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
        # Params-only restore: no knowledge of the training run's
        # optimizer config needed (clip/skip wrappers change the
        # TrainState structure; decode only wants the weights).
        from tpudp.utils.checkpoint import latest_step_dir, restore_params

        latest = latest_step_dir(args.checkpoint_dir)
        if not latest:
            raise SystemExit(
                f"error: no step_N checkpoint under {args.checkpoint_dir!r} "
                "— generating from random weights would be misleading; "
                "drop --checkpoint-dir for an explicit random-init demo")
        params = restore_params(latest)
        # The restore is target-free, so a config/checkpoint mismatch
        # would otherwise decode silently with half the layers or a
        # clamped vocab — validate the structure against the CLI flags.
        # Family first: it IS recoverable (gpt2 has a wpe position table,
        # llama has none), and a mismatch would otherwise die on a raw
        # KeyError deep in the family-specific checks below.
        is_llama_ckpt = "wpe" not in params
        if (args.family == "llama") != is_llama_ckpt:
            raise SystemExit(
                f"error: checkpoint {latest} is a "
                f"{'llama' if is_llama_ckpt else 'gpt2'}-family checkpoint "
                f"(position table {'absent' if is_llama_ckpt else 'present'}"
                f"), but --family {args.family} was passed — pass the "
                "training run's --family")
        n_layers = sum(1 for k in params if k.startswith("h_"))
        wte = params["wte"]["embedding"]
        if n_layers != cfg.num_layers or wte.shape != (cfg.vocab_size,
                                                       cfg.d_model):
            raise SystemExit(
                f"error: checkpoint {latest} holds {n_layers} layers and "
                f"wte {tuple(wte.shape)}, but the flags describe "
                f"{cfg.num_layers} layers / vocab {cfg.vocab_size} x "
                f"d_model {cfg.d_model} — pass the training run's "
                "--layers/--d-model/--vocab")
        if args.family == "llama":
            # RoPE has no position table, so --seq-len only bounds decode
            # length here.  The llama-specific silent hazard is GQA
            # width: wk's output dim IS recoverable from the params, so a
            # wrong --kv-heads is catchable — catch it.
            dh = cfg.d_model // cfg.num_heads
            wk = params["h_0"]["attn"]["wk"]["kernel"]
            if wk.shape[1] != cfg.kv_heads * dh:
                raise SystemExit(
                    f"error: checkpoint {latest} holds wk width "
                    f"{wk.shape[1]} (= {wk.shape[1] // dh} KV heads at "
                    f"head dim {dh}), but the flags describe "
                    f"{cfg.kv_heads} KV heads — pass the training run's "
                    "--kv-heads/--heads")
            # (lm_head shape needs no separate check: any checkpoint this
            # CLI restores was written from one LlamaConfig, so the wte
            # check above already pinned d_model and vocab.)
        else:
            # wpe mismatch is the silent one: decoding past the trained
            # max_seq_len clamps the position-embedding gather (JAX clamp
            # semantics) — garbage output, no error (round-4 advisor).
            # Only a TABLE SHORTER than --seq-len is that hazard; a
            # --seq-len below the trained context is valid and safe (all
            # decoded positions stay inside the table — round-5 advisor:
            # the old exact-equality check rejected it needlessly).
            wpe = params["wpe"]["embedding"]
            if wpe.shape[0] < cfg.max_seq_len or wpe.shape[1] != cfg.d_model:
                raise SystemExit(
                    f"error: checkpoint {latest} holds wpe "
                    f"{tuple(wpe.shape)}, but the flags describe "
                    f"max_seq_len {cfg.max_seq_len} x d_model "
                    f"{cfg.d_model} — pass a --seq-len <= the training "
                    "run's (positions past the trained table would "
                    "silently clamp, not error) with its --d-model")
        # --heads is NOT recoverable from params (attention weights are
        # stored fused at d_model width), so a wrong value reshapes Q/K/V
        # silently into the wrong heads.  It must match the training run;
        # the head-dim divisibility check below is the only guard possible
        # from params alone.
        if cfg.d_model % cfg.num_heads:
            raise SystemExit(
                f"error: d_model {cfg.d_model} is not divisible by "
                f"num_heads {cfg.num_heads} — pass the training run's "
                "--heads")
        print(f"[generate] restored params from {latest}")
    else:
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, min(args.seq_len, 16)),
                                      jnp.int32))["params"]
        print("[generate] RANDOM-INIT weights (no --checkpoint-dir): "
              "output demonstrates the decode path, not a trained model")

    if args.prompt_ids:
        try:
            ids = [int(x) for x in args.prompt_ids.split(",")]
        except ValueError:
            raise SystemExit(
                f"error: --prompt-ids must be comma-separated integers "
                f"(got {args.prompt_ids!r})") from None
    else:
        # first tokens of the training examples' deterministic corpus
        # (same draw as train_gpt2.py's base sequence)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, args.vocab, size=4096)[:8].tolist()
    if not ids or any(not 0 <= i < args.vocab for i in ids):
        raise SystemExit(f"error: prompt ids must be in [0, {args.vocab})")
    prompt = jnp.asarray([ids], jnp.int32)

    if args.concurrent is not None:
        import math
        import time

        from tpudp.serve import Engine

        # A chunk that divides max_seq_len, so the Engine's round-down of
        # the arena never strands positions the plain decode path would
        # accept with identical flags (e.g. --seq-len 100 -> chunk 4,
        # arena 100 — not chunk 16, arena 96).
        engine = Engine(model, params, num_slots=args.concurrent,
                        prefill_chunk=math.gcd(16, cfg.max_seq_len))
        t0 = time.perf_counter()
        outs = engine.generate_many(
            [prompt[0]] * args.concurrent, args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed)
        dt = time.perf_counter() - t0
        mode = ("greedy" if args.temperature == 0 else
                f"T={args.temperature} top_k={args.top_k} "
                f"top_p={args.top_p} seeds={args.seed}..")
        print(f"[generate] concurrent={args.concurrent} {mode} "
              f"prompt={ids} "
              f"aggregate {args.concurrent * args.max_new_tokens / dt:.1f} "
              f"tokens/sec incl. compile (benchmarks/serve_bench.py "
              f"measures warm throughput)")
        for i, out in enumerate(outs):
            print(f"tokens[{i}]:", out[len(ids):].tolist())
        return

    if args.beam is not None:
        from tpudp.models.generate import beam_search

        seqs, scores = beam_search(model, params, prompt,
                                   args.max_new_tokens,
                                   beam_width=args.beam)
        print(f"[generate] beam={args.beam} "
              f"logprob={float(scores[0]):.4f} prompt={ids}")
        print("tokens:", np.asarray(seqs[0, len(ids):]).tolist())
        return

    from tpudp.models.generate import generate

    out = generate(model, params, prompt, args.max_new_tokens,
                   temperature=args.temperature, top_k=args.top_k,
                   top_p=args.top_p,
                   key=(jax.random.PRNGKey(args.seed)
                        if args.temperature > 0 else None))
    mode = ("greedy" if args.temperature == 0 else
            f"T={args.temperature} top_k={args.top_k} top_p={args.top_p} "
            f"seed={args.seed}")
    print(f"[generate] {mode} prompt={ids}")
    print("tokens:", np.asarray(out[0, len(ids):]).tolist())


if __name__ == "__main__":
    main()
