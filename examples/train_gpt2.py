"""Train a GPT-2 LM with tpudp — any parallelism rung from one script.

Beyond-parity example (BASELINE.json configs[4]: "GPT-2-small (124M) LM —
transformer grads all-reduced over a v5p pod slice").  With no egress the
corpus is a synthetic deterministic byte stream; point --tokens-file at a
binary file of uint16 token ids to train on real data.

  # DP over all devices (1-D mesh):
  python examples/train_gpt2.py --layers 4 --d-model 256 --seq-len 256

  # DP x SP over a 2-D mesh (ring attention over the seq axis):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/train_gpt2.py --platform cpu --mesh 2x4 --seq-parallel \
      --layers 2 --d-model 64 --seq-len 64 --steps 10

  # Megatron tensor parallelism (DP x TP), GPipe pipeline (DP x PP),
  # ZeRO-3 (FSDP), or MoE expert parallelism (DP x EP) — the --mesh
  # second axis becomes the strategy axis (model/pipe/expert):
  ... --mesh 4x2 --strategy tp
  ... --mesh 4x2 --strategy pp --microbatches 4
  ... --mesh 8x1 --strategy fsdp     # or zero1
  ... --mesh 4x2 --strategy ep
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--mesh", type=str, default=None,
                   help="'DxS' data x seq mesh shape (default: all devices x 1)")
    p.add_argument("--seq-parallel", action="store_true",
                   help="shard the sequence axis + ring attention")
    p.add_argument("--strategy", default="dp",
                   choices=["dp", "tp", "pp", "fsdp", "zero1", "ep"],
                   help="parallelism rung (tpudp.strategy); the --mesh "
                        "second axis is the strategy axis")
    p.add_argument("--microbatches", type=int, default=2,
                   help="pipeline microbatches (--strategy pp)")
    p.add_argument("--family", default="gpt2", choices=["gpt2", "llama"],
                   help="decoder family: gpt2 (learned positions, "
                        "LayerNorm, GELU, tied head) or llama (RoPE, "
                        "RMSNorm, SwiGLU, GQA via --kv-heads, untied "
                        "head).  llama supports dp/sp/tp/fsdp/zero1; "
                        "pp/ep, --loss-chunk and --sample are "
                        "gpt2-family paths")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA KV-head count (llama family; default = "
                        "--heads, i.e. MHA)")
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--d-model", type=int, default=768)
    p.add_argument("--heads", type=int, default=None)
    p.add_argument("--vocab", type=int, default=50_257)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--clip-norm", type=float, default=None,
                   help="global-norm gradient clipping (LM stabilizer)")
    p.add_argument("--skip-nonfinite", type=int, default=None, metavar="N",
                   help="skip optimizer updates whose gradients contain "
                        "NaN/Inf (transient bf16 overflow resilience); "
                        "after N consecutive bad steps the NaN propagates "
                        "so persistent instability fails loudly")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--dtype", choices=["float32", "bfloat16"], default="bfloat16")
    p.add_argument("--loss-chunk", type=int, default=None, metavar="N",
                   help="chunked vocabulary loss: compute the tied-head CE "
                        "over N-token chunks so the (batch*seq, vocab) "
                        "logits tensor is never materialized (DP path only)")
    p.add_argument("--sample", type=int, default=0, metavar="N",
                   help="after training, greedily generate N tokens from a "
                        "corpus prompt via the KV-cached decode path")
    p.add_argument("--tokens-file", type=str, default=None)
    p.add_argument("--save-checkpoint", type=str, default=None, metavar="DIR",
                   help="save the final TrainState to DIR/step_<steps> "
                        "(orbax; restorable by examples/generate_gpt2.py "
                        "--checkpoint-dir DIR with the matching --family)")
    p.add_argument("--platform", type=str, default=None)
    args = p.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    if args.save_checkpoint:
        # Fail fast on a missing orbax / unwritable DIR before
        # any compute is spent (tpudp/utils/checkpoint.py).
        from tpudp.utils.checkpoint import ensure_writable

        ensure_writable(args.save_checkpoint)
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # no-op on the CPU backend (smoke mode)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpudp.models.gpt2 import GPT2Config, GPT2
    from tpudp.train import (init_state, make_optimizer,
                             make_seq_parallel_train_step, make_train_step)

    devices = jax.devices()
    if args.mesh:
        d, s = (int(x) for x in args.mesh.split("x"))
    else:
        d, s = len(devices), 1
    mesh = Mesh(np.asarray(devices[: d * s]).reshape(d, s), ("data", "seq"))

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.seq_parallel and args.strategy != "dp":
        raise SystemExit("error: --seq-parallel is its own rung; drop "
                         "--strategy (or use --strategy dp)")
    if args.family == "llama":
        # pp drives the GPT-2 raw-param stage twins (embed_tokens/lm_head)
        # and ep the GPT-2 MoE MLP — both family-specific by design.
        if args.strategy in ("pp", "ep"):
            raise SystemExit(f"error: --strategy {args.strategy} is a "
                             "gpt2-family path (pipeline stage twins / MoE "
                             "MLP); use --family gpt2")
        if args.loss_chunk is not None:
            raise SystemExit("error: --loss-chunk needs the tied-embedding "
                             "head (gpt2 family)")
        if args.sample:
            raise SystemExit("error: --sample drives the GPT-2 KV-cached "
                             "decode path; use --family gpt2")
        from tpudp.models.llama import Llama, LlamaConfig

        model = Llama(LlamaConfig(
            vocab_size=args.vocab,
            max_seq_len=args.seq_len,
            num_layers=args.layers,
            num_heads=args.heads or max(args.d_model // 64, 1),
            num_kv_heads=args.kv_heads,
            d_model=args.d_model,
            dtype=dtype,
            attn_impl="ring" if args.seq_parallel else "dense",
            seq_axis="seq" if args.seq_parallel else None,
        ))
    else:
        if args.kv_heads is not None:
            raise SystemExit("error: --kv-heads (GQA) is a llama-family "
                             "option")
        moe = {}
        if args.strategy == "ep":
            moe = dict(mlp_impl="moe", num_experts=max(2 * s, 2),
                       capacity_factor=2.0, expert_axis="expert")
        cfg = GPT2Config(
            vocab_size=args.vocab,
            max_seq_len=args.seq_len,
            num_layers=args.layers,
            num_heads=args.heads or max(args.d_model // 64, 1),
            d_model=args.d_model,
            dtype=dtype,
            attn_impl="ring" if args.seq_parallel else "dense",
            seq_axis="seq" if args.seq_parallel else None,
            **moe,
        )
        model = GPT2(cfg)
    if args.skip_nonfinite is not None and args.strategy not in ("dp",
                                                                 "zero1"):
        # The skip decision needs cross-device-synchronized gradients at
        # tx.update (see make_optimizer docstring); tp/pp/fsdp/ep update
        # on shard-local grads and would silently desync.
        raise SystemExit("error: --skip-nonfinite supports the dp/zero1 "
                         f"strategies only (got {args.strategy!r})")
    tx = make_optimizer(learning_rate=args.lr, momentum=0.9, weight_decay=0.0,
                        clip_norm=args.clip_norm,
                        skip_nonfinite=args.skip_nonfinite)
    state = init_state(model, tx, input_shape=(1, min(args.seq_len, 16)))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    print(f"[{args.family}] params={n_params/1e6:.1f}M mesh=({d}x{s}) "
          f"seq_parallel={args.seq_parallel} seq_len={args.seq_len} "
          f"batch={args.batch_size} dtype={args.dtype}")

    if args.loss_chunk is not None and args.loss_chunk < 1:
        raise SystemExit(
            f"error: --loss-chunk must be >= 1 (got {args.loss_chunk})")
    if args.sample:
        # Validate up front — failing after the training run wastes it.
        if args.seq_parallel:
            raise SystemExit(
                "error: --sample needs the dense DP path (generate() does "
                "not drive ring attention); drop --seq-parallel")
        if args.sample + min(16, args.seq_len) > args.seq_len:
            raise SystemExit(
                f"error: --sample {args.sample} + prompt "
                f"{min(16, args.seq_len)} exceeds --seq-len {args.seq_len} "
                "(the model's position table)")
    if args.strategy != "dp":
        if args.loss_chunk is not None:
            raise SystemExit("error: --loss-chunk is a DP-path option")
        if args.sample:
            raise SystemExit("error: --sample needs the DP path (generate() "
                             "drives replicated params)")
        from tpudp.mesh import make_mesh_nd
        from tpudp.strategy import build_strategy

        axis = {"tp": "model", "pp": "pipe", "ep": "expert"}.get(args.strategy)
        if args.strategy in ("fsdp", "zero1"):
            smesh = make_mesh_nd({"data": d * s}, devices=devices[: d * s])
        else:
            smesh = make_mesh_nd({"data": d, axis: s},
                                 devices=devices[: d * s])
        options = {}
        if args.strategy == "tp":
            from tpudp.parallel.tensor import gpt2_tp_rules, llama_tp_rules

            options["rules"] = (llama_tp_rules() if args.family == "llama"
                                else gpt2_tp_rules())
        if args.strategy == "pp":
            options["n_microbatches"] = args.microbatches
        built = build_strategy(args.strategy, model, tx, smesh, state,
                               donate=False, **options)
        state, step = built.state, built.train_step
        sharding = built.shard_for(np.zeros((args.batch_size, args.seq_len)))
    elif args.seq_parallel:
        if args.loss_chunk is not None:
            raise SystemExit("error: --loss-chunk is a DP-path option")
        step = make_seq_parallel_train_step(model, tx, mesh, donate=False)
        sharding = NamedSharding(mesh, P("data", "seq"))
    else:
        mesh1d = Mesh(np.asarray(devices[:d]), ("data",))
        step = make_train_step(model, tx, mesh1d, "allreduce", donate=False,
                               loss_chunk=args.loss_chunk)
        sharding = NamedSharding(mesh1d, P("data"))

    if args.tokens_file:
        corpus = np.fromfile(args.tokens_file, dtype=np.uint16).astype(np.int32)
        corpus = corpus % args.vocab
    else:  # deterministic synthetic corpus with learnable n-gram structure
        rng = np.random.default_rng(0)
        base = rng.integers(0, args.vocab, size=4096)
        corpus = np.tile(base, 64).astype(np.int32)

    rng = np.random.default_rng(1)

    def sample_batch():
        starts = rng.integers(0, len(corpus) - args.seq_len - 1, args.batch_size)
        toks = np.stack([corpus[s0 : s0 + args.seq_len] for s0 in starts])
        tgts = np.stack([corpus[s0 + 1 : s0 + args.seq_len + 1] for s0 in starts])
        return (jax.device_put(toks, sharding), jax.device_put(tgts, sharding))

    prev_cum, t0 = 0.0, time.perf_counter()
    for it in range(1, args.steps + 1):
        tokens, targets = sample_batch()
        state, _ = step(state, tokens, targets)
        if it % args.log_every == 0:
            jax.block_until_ready(state.params)  # honest timing edge
            from tpudp.utils.watchdog import check_finite

            # Loud failure on divergence — with --skip-nonfinite this is
            # what fires once the consecutive-skip budget is exhausted and
            # the NaN finally propagates.
            cum = check_finite(float(state.loss_sum), step=it)
            dt = time.perf_counter() - t0
            tok_s = args.log_every * args.batch_size * args.seq_len / dt
            print(f"step {it}: loss {(cum - prev_cum) / args.log_every:.4f} "
                  f"({tok_s:,.0f} tok/s)")
            prev_cum, t0 = cum, time.perf_counter()

    if args.save_checkpoint:
        from tpudp.utils.checkpoint import save_checkpoint

        ckpt = save_checkpoint(
            os.path.join(args.save_checkpoint, f"step_{args.steps}"), state)
        print(f"[{args.family}] saved checkpoint {ckpt}")

    if args.sample:
        from tpudp.models.generate import generate

        prompt_len = min(16, args.seq_len)
        prompt = jnp.asarray(corpus[:prompt_len][None], jnp.int32)
        out = generate(model, jax.device_get(state.params), prompt,
                       args.sample)
        print(f"[gpt2] greedy sample (prompt {prompt_len} tokens): "
              f"{np.asarray(out[0, prompt_len:]).tolist()}")


if __name__ == "__main__":
    main()
