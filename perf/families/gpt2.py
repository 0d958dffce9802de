"""GPT-2 family: how a configuration file becomes the system's model, and
the plain reference it is held to.

The reference is the published architecture in straightforward
``jax.numpy`` and float32 (Radford et al. 2019; HF ``GPT2LMHeadModel``):
learned token and position embeddings, pre-LayerNorm blocks of causal
multi-head attention and a GELU (tanh form, ``gelu_new``) MLP of width
``4 x n_embd``, a final LayerNorm and the tied embedding as output head.
No kernel, no cache, no batching tricks.  It reads the same parameter
tree as ``tpudp/models/gpt2.py`` and shares no code with it.
"""

from __future__ import annotations

from perf.harness import flops


def build_model(config: dict, *, attn_impl: str = "dense"):
    """The system's model at the file's sizes."""
    import jax.numpy as jnp

    from tpudp.models.gpt2 import GPT2, GPT2Config

    return GPT2(GPT2Config(
        vocab_size=config["vocab_size"], max_seq_len=config["n_positions"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        d_model=config["n_embd"], ln_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(config["compute_dtype"]), attn_impl=attn_impl))


def init_input_shape(config: dict) -> tuple:
    return (1, min(config["n_positions"], 16))


def train_flops_per_sample(config: dict, traffic: dict) -> float:
    """Forward + backward operations for one sample (one token) at the
    cell's sequence length."""
    t = traffic["seq_len"]
    fwd = flops.gpt2_fwd_flops(
        1, t, num_layers=config["n_layer"], d_model=config["n_embd"],
        vocab_size=config["vocab_size"])
    return flops.train_step_flops(fwd) / t


def serve_costs(config: dict) -> dict:
    """What serving one unit of the window's work (the serving driver's
    ``WORK``) costs at the file's sizes, as the least a correct engine
    can do; ``perf/metrics/serve_mfu.py`` prices a window with it.

    Operations are the matmuls' (2 x multiply-accumulates): a token row
    through the blocks meets ``12 x n_embd**2`` weights a layer (qkv,
    projection, the two MLP products), a row of logits the tied embedding,
    a query-key pair ``QK^T`` and ``AV`` over ``n_embd`` in every layer.
    Bytes: one run of a program reads every weight it multiplies by once
    (all but the position table, which is gathered by row), in the type
    served; a cached token is its key and value in every layer, in the
    compute type (the engine's pages: 98,304 B at these sizes)."""
    import jax.numpy as jnp

    d, layers, vocab = config["n_embd"], config["n_layer"], config["vocab_size"]
    wsize = jnp.dtype(config["serve"]["weight_dtype"]).itemsize
    csize = jnp.dtype(config["compute_dtype"]).itemsize
    block = 12 * d * d + 13 * d  # kernels, their biases, the two norms
    return {
        "flops_per_token": 2 * layers * 12 * d * d,
        "flops_per_logit": 2 * vocab * d,
        "flops_per_attended": 2 * 2 * layers * d,
        "bytes_per_run": wsize * (layers * block + 2 * d + vocab * d),
        "bytes_per_cache_token": csize * 2 * layers * d,
    }


# ------------------------------------------------------------- reference


def _layer_norm(x, p, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def reference_logits(params, tokens, config: dict):
    """``(B, T)`` tokens -> ``(B, T, vocab)`` float32 logits.  Call under
    ``jax.default_matmul_precision("highest")``: on a TPU a float32
    product otherwise runs in bf16 passes."""
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), tree)
    p = f32(params)
    eps = config["layer_norm_epsilon"]
    h = config["n_head"]
    b, t = tokens.shape
    d = config["n_embd"]
    x = p["wte"]["embedding"][tokens] + p["wpe"]["embedding"][jnp.arange(t)]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(config["n_layer"]):
        blk = p[f"h_{i}"]
        a = _layer_norm(x, blk["ln_1"], eps)
        qkv = a @ blk["attn"]["qkv"]["kernel"] + blk["attn"]["qkv"]["bias"]
        q, k, v = (z.reshape(b, t, h, d // h) for z in jnp.split(qkv, 3, -1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d // h)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + (o.reshape(b, t, d) @ blk["attn"]["proj"]["kernel"]
                 + blk["attn"]["proj"]["bias"])
        m = _layer_norm(x, blk["ln_2"], eps)
        m = _gelu_new(m @ blk["mlp_fc"]["kernel"] + blk["mlp_fc"]["bias"])
        x = x + m @ blk["mlp_proj"]["kernel"] + blk["mlp_proj"]["bias"]
    x = _layer_norm(x, p["ln_f"], eps)
    return x @ p["wte"]["embedding"].T


def reference_token_losses(params, tokens, targets, config: dict):
    """``(B, T)`` next-token cross entropies of the reference forward."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(reference_logits(params, tokens, config), -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def system_token_losses(model, params, tokens, targets):
    """The same ``(B, T)`` cross entropies through the system's model, as
    ``tpudp/train.py``'s ``loss_fn`` takes them before its mean."""
    import optax

    logits = model.apply({"params": params}, tokens, train=True)
    return optax.softmax_cross_entropy_with_integer_labels(logits, targets)
