"""Helper for tests/test_tpu_aot_compile.py: compile every Pallas kernel
family for a TPU v5e TOPOLOGY on this CPU host (libtpu compiles without a
chip; nothing runs).  Exit 77 = this installation cannot describe a TPU
topology (the test skips); exit 1 = a kernel failed to compile."""

import os
import re
import sys

os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

try:
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
except Exception as exc:  # noqa: BLE001 — no libtpu / no topology support
    print(f"no TPU topology here: {type(exc).__name__}: {exc}")
    sys.exit(77)

from tpudp.ops.flash_attention import flash_attention  # noqa: E402
from tpudp.ops.grouped_matmul import gmm  # noqa: E402
from tpudp.ops.paged_attention import (paged_attention,  # noqa: E402
                                       tree_paged_attention)

# GPT-2-small head geometry at the engine's default page size.
H, DH, T, SLOTS, PAGES = 12, 64, 16, 4, 256
M = 1024 // T
BF16 = jnp.bfloat16


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def paged(cur, batch, pos_shape):
    def fn(q, k, v, table, pos):
        return paged_attention(q, (k, v), table, pos, dtype=BF16,
                               impl="kernel", interpret=False)
    return fn, (sds((batch, cur, H, DH), BF16),
                sds((PAGES + 1, T, H * DH), BF16),  # a token row a line
                sds((PAGES + 1, T, H * DH), BF16),
                sds((batch, M), jnp.int32), sds(pos_shape, jnp.int32))


def flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def gmm_grads(x, w, sizes):
    """One grouped product and its VJP: ``moe_gmm`` forward and for the
    data gradient (transposed rhs), ``moe_tgmm`` for the weights'."""
    return jax.grad(lambda a, b: gmm(a, b, sizes, interpret=False).astype(
        jnp.float32).sum(), argnums=(0, 1))(x, w)


def moe_layer():
    """One chip's share of the LFM2 expert layer at the benchmark cell's
    shapes, forward and backward: the grouped products and every row
    kernel between the sort and the combine (tpudp/ops/expert_rows.py)."""
    from tpudp.models.moe import DroplessMoe

    sys.modules["tpudp.ops.grouped_matmul"]._interpret_default = (
        lambda: False)
    layer = DroplessMoe(num_experts=G, hidden=F, top_k=4,
                        num_experts_routed=32, selection_bias=True,
                        dtype=BF16)
    params = {"gate": sds((D, 32), jnp.float32),
              "expert_bias": sds((32,), jnp.float32),
              "w1": sds((G, D, F), jnp.float32),
              "w3": sds((G, D, F), jnp.float32),
              "w2": sds((G, F, D), jnp.float32)}
    return (jax.grad(lambda p, x: layer.apply({"params": p}, x).astype(
        jnp.float32).sum(), argnums=(0, 1)),
        (params, sds((ROWS // 4, D), BF16)))


def lfm2_step():
    """The LFM2-MoE train step (flash attention, grouped-matmul experts,
    remat) at the depth and pattern of the benchmark's cell and small
    widths; the program's own CPU-means-interpret default is steered from
    here, as the on-chip-measurement guide asks of a test."""
    from tpudp.models.lfm2 import Lfm2, Lfm2Config
    from tpudp.train import TrainState, make_optimizer, make_train_step

    # by sys.modules: tpudp.ops re-exports the FUNCTION flash_attention
    # under the module's own name
    for name in ("tpudp.ops.flash_attention", "tpudp.ops.grouped_matmul"):
        sys.modules[name]._interpret_default = lambda: False
    model = Lfm2(Lfm2Config(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=5, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        num_attention_heads=2, num_key_value_heads=1, num_experts=2,
        num_experts_routed=8, num_experts_per_tok=4, attn_impl="flash",
        moe_impl="gmm", remat=True, dtype=BF16))
    tx = make_optimizer(learning_rate=3e-4, weight_decay=0.0,
                        optimizer="adamw")

    def make_state(key):
        params = model.init(key, jnp.zeros((1, 16), jnp.int32))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          batch_stats={}, opt_state=tx.init(params),
                          loss_sum=jnp.zeros((), jnp.float32))

    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:1]), ("data",))
    spec = jax.sharding.PartitionSpec
    rep = jax.sharding.NamedSharding(mesh, spec())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(make_state, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct(
        (2, 128), jnp.int32,
        sharding=jax.sharding.NamedSharding(mesh, spec("data")))
    return make_train_step(model, tx, mesh, "allreduce"), (state, tokens,
                                                           tokens)


def engine_steps(name, steps, params, pool, *, slots, width, chunk):
    """The engine's paged decode and prefill programs (``_build_steps``'
    seventh and ninth) with the shapes ``Engine.step`` calls them with."""
    i32, f32 = jnp.int32, jnp.float32
    return {
        name("decode"): (steps[6], (
            params, pool, sds((slots, width), i32), sds((slots,), i32),
            sds((slots,), i32), sds((slots,), jnp.bool_), sds((slots,), f32),
            sds((slots,), i32), sds((slots,), f32),
            sds((slots, 2), jnp.uint32), sds((5,), f32))),
        name("prefill"): (steps[8], (
            params, pool, sds((width,), i32), sds((1, chunk), i32),
            sds((), i32), sds((), i32)))}


def shapes(tree, dtype=None):
    """A tree of arrays or shapes, described on the topology's first chip
    (float32 leaves in ``dtype`` where one is given: served weights)."""
    return jax.tree.map(lambda a: sds(a.shape, dtype if dtype is not None
                                      and a.dtype == jnp.float32
                                      else a.dtype), tree)


def latent_steps():
    """The serve engine's two programs for the latent-attention expert
    family at the benchmark cell's real sizes (perf/configs/
    pangu_ultra_moe_718b.json; 64 slots, 8,192 positions, 512-token pages,
    1,024 of them, the kernel backend): absorbed attention as one
    ``latent_attn`` Mosaic call a layer (a chunk's 1,024-row blocks against
    a ``(512, 512 + 128)`` page; no ``while`` loop over page tiles is
    left), the grouped kernels at a decode step's 512 rows and a
    chunk's 4,096, the whole program within the chip's memory, and the
    page pool in ONE layout (PR 34 found XLA transposing all 3.4 GB of it
    into a token-minor layout and back around a page write)."""
    import json

    from tpudp.models.generate import LatentPages
    from tpudp.models.pangu import Pangu, PanguConfig
    from tpudp.serve import engine

    for name in ("tpudp.ops.grouped_matmul", "tpudp.ops.paged_attention"):
        sys.modules[name]._interpret_default = lambda: False
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "pangu_ultra_moe_718b.json")) as f:
        config = {k: v for k, v in json.load(f).items() if k != "rehearsal"}
    cfg = PanguConfig.from_dict(config, dtype=BF16, param_dtype=BF16)
    params = shapes(jax.eval_shape(
        lambda k: Pangu(cfg).init(k, jnp.zeros((1, 16), jnp.int32))["params"],
        jax.random.PRNGKey(0)))
    slots, chunk, width = 64, 512, 8192 // 512
    pool = shapes(jax.eval_shape(
        lambda: LatentPages.zeros(cfg, LATENT_PAGES + 1, chunk)))
    return engine_steps("latent_{}".format,
                        engine._build_steps(cfg, "kernel"), params, pool,
                        slots=slots, width=width, chunk=chunk)


def laguna_steps():
    """The serve engine's two programs for the window-and-full-attention
    expert family at the benchmark cell's real sizes (perf/configs/
    laguna_xs2.json; 64 slots, 8,192 positions, 512-token pages: 1,024
    global pages and 64 x 2 window pages, each pool with its scratch page,
    the kernel backend): a page block is ``(512, 1,024)`` bf16 = 1 MB, four
    times `gpt2m.serve_closed`'s, and the kernels upcast it to float32, so
    this is where VMEM would refuse; the whole program within the chip's
    memory; each pool in ONE layout with no copy of its shape."""
    import json

    from tpudp.models.generate import WindowedPages
    from tpudp.models.laguna import Laguna, LagunaConfig
    from tpudp.serve import engine

    for name in ("tpudp.ops.grouped_matmul", "tpudp.ops.paged_attention"):
        sys.modules[name]._interpret_default = lambda: False
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "laguna_xs2.json")) as f:
        config = {k: v for k, v in json.load(f).items() if k != "rehearsal"}
    cfg = LagunaConfig.from_dict(config, dtype=BF16, param_dtype=BF16)
    params = shapes(jax.eval_shape(
        lambda k: Laguna(cfg).init(k, jnp.zeros((1, 16), jnp.int32))["params"],
        jax.random.PRNGKey(0)))
    slots, chunk, width = 64, 512, 8192 // 512
    pool = shapes(jax.eval_shape(lambda: WindowedPages.zeros(
        cfg, LAGUNA_PAGES[0] + 1, chunk, LAGUNA_PAGES[1] + 1)))
    steps = engine_steps("laguna_{}".format,
                         engine._build_steps(cfg, "kernel"), params, pool,
                         slots=slots, width=width, chunk=chunk)
    # the engine hands this family the pair of its two pools' tables
    return {name: (fn, (*args[:2], (args[2], args[2]), *args[3:]))
            for name, (fn, args) in steps.items()}


def gpt2m_steps(pages):
    """The serve engine's paged decode and prefill programs for
    perf/configs/gpt2_medium.json at `gpt2m.serve_closed`'s sizes (64
    slots, 1,024 positions, 128-token pages, the kernel backend) over a
    pool of ``pages`` pages: the pool in ONE layout with no copy of its
    shape (until PR 35 it was stored ``(..., 16, 64)``, XLA kept it in
    one tiling, Mosaic wanted another, and each program copied all of K
    and V in and out: 10.9 GB of temporaries at 288 pages)."""
    import json

    from perf.families.gpt2 import build_model
    from tpudp.models.generate import page_type
    from tpudp.serve import engine

    sys.modules["tpudp.ops.paged_attention"]._interpret_default = (
        lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "gpt2_medium.json")) as f:
        config = json.load(f)
    model = build_model(config)  # as the cell's driver builds it
    cfg = model.config
    params = shapes(jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 16), jnp.int32))["params"],
        jax.random.PRNGKey(0)), BF16)  # the cell serves bf16 weights
    chunk = 128
    pool = shapes(jax.eval_shape(
        lambda: page_type(cfg).zeros(cfg, pages + 1, chunk)))
    return engine_steps(f"gpt2m_{{}}_{pages}".format,
                        engine._build_steps(cfg, "kernel"), params, pool,
                        slots=64, width=config["n_positions"] // chunk,
                        chunk=chunk)


LATENT_PAGES = 1024
LAGUNA_PAGES = (1024, 128)  # global pages; window pages: 64 slots x 2
ANC = tuple(map(tuple, np.tril(np.ones((5, 5), np.int32))))
x = sds((2, 1024, H, DH), BF16)
x8k = sds((1, 8192, H, DH), BF16)  # the chooser's blocks at a long sequence
w = sds((SLOTS, 5, H, DH), BF16)
# the LFM2 expert layer at the benchmark cell's shapes: 4 x 8,192 tokens x
# top-4 rows, 8 held experts of 2,048 x 1,792 (up) and 1,792 x 2,048 (down)
ROWS, D, F, G = 131072, 2048, 1792, 8
CASES = {
    "moe_gmm_up": (gmm_grads, (sds((ROWS, D), BF16),
                               sds((G, D, F), jnp.float32),
                               sds((G,), jnp.int32))),
    "moe_gmm_down": (gmm_grads, (sds((ROWS, F), BF16),
                                 sds((G, F, D), jnp.float32),
                                 sds((G,), jnp.int32))),
    "moe_layer": moe_layer(),
    "lfm2_train_step": lfm2_step(),
    **latent_steps(),
    **laguna_steps(),
    **gpt2m_steps(288), **gpt2m_steps(512),
    "flash_fwd": (flash, (x, x, x)),
    "flash_bwd": (jax.grad(lambda q, k, v: flash(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2)), (x, x, x)),
    "flash_bwd_8k": (jax.grad(lambda q, k, v: flash(q, k, v).astype(
        jnp.float32).sum(), argnums=(0, 1, 2)), (x8k, x8k, x8k)),
    "paged_decode": paged(1, SLOTS, (SLOTS,)),
    "paged_window_verify": paged(5, SLOTS, (SLOTS,)),
    "paged_window_prefill": paged(T, 1, ()),
    "paged_tree": (
        lambda q, k, v, table, pos, wk, wv: tree_paged_attention(
            q, (k, v), table, pos, wk, wv, ANC, dtype=BF16,
            interpret=False),
        (w, sds((PAGES + 1, T, H * DH), BF16),
         sds((PAGES + 1, T, H * DH), BF16), sds((SLOTS, M), jnp.int32),
         sds((SLOTS,), jnp.int32), w, w)),
}

# The name= on each pallas_call: it reaches the compiled program as a
# component of the Mosaic custom call's op_name (inside a flax module the
# HLO instruction itself is then ``flash_fwd.<n>``, which is what the
# benchmark's kernel_ms.* readers match in a device trace).
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_decode",
           "paged_prefill", "paged_decode_window", "paged_prefill_window",
           "paged_tree", "latent_attn", "moe_gmm", "moe_tgmm",
           "moe_swiglu", "moe_swiglu_bwd", "moe_combine", "moe_unwritten")
MOSAIC_OP = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"')

failed = []
only = set(sys.argv[1:])  # by hand: the families to compile; none = all
for name, (fn, args) in CASES.items():
    if only and name not in only:
        continue
    try:
        lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args)
        assert "tpu_custom_call" in lowered.as_text(), "no Mosaic call"
        compiled = lowered.compile()
        text = compiled.as_text()
        ops = MOSAIC_OP.findall(text)
        calls = {k: sum(bool(re.search(rf"[/(]{k}[/)]", op)) for op in ops)
                 for k in KERNELS}
        print(f"OK {name}")
        print(f"KERNELS {name} " + ",".join(
            f"{k}={n}" for k, n in sorted(calls.items()) if n))
        if name.startswith(("latent_", "gpt2m_", "laguna_")):
            mem = compiled.memory_analysis()
            pool = (rf"bf16\[5,{LATENT_PAGES + 1},512,\d+\]"
                    if name.startswith("latent_") else
                    rf"bf16\[(?:2,{LAGUNA_PAGES[0] + 1}|3,"
                    rf"{LAGUNA_PAGES[1] + 1}),512,1024\]"
                    if name.startswith("laguna_") else
                    rf"bf16\[24,{int(name.rsplit('_', 1)[1]) + 1},128,1024\]")
            layouts = sorted(set(re.findall(pool + r"{([\d,]+)", text)))
            copies = len(re.findall(rf"= {pool}\S* copy\(", text))
            # the XLA form of absorbed attention: a loop over page tiles
            # that carries the running maximum, denominator, accumulator
            loops = len(re.findall(
                r"= \(s32\[\]\S* (?:f32\[\d+,\d+,128\]\S* ){2}"
                r"f32\[\d+,\d+,128,512\]\S* .* while\(", text))
            print(f"POOL {name} layouts={'|'.join(layouts)} copies={copies}"
                  f" attn_loops={loops}"
                  f" temp={mem.temp_size_in_bytes} bytes="
                  f"{mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes}")
    except Exception as exc:  # noqa: BLE001 — report every family
        failed.append(name)
        print(f"FAIL {name}: {type(exc).__name__}: {str(exc)[:800]}")
sys.exit(1 if failed else 0)
