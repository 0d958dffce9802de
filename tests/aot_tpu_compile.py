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
                sds((PAGES + 1, T, H, DH), BF16),
                sds((PAGES + 1, T, H, DH), BF16),
                sds((batch, M), jnp.int32), sds(pos_shape, jnp.int32))


def flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


ANC = tuple(map(tuple, np.tril(np.ones((5, 5), np.int32))))
x = sds((2, 1024, H, DH), BF16)
x8k = sds((1, 8192, H, DH), BF16)  # the chooser's blocks at a long sequence
w = sds((SLOTS, 5, H, DH), BF16)
CASES = {
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
        (w, sds((PAGES + 1, T, H, DH), BF16),
         sds((PAGES + 1, T, H, DH), BF16), sds((SLOTS, M), jnp.int32),
         sds((SLOTS,), jnp.int32), w, w)),
}

# The name= on each pallas_call: it reaches the compiled program as a
# component of the Mosaic custom call's op_name (inside a flax module the
# HLO instruction itself is then ``flash_fwd.<n>``, which is what the
# benchmark's kernel_ms.* readers match in a device trace).
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_decode",
           "paged_prefill", "paged_tree")
MOSAIC_OP = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"')

failed = []
for name, (fn, args) in CASES.items():
    try:
        lowered = jax.jit(fn).lower(*args)
        assert "tpu_custom_call" in lowered.as_text(), "no Mosaic call"
        ops = MOSAIC_OP.findall(lowered.compile().as_text())
        named = sorted({k for k in KERNELS for op in ops
                        if re.search(rf"[/(]{k}[/)]", op)})
        print(f"OK {name}")
        print(f"KERNELS {name} {','.join(named)}")
    except Exception as exc:  # noqa: BLE001 — report every family
        failed.append(name)
        print(f"FAIL {name}: {type(exc).__name__}: {str(exc)[:800]}")
sys.exit(1 if failed else 0)
