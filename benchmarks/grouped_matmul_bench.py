"""Time the grouped-matmul kernels (tpudp/ops/grouped_matmul.py) on the chip
at the LFM2 expert layer's shapes, over block choices.

    python benchmarks/grouped_matmul_bench.py [tokens] [--sweep]

Each timing chains CALLS calls inside one program (a sub-millisecond kernel
timed one host dispatch at a time reads the dispatch, PERF.md section 6,
PR 27); every call gets its own rotation of the group sizes, so that no two
are the same computation and nothing else runs between them.  Prints one JSON row per (kernel, shape, blocks): ms a call and
the share of the MXU roofline at the rows the groups hold.  Exits non-zero
without a TPU."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

CALLS = 8


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudp.ops.grouped_matmul import choose_blocks, gmm, tgmm

    if jax.devices()[0].platform != "tpu":
        print("no TPU here", file=sys.stderr)
        return 1
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    tokens = int(args[0]) if args else 32768
    sweep = "--sweep" in sys.argv
    m, d, f, g, routed, k = tokens * 4, 2048, 1792, 8, 32, 4
    rng = np.random.default_rng(0)
    # loads of the 8 held experts when tokens x 4 assignments spread evenly
    # over 32: multinomial, as a router at initialisation gives
    loads = rng.multinomial(tokens * k, [1 / routed] * routed)[:g]
    sizes_all = jnp.asarray([np.roll(loads, i) for i in range(CALLS)],
                            jnp.int32)
    rows = int(loads.sum())
    bf = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, d), bf)
    h = jax.random.normal(key, (m, f), bf)
    w_up = jax.random.normal(key, (g, d, f), bf)
    w_down = jax.random.normal(key, (g, f, d), bf)

    def timed(fn, *operands):
        @jax.jit
        def chain(sizes, *ops):
            acc = jnp.zeros((), jnp.float32)
            for i in range(CALLS):
                acc = acc + fn(*ops, sizes[i]).ravel()[0].astype(jnp.float32)
            return acc

        jax.block_until_ready(chain(sizes_all, *operands))
        t0 = time.perf_counter()
        jax.block_until_ready(chain(sizes_all, *operands))
        return (time.perf_counter() - t0) / CALLS

    ideal = 2.0 * rows * d * f / 197e12
    # product, kernel, operands, (K, N), transposed rhs, swept (bk,) bn
    products = [
        ("gmm_up", "gmm", (x, w_up), (d, f), False, [(d, 896), (d, 1792)]),
        ("gmm_down", "gmm", (h, w_down), (f, d), False,
         [(f, 1024), (f, 2048)]),
        ("gmm_dlhs_up", "gmm", (h, w_up), (f, d), True,
         [(f, 1024), (f, 2048)]),
        ("tgmm_up", "tgmm", (x, h), (d, f), False,
         [(512, 896), (1024, 896), (1024, 1792), (2048, 896)]),
    ]
    for name, kind, operands, (kk, nn), transposed, swept in products:
        chosen = choose_blocks(kind, m, kk, nn, bf,
                               bf if kind == "gmm" else jnp.float32)
        blocks = [(bm, bk, bn) for bm in (128, 256, 512)
                  for bk, bn in swept] if sweep else [chosen]
        for bm, bk, bn in blocks:
            if kind == "gmm":
                def fn(a, b, sizes, bm=bm, bn=bn):
                    return gmm(a, b, sizes, transpose_rhs=transposed,
                               block_m=bm, block_n=bn)
            else:
                def fn(a, b, sizes, bm=bm, bk=bk, bn=bn):
                    return tgmm(a, b, sizes, block_m=bm, block_k=bk,
                                block_n=bn)
            row = {"kernel": name, "rows": rows, "m": m,
                   "blocks": [bm, bk, bn], "chosen": (bm, bk, bn) == chosen}
            try:
                s = timed(fn, *operands)
                row.update(ms=round(1e3 * s, 4),
                           mxu_roofline_pct=round(100 * ideal / s, 1))
            except Exception as e:  # noqa: BLE001 — report every case
                row["error"] = str(e)[:300]
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
