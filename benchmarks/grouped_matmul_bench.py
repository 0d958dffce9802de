"""Time the expert layer's kernels on the chip at the LFM2 layer's shapes:
the grouped products (tpudp/ops/grouped_matmul.py) over block choices, and
the row operations around them (tpudp/ops/expert_rows.py) beside the same
operation in plain XLA over all the rows.

    python benchmarks/grouped_matmul_bench.py [tokens] [--sweep]

Each timing chains CALLS calls inside one program (a sub-millisecond kernel
timed one host dispatch at a time reads the dispatch, PERF.md section 6,
PR 27); every call gets its own rotation of the group sizes and of the
index vectors, so that no two are the same computation and nothing else
runs between them.  Prints one JSON row per (kernel, shape, blocks): ms a
call and the share of the MXU roofline at the rows the groups hold; per row
operation: ms a call, ms of the XLA form over all ``m`` rows, and the least
the bytes it moves can take at 819 GB/s.  ``--sweep`` adds the block sweep
and the gather's chunk sizes.  Exits non-zero without a TPU."""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

CALLS = 8
HBM_BYTES_PER_S = 819e9


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from tpudp.ops import expert_rows as er
    from tpudp.ops import grouped_matmul as gm
    from tpudp.ops.grouped_matmul import choose_blocks, gmm, tgmm

    if jax.devices()[0].platform != "tpu":
        print("no TPU here", file=sys.stderr)
        return 1
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    tokens = int(args[0]) if args else 32768
    sweep = "--sweep" in sys.argv
    m, d, f, g, routed, k = tokens * 4, 2048, 1792, 8, 32, 4
    rng = np.random.default_rng(0)
    # every token picks 4 of 32 experts evenly, as a router at
    # initialisation does; experts 0-7 are held, the rest sort last
    chosen = np.argsort(rng.random((tokens, routed)), axis=1)[:, :k]
    flat = np.where(chosen < g, chosen, g).reshape(-1)
    loads = np.bincount(flat, minlength=g + 1)[:g]
    order = np.argsort(flat, kind="stable")
    rolled = lambda a: jnp.asarray(  # noqa: E731
        [np.roll(a, 8 * i) for i in range(CALLS)], jnp.int32)
    rows = int(loads.sum())
    slot_of = rolled(np.argsort(order)).reshape(CALLS, tokens, k)
    plans = [er.combine_plan(s, jnp.int32(rows)) for s in slot_of]
    per_call = {
        "n": jnp.arange(1, CALLS + 1),
        "sizes": jnp.asarray([np.roll(loads, i) for i in range(CALLS)],
                             jnp.int32),
        "token_of": rolled(order // k), "slot_of": slot_of,
        "plan": tuple(jnp.stack(p) for p in zip(*plans))}
    walked = int(gm.visited_rows(jnp.asarray(loads), m))
    bf = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, d), bf)
    h, h2, h3 = (jax.random.normal(jax.random.fold_in(key, i), (m, f), bf)
                 for i in range(3))
    xt = x[:tokens]
    w_rows = jax.random.uniform(key, (m,), jnp.float32)
    w_up = jax.random.normal(key, (g, d, f), bf)
    w_down = jax.random.normal(key, (g, f, d), bf)

    def timed(fn, *operands, taken=None):
        """Seconds a call of ``fn(*operands, r)``, ``r`` the call's own
        entry of ``per_call`` (an argument of the program: closed over, its
        sums would be folded to constants and equal calls run once); what
        ``fn`` returns is held behind a barrier and one element read.
        ``taken``: an operand whose buffer ``fn``'s result takes, as
        ``fn(*operands, taken, r)``; every call is given a copy of its own
        to use up (donated), as the layer gives it a value nothing else
        reads: one shared array would be copied before every call."""
        @functools.partial(jax.jit, donate_argnums=(1,))
        def chain(calls, mine, *ops):
            acc, outs = jnp.zeros((), jnp.float32), []
            for i in range(CALLS):
                r = jax.tree.map(lambda a: a[i], calls)
                out = lax.optimization_barrier(fn(*ops, *mine[i], r))
                first = jax.tree.leaves(out)[0]
                acc = acc + first.ravel()[0].astype(jnp.float32)
                if taken is not None:  # a donated buffer is used only
                    outs.append(first)  # where a result can live in it
            return acc, outs

        def own():
            return [() if taken is None else (taken + 0,)
                    for _ in range(CALLS)]

        jax.block_until_ready(chain(per_call, own(), *operands))
        mine = jax.block_until_ready(own())
        t0 = time.perf_counter()
        jax.block_until_ready(chain(per_call, mine, *operands))
        return (time.perf_counter() - t0) / CALLS

    def report(row, fn, *operands, taken=None, **more):
        try:
            s = timed(fn, *operands, taken=taken)
            row.update(ms=round(1e3 * s, 4),
                       **{key: val(s) for key, val in more.items()})
        except Exception as e:  # noqa: BLE001 — report every case
            row["error"] = str(e)[:300]
        print(json.dumps(row), flush=True)

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
        picked = choose_blocks(kind, m, kk, nn, bf,
                               bf if kind == "gmm" else jnp.float32)
        blocks = [(bm, bk, bn) for bm in (128, 256, 512)
                  for bk, bn in swept] if sweep else [picked]
        for bm, bk, bn in blocks:
            if kind == "gmm":
                def fn(a, b, r, bm=bm, bn=bn):
                    return gmm(a, b, r["sizes"], transpose_rhs=transposed,
                               block_m=bm, block_n=bn)
            else:
                def fn(a, b, r, bm=bm, bk=bk, bn=bn):
                    return tgmm(a, b, r["sizes"], block_m=bm, block_k=bk,
                                block_n=bn)
            report({"kernel": name, "rows": rows, "m": m,
                    "blocks": [bm, bk, bn], "chosen": (bm, bk, bn) == picked},
                   fn, *operands,
                   mxu_roofline_pct=lambda s: round(100 * ideal / s, 1))

    # --- the row operations: (name, the bounded form, the XLA form over
    # all m rows, operands, the operand whose buffer the bounded form's
    # result takes, (M, n) bf16 arrays the bounded form moves)
    walk = lambda r: gm.visits(r["sizes"], m)  # noqa: E731
    total = lambda r: jnp.sum(r["sizes"])  # noqa: E731
    # an XLA form whose operands do not change with the call is scaled by
    # the call's number (inside its one fusion), or XLA runs it once
    nth = lambda a, r: a * r["n"].astype(a.dtype)  # noqa: E731

    def xla_swiglu(a, b, w):
        return (w[:, None] * jax.nn.silu(a.astype(jnp.float32))
                * b.astype(jnp.float32)).astype(bf)

    def xla_combine(a, r):  # the layer's combine before PR 30
        mine = a[r["slot_of"].reshape(-1)].reshape(-1, k, d)
        return mine.astype(jnp.float32).sum(axis=1).astype(bf)

    row_ops = [
        ("gmm_up_tail_unwritten",
         lambda a, b, r: gm.gmm_walk(a, b, walk(r)),
         lambda a, b, r: gmm(a, b, r["sizes"]), (x, w_up), None, 0),
        ("gmm_dlhs_up_plus",
         lambda a, b, c, r: gm.gmm_walk(a, b, walk(r), transpose_rhs=True,
                                        plus=c),
         lambda a, b, c, r: gmm(a, b, r["sizes"], transpose_rhs=True) + c,
         (h, w_up), x, 0),
        ("swiglu", lambda a, b, w, r: er.swiglu(a, b, w, walk(r)),
         lambda a, b, w, r: nth(xla_swiglu(a, b, w), r), (h, h2, w_rows),
         None, 3 * f),
        ("swiglu_bwd",
         lambda a, b, w, c, r: er.swiglu_bwd(a, b, c, w, walk(r)),
         lambda a, b, w, c, r: jax.vjp(xla_swiglu, a, b, w)[1](c),
         (h, h2, w_rows), h3, 5 * f),
        ("gather", lambda a, r: er.gather_rows(a, r["token_of"], total(r)),
         lambda a, r: a[r["token_of"]], (xt,), None, 2 * d),
        ("combine", lambda a, r: er.combine_rows(a, r["plan"]), xla_combine,
         (x,), None, 3 * d),
        ("combine_sum_alone",
         lambda a, r: er.segment_sum(
             a, r["plan"][1], r["plan"][2][::er.combine_tokens(tokens)],
             tokens), None, (x,), None, d),
        ("combine_plan",
         lambda r: er.combine_plan(r["slot_of"], total(r)), None, (), None,
         0),
    ]
    for name, ours, xla, operands, taken, width in row_ops:
        row = {"op": name, "rows": rows, "walked": walked, "m": m}
        if width:
            row["hbm_floor_ms"] = round(
                1e3 * walked * width * 2 / HBM_BYTES_PER_S, 4)
        try:
            if xla is not None:
                row["xla_all_rows_ms"] = round(
                    1e3 * timed(xla, *operands, taken=taken), 4)
        except Exception as e:  # noqa: BLE001 — report every case
            row["xla_error"] = str(e)[:300]
        report(row, ours, *operands, taken=taken)
    for chunk in (1024, 2048, 8192, 16384) if sweep else ():
        report({"op": "gather", "chunk": chunk, "rows": rows, "m": m},
               lambda a, r, c=chunk: er.gather_rows(
                   a, r["token_of"], total(r), c), xt)
    return 0


if __name__ == "__main__":
    sys.exit(main())
