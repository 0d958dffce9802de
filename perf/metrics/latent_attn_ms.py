"""Device milliseconds an engine step inside absorbed latent attention
(tpudp/ops/paged_attention.py::latent_paged_attention): the prefill
chunk's and the decode run's online-softmax loops over page tiles, one a
layer a program, in the traced window over the loop's steps.

The trace's plain form keeps an operation's name, opcode and shape, not
the scope it was traced under (``latent_attn``), and XLA names the loop
``while.<n>``.  So the loop is found by its opcode and its carry: a
counter, then the running maximum and denominator ``f32[b, cur, heads]``
and the accumulator ``f32[b, cur, heads, kv_lora_rank]``, sizes from the
cell's configuration.  A ``while`` event holds its body's operations, so
its duration is the loop's whole time.  Nothing without a trace, for a
configuration without latent attention, or where no such loop ran (the
parent of the PR that brought it)."""

import re

from perf.harness.trace import WINDOW_SPAN


def read(run):
    form, traced, cfg = run.trace_form, run.traced, run.cell.config
    if not form or not traced or not traced.get("steps_with_drain") \
            or "kv_lora_rank" not in cfg or not form.get("devices"):
        return None
    windows = [h for h in form.get("host", []) if h[0] == WINDOW_SPAN]
    if not windows:
        return None
    w0 = windows[-1][1]
    w1 = w0 + windows[-1][2]
    row = rf"f32\[\d+,\d+,{cfg['num_attention_heads']}"
    loop = re.compile(rf"while(\.\d+)? while \(s32\[\], {row}\], {row}\], "
                      rf"{row},{cfg['kv_lora_rank']}\]")
    total, loops = 0.0, 0
    for ops in form["devices"].values():
        for label, start, dur, _kernel, op in ops:
            if (op == "while" and start < w1 and start + dur > w0
                    and loop.match(label)):
                total += dur
                loops += 1
    if not loops:
        return None
    return 1e3 * total / len(form["devices"]) / traced["steps_with_drain"]
