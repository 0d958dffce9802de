"""The per-layer readers of what the program records about itself (PR 26):
the engine's phase seconds and request stamps in ``Engine.metrics()``'s
``stats``, and the attention kernels' names on the device trace.  Each
reader on a hand-made run whose answer can be checked by eye, each
returning nothing where the program (the parent commit's, or an engine
with ``obs=False``) gives it nothing to read, and the rehearsal of both
cells."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf.harness import trace  # noqa: E402
from perf.harness.cells import load_json, load_module  # noqa: E402

SERVE, TRAIN = "gpt2m.serve_closed", "gpt2m.train"
SHAPE = " custom-call bf16[64,16,64]"

# 10 engine steps of a window: 0.9 s in ``step``, of which the host was
# blocked 0.7 s on the decode's tokens and 0.05 s on first tokens; four
# first tokens after 2.0 s in all, 1.7 s of it holding a slot.
ENGINE_STATS = {
    "steps": 10, "decode_steps": 10, "active_slot_steps": 40,
    "step_s": 0.9, "admit_s": 0.02, "dispatch_s": 0.06, "pages_s": 0.01,
    "fetch_wait_s": 0.7, "first_token_wait_s": 0.05, "commit_s": 0.03,
    "first_tokens": 4, "ttft_s": 2.0, "ttft_queue_s": 0.1,
    "ttft_prefill_wait_s": 1.7,
}


def _form(ops):
    """A trace in the plain form: one chip, a traced window of 0..10 s."""
    return {"devices": {"/device:TPU:0": ops}, "async": {},
            "host": [["perf.engine_step", 0.5, 1.0],
                     [trace.WINDOW_SPAN, 0.0, 10.0]]}


# two train steps: per step 2 forward calls of 0.1 s, 2 dq of 0.2 s, 2 dkv
# of 0.3 s (instruction names as the v5e compiler gives them under
# name=: ``flash_fwd.<n>``), a fusion, and one call outside the window
TRAIN_FORM = _form(
    [[f"flash_fwd.{i} custom-call (bf16[128,1024,64], f32[128,1,1024])",
      float(i), 0.1, True, "custom-call"] for i in range(4)]
    + [[f"flash_bwd_dq.{i}" + SHAPE, 4.0 + 0.25 * i, 0.2, True, "custom-call"]
       for i in range(4)]
    + [[f"flash_bwd_dkv.{i}" + SHAPE, 5.0 + 0.5 * i, 0.3, True, "custom-call"]
       for i in range(4)]
    + [["fusion.13 fusion:kOutput f32[8,16]", 8.0, 1.0, False, "fusion"],
       ["flash_fwd.9" + SHAPE, 11.0, 0.1, True, "custom-call"]])
# five engine steps (drain included): a decode kernel call a layer in a
# ``while`` body (same instruction every time), a prefill call in three
# of the steps, and a non-kernel op that only shares the prefix
SERVE_FORM = _form(
    [["while.2 while (s32[], bf16[64,16,64])", 0.0, 9.0, False, "while"]]
    + [["paged_decode.3" + SHAPE, 0.1 * i, 0.05, True, "custom-call"]
       for i in range(10)]
    + [["paged_prefill.7 custom-call bf16[1,128,16,64]", 2.0 + i, 0.4, True,
        "custom-call"] for i in range(3)]
    + [["paged_decode_table.1 fusion:kLoop s32[64,8]", 6.0, 0.5, False,
        "fusion"]])


def _run(cell, *, stats=None, form=None, traced=None):
    return SimpleNamespace(
        cell=SimpleNamespace(name=cell), rehearse=False,
        window={"engine_stats": stats, "num_slots": 4} if stats is not None
        else {}, traced=traced, trace_form=form,
        trace=trace.reduce(form) if form else None)


FULL = {
    SERVE: _run(SERVE, stats=ENGINE_STATS, form=SERVE_FORM,
                traced={"steps": 4, "steps_with_drain": 5}),
    TRAIN: _run(TRAIN, form=TRAIN_FORM, traced={"steps": 2, "samples": 16}),
}
# (metric, its cell, the reading on the full run)
READERS = [
    ("engine_host_ms", SERVE, 1e3 * (0.9 - 0.7 - 0.05) / 10),
    ("engine_fetch_wait_ms", SERVE, 1e3 * (0.7 + 0.05) / 10),
    ("first_token_sync_ms", SERVE, 1e3 * 0.05 / 10),
    ("ttft_prefill_wait_share", SERVE, 100.0 * 1.7 / 2.0),
    ("kernel_ms.flash_fwd", TRAIN, 1e3 * 4 * 0.1 / 2),
    ("kernel_ms.flash_bwd_dq", TRAIN, 1e3 * 4 * 0.2 / 2),
    ("kernel_ms.flash_bwd_dkv", TRAIN, 1e3 * 4 * 0.3 / 2),
    ("kernel_ms.paged_decode", SERVE, 1e3 * 10 * 0.05 / 5),
    ("kernel_ms.paged_prefill", SERVE, 1e3 * 3 * 0.4 / 5),
]


def _reader(name):
    return load_module("metrics", name).read


@pytest.mark.parametrize("name, cell, want", READERS)
def test_a_reader_on_a_hand_made_run(name, cell, want):
    assert _reader(name)(FULL[cell]) == pytest.approx(want)


@pytest.mark.parametrize("name, cell, _want", READERS)
def test_a_reader_returns_nothing_where_the_program_gives_nothing(
        name, cell, _want):
    """The parent's program: ``stats`` without the seconds and the TTFT
    split, kernels called ``attn.N`` / ``decode_step_paged.N``; a run
    without a trace; a window without a step."""
    old_stats = {k: v for k, v in ENGINE_STATS.items()
                 if k in ("steps", "decode_steps", "active_slot_steps")}
    unnamed = _form([["attn.135" + SHAPE, 1.0, 0.1, True, "custom-call"],
                     ["decode_step_paged.36" + SHAPE, 2.0, 0.1, True,
                      "custom-call"]])
    traced = FULL[cell].traced
    read = _reader(name)
    assert read(_run(cell, stats=old_stats, form=unnamed, traced=traced)) is None
    assert read(_run(cell)) is None
    assert read(_run(cell, stats=dict(ENGINE_STATS, steps=0, first_tokens=0),
                     form=FULL[cell].trace_form, traced={})) is None


def test_the_kernels_of_a_cell_sum_to_its_kernel_share():
    """``kernel_ms.*`` reads the operations ``kernel_s`` is made of, so
    a cell's kernels x steps add up to ``kernel_share`` x busy time."""
    for cell, steps_key in ((TRAIN, "steps"), (SERVE, "steps_with_drain")):
        run = FULL[cell]
        total = sum(_reader(name)(run) for name, c, _ in READERS
                    if c == cell and name.startswith("kernel_ms."))
        assert total * run.traced[steps_key] / 1e3 == pytest.approx(
            run.trace["kernel_s"])
    serve = FULL[SERVE]
    assert (_reader("engine_host_ms")(serve)
            + _reader("engine_fetch_wait_ms")(serve)) == pytest.approx(
        1e3 * ENGINE_STATS["step_s"] / ENGINE_STATS["steps"])


def test_the_nine_metrics_are_appended_entries_with_a_file_each():
    b = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    tail = b["per_layer"][-len(READERS):]
    assert [m["name"] for m in tail] == [name for name, _, _ in READERS]
    for m, (_name, cell, _want) in zip(tail, READERS):
        assert m["workloads"] == [cell] and m["better"] == "lower"
        assert m["source"] == ("device_trace" if m["name"].startswith("kernel_ms.")
                               else "program_counter" if m["unit"] == "%"
                               else "program_span")
        assert os.path.isfile(os.path.join(ROOT, "perf", "metrics",
                                           m["name"] + ".py"))


@pytest.mark.parametrize("cell", [SERVE, TRAIN])
def test_the_rehearsal_prints_what_a_cpu_can_read(cell):
    """``--rehearse --trace 1`` of both cells with the new readers in
    place: the span and counter metrics print (the engine records them
    on any platform); a ``kernel_ms`` has no reading without a device
    plane, and its reader returns nothing instead of raising."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         cell, "--seed", "3000000019", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    got = set(line["metrics"])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    assert not any(name.startswith("kernel_ms.") for name in got)
    program = {name for name, c, _ in READERS
               if c == cell and not name.startswith("kernel_ms.")}
    assert program <= got
    if cell == SERVE:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["engine_host_ms"] > 0 and m["engine_fetch_wait_ms"] > 0
        assert m["engine_fetch_wait_ms"] >= m["first_token_sync_ms"] >= 0
        assert 0 <= m["ttft_prefill_wait_share"] <= 100
