"""The per-layer readers of what the program records about itself (PR 26):
the engine's phase seconds and request stamps in ``Engine.metrics()``'s
``stats``, and the attention kernels' names on the device trace.  Each
reader on a hand-made run whose answer can be checked by eye, each
returning nothing where the program (the parent commit's, or an engine
with ``obs=False``) gives it nothing to read, and the rehearsal of both
cells.  And ``serve_mfu`` (PR 33): the whole serving step's share of the
chip's peak, from the work the driver counted and the family's costs."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]

import benchmark_contracts as contracts  # noqa: E402
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


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_nine_metrics_are_entries_with_a_file_each(kind, tmp_path):
    """Found by name: present, in this order among themselves, their cell
    among their ``workloads``.  What else the lists hold, before, between
    or after, is other PRs' business (benchmark_contracts.py)."""
    root = contracts.checkout(kind, tmp_path)
    per_layer = {m["name"]: m for m in contracts.load(root)["per_layer"]}
    assert contracts.subsequence([name for name, _, _ in READERS], per_layer)
    for name, cell, _want in READERS:
        m = per_layer[name]
        assert cell in m["workloads"] and m["better"] == "lower"
        assert m["source"] == ("device_trace" if name.startswith("kernel_ms.")
                               else "program_counter" if m["unit"] == "%"
                               else "program_span")
        assert os.path.isfile(os.path.join(root, "perf", "metrics",
                                           name + ".py"))


# ------------------------------------------------------------- serve_mfu

# A chip that does 1e12 operations and moves 1e9 bytes a second, and a
# model priced so that the sums can be made by eye:
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
COSTS = {"flops_per_token": 1e8,      # 0.1 ms a token row through the blocks
         "flops_per_logit": 1e7,      # 0.01 ms a row of logits
         "flops_per_attended": 1e4,   # 10 ns a query-key pair
         "bytes_per_run": 2e6,        # 2 ms to read the weights once
         "bytes_per_cache_token": 1e3}  # 1 us a cached token read
# A 2 s window.  Decode: 10 runs of 40 rows over 100,000 cached tokens in
# all: operations 40 + 4 + 1 = 45 ms, bytes 20 + 100 = 120 ms: the memory
# bounds it.  Prefill: 5 runs, 2 prompts of 320 tokens: operations 64 +
# 0.02 + 2 x (320 x 321 / 2) x 10 ns = 65.0472 ms, bytes 10 + 0.64 ms:
# the arithmetic bounds it.
WORK = {"decode": {"runs": 10, "tokens": 400, "logits": 400,
                   "cache_tokens": 100_000, "attended": 100_000},
        "prefill": {"runs": 5, "tokens": 640, "logits": 2,
                    "cache_tokens": 640, "attended": 2 * 320 * 321 // 2}}


def _serve_run(work=WORK, *, family=None, rehearse=False):
    family = SimpleNamespace(serve_costs=lambda _cfg: COSTS) \
        if family is None else family
    return SimpleNamespace(
        cell=SimpleNamespace(name=SERVE, family=family, config={}),
        rehearse=rehearse, device_kind="a chip of round numbers",
        window={"t0": 100.0, "t1": 102.0, "work": work} if work else
        {"t0": 100.0, "t1": 102.0})


@pytest.fixture()
def serve_mfu(monkeypatch):
    module = load_module("metrics", "serve_mfu")
    monkeypatch.setattr(module, "peaks", lambda _kind: PEAKS)
    return module.read


def test_serve_mfu_on_a_hand_made_run(serve_mfu):
    """Both branches of the ``max``: decode at its bytes, prefill at its
    operations; each program alone reads its own share."""
    assert serve_mfu(_serve_run()) == pytest.approx(
        100 * (0.120 + 0.0650472) / 2.0)
    idle = dict.fromkeys(WORK["decode"], 0)
    assert serve_mfu(_serve_run({"decode": WORK["decode"], "prefill": idle})
                     ) == pytest.approx(100 * 0.120 / 2.0)
    assert serve_mfu(_serve_run({"decode": idle, "prefill": WORK["prefill"]})
                     ) == pytest.approx(100 * 0.0650472 / 2.0)


def test_serve_mfu_returns_nothing_where_there_is_nothing_to_read(serve_mfu):
    """A family without ``serve_costs`` (every training family), a driver
    that kept no work (the parent's), a window without a step, a rehearsal
    (a CPU has no peaks: as ``mfu``)."""
    assert serve_mfu(_serve_run(family=SimpleNamespace())) is None
    assert serve_mfu(_serve_run(None)) is None
    idle = dict.fromkeys(WORK["decode"], 0)
    assert serve_mfu(_serve_run({"decode": idle, "prefill": idle})) is None
    assert serve_mfu(_serve_run(rehearse=True)) is None
    # ... and with the real table it asks for the kind by name
    with pytest.raises(KeyError):
        load_module("metrics", "serve_mfu").read(_serve_run())


def test_serve_costs_are_the_hand_count():
    """GPT-2-medium as served: 0.71 GB of weights a program run (all
    354,823,168 parameters but the 1,024 x 1,024 position table, in bf16),
    98,304 B a cached token, and one full decode step of the cell (64 rows
    over 400 cached tokens each) bound by its 3.22 GB, not its 48 GFLOP."""
    cfg = load_json(os.path.join(ROOT, "perf", "configs", "gpt2_medium.json"))
    c = load_module("families", "gpt2").serve_costs(cfg)
    assert c["bytes_per_run"] == 2 * (cfg["parameters"] - 1024 * 1024)
    assert c["bytes_per_run"] == 707_549_184
    assert c["bytes_per_cache_token"] == 2 * 24 * 1024 * 2 == 98_304
    assert c["flops_per_token"] == 2 * 24 * 12 * 1024 * 1024
    assert c["flops_per_logit"] == 2 * 50257 * 1024
    assert c["flops_per_attended"] == 4 * 24 * 1024
    flops = 64 * (c["flops_per_token"] + c["flops_per_logit"]) \
        + 64 * 400 * c["flops_per_attended"]
    nbytes = c["bytes_per_run"] + 64 * 400 * c["bytes_per_cache_token"]
    assert flops / 197e12 == pytest.approx(0.2424e-3, rel=1e-3)
    assert nbytes / 819e9 == pytest.approx(3.9366e-3, rel=1e-3)
    assert not hasattr(load_module("families", "lfm2_moe"), "serve_costs")


def test_the_serving_driver_counts_what_a_windows_tokens_required():
    """A prompt of 10 tokens whose tokens 0, 1 and 2 fell in the window,
    and one of 7 whose tokens 5 and 6 did."""
    driver = load_module("drivers", "serve_closed")
    work = {k: dict.fromkeys(driver.WORK, 0) for k in ("decode", "prefill")}
    driver._add_work(work, 10, [0, 1, 2])
    driver._add_work(work, 7, [5, 6])
    assert work["prefill"] == {"runs": 0, "tokens": 10, "logits": 1,
                               "cache_tokens": 10, "attended": 55}
    assert work["decode"] == {"runs": 0, "tokens": 4, "logits": 4,
                              "cache_tokens": 11 + 12 + 12 + 13,
                              "attended": 11 + 12 + 12 + 13}


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
    assert program <= got and "serve_mfu" not in got  # a CPU has no peaks
    if cell == SERVE:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["engine_host_ms"] > 0 and m["engine_fetch_wait_ms"] > 0
        assert m["engine_fetch_wait_ms"] >= m["first_token_sync_ms"] >= 0
        assert 0 <= m["ttft_prefill_wait_share"] <= 100
