"""``paged_decode_roofline``, the share of its roofline the full-attention
layers' paged-decode kernel reaches (perf/metrics/paged_decode_roofline.py):
right on a hand-made run of ``gpt2m.serve_closed``, silent where the
program, the trace or the family gives it nothing to read, and an entry of
``BENCHMARK.json`` (on the tree and on a grown copy) that names that cell
alone."""

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests", "perf")]

import benchmark_contracts as contracts  # noqa: E402
from perf.harness import trace  # noqa: E402
from perf.harness.cells import load_json, load_module  # noqa: E402

CELL, NAME = "gpt2m.serve_closed", "paged_decode_roofline"
CONFIG = {k: v for k, v in load_json(os.path.join(
    ROOT, "perf", "configs", "gpt2_medium.json")).items() if k != "rehearsal"}
fam = load_module("families", "gpt2")
SHAPE = " custom-call bf16[64,16,64]"
# four traced steps and the drain's one: 24 decode calls of 0.1 ms a
# step, 0.5 ms of prefill, all inside the traced window
FORM = {"devices": {"/device:TPU:0": (
    [[f"paged_decode.{i % 24}" + SHAPE, 0.01 * i, 1e-4, True, "custom-call"]
     for i in range(120)]
    + [["paged_prefill.3 custom-call bf16[1,128,16,64]", 2.0, 5e-4, True,
        "custom-call"]])},
    "async": {}, "host": [["perf.engine_step", 0.0, 3.0],
                          [trace.WINDOW_SPAN, 0.0, 3.0]]}
TRACED = {"steps": 4, "steps_with_drain": 5}
# the decode runs of the four steps read 48 slots of ~300 cached tokens
STATS = {"steps": 4, "decode_rows_read": 4 * 48 * 300,
         "decode_page_visits": 4 * 24 * 170}


def _run(form=FORM, traced=TRACED, stats=STATS, family=fam):
    return SimpleNamespace(
        cell=SimpleNamespace(name=CELL, family=family, config=CONFIG),
        rehearse=False, device_kind="TPU v5 lite", window={},
        traced=traced and {**traced, "engine_stats": stats},
        trace_form=form, trace=trace.reduce(form) if form else None)


def test_the_reader_on_a_hand_made_run():
    """48 x 300 tokens a step of 98,304 B (a token's K and V in 24 layers
    of 1,024 bf16) at 819 GB/s over 24 x 0.1 ms x 120 / 24 / 5 calls a
    step: 1.728 ms of 2.4 = 72%."""
    assert fam.serve_costs(CONFIG)["bytes_per_cache_token"] == 98304
    least_ms = 1e3 * 48 * 300 * 98304 / 819e9
    took = 1e3 * 120 * 1e-4 / 5
    read = load_module("metrics", NAME).read
    assert read(_run()) == pytest.approx(100 * least_ms / took)
    assert 0 < read(_run()) < 100


def test_the_reader_returns_nothing_where_there_is_nothing_to_read():
    """The parent's engine (no counter), a run without a trace or without
    the kernel in it, a segment without a step, a family without
    ``serve_costs``: None, never an exception."""
    read = load_module("metrics", NAME).read
    assert read(_run(stats={"steps": 4, "decode_steps": 4})) is None
    assert read(_run(form=None, traced=None)) is None
    bare = {**FORM, "devices": {"/device:TPU:0": FORM["devices"][
        "/device:TPU:0"][-1:]}}
    assert read(_run(form=bare)) is None
    assert read(_run(stats={**STATS, "steps": 0})) is None
    assert read(_run(family=SimpleNamespace())) is None


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_metric_is_an_entry_with_a_file(kind, tmp_path):
    """Appended after the kernel metrics that were there, with its unit,
    direction, source, layer and the metric it moves, its cell first on
    its ``workloads`` and no other accepted cell there (the laguna cell's
    own test pins the set of metrics naming it)."""
    root = contracts.checkout(kind, tmp_path)
    b = contracts.load(root)
    per_layer = {m["name"]: m for m in b["per_layer"]}
    assert contracts.subsequence(
        ["kernel_ms.paged_decode", "paged_window_roofline",
         "retention_decode_roofline", NAME], per_layer)
    m = per_layer[NAME]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("%", "higher", "device_trace", "Kernels", "serve_tokens_per_s")
    assert m["workloads"][0] == CELL
    assert set(m["workloads"]) <= {CELL, contracts.NEW_CELL}
    assert os.path.isfile(os.path.join(root, "perf", "metrics",
                                       NAME + ".py"))
