"""The ``lfm2moe.train_8k`` cell's own files (PR 29): its configuration
keeps the published widths and states its cut, its operation counts equal
a hand count, the four grouped-kernel readers are right on a hand-made
trace and silent where there is nothing to read, the cell's entries are in
``BENCHMARK.json`` (the readers' since PR 33), the cell rehearses on the
CPU through ``perf/run.py`` with the routing-aware half of ``correct``,
and that half comes out false on float8 matrices, a dropped expert layer
and experts that compute a neighbour's function."""

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
from perf.harness.cells import Cell, load_json, load_module  # noqa: E402

CELL = "lfm2moe.train_8k"
CONFIG = load_json(os.path.join(ROOT, "perf", "configs", "lfm2_8b_a1b.json"))
TRAFFIC = load_json(os.path.join(ROOT, "perf", "traffic", "lm_tokens_8k.json"))
fam = load_module("families", "lfm2_moe")

# the catalog row's ``config`` (LiquidAI/LFM2-8B-A1B config.json)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


def test_the_configuration_keeps_every_published_width():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "num_dense_layers", "layer_types",
                       "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    # the cut: one leading dense layer and one whole period of the pattern
    # that follows, a quarter of the experts and of the vocabulary
    types = CONFIG["published"]["layer_types"]
    assert len(types) == 24 and types.count("full_attention") == 6
    assert CONFIG["layer_types"] == types[:1] + types[2:6]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"]) == (5, 1)
    assert (CONFIG["num_experts"], CONFIG["num_experts_routed"],
            CONFIG["first_expert"], CONFIG["vocab_size"]) == (8, 32, 0, 16384)
    assert "four chips" in CONFIG["deployment"] and CONFIG["assumed"]


def test_train_flops_are_the_hand_count():
    """ISSUE 29's count at the cell's sizes: 432 MFLOP forward a token."""
    d, f, dense, t = 2048, 1792, 7168, 8192
    conv = 2 * d * 3 * d + 2 * d * d
    attn = 2 * (2 * d * d + 2 * d * 512) + 2 * 2 * (t + 1) / 2 * d
    experts = 2 * d * 32 + (4 * 8 / 32) * 3 * 2 * d * f
    want = (conv + 3 * 2 * d * dense) + (attn + experts) \
        + 3 * (conv + experts) + 2 * d * 16384
    assert fam.fwd_flops_per_token(CONFIG, t) == pytest.approx(want)
    assert want == pytest.approx(432.5e6, rel=1e-3)
    traffic = {k: v for k, v in TRAFFIC.items() if k != "rehearsal"}
    assert fam.train_flops_per_sample(CONFIG, traffic) == pytest.approx(
        3 * want)
    # and at the rehearsal's sizes (hidden 128, experts 2 of 8, vocab 256)
    small = {**CONFIG, **CONFIG["rehearsal"]}
    conv, experts = 2 * 128 * 384 + 2 * 128 * 128, \
        2 * 128 * 8 + (4 * 2 / 8) * 3 * 2 * 128 * 128
    attn = 2 * (2 * 128 * 128 + 2 * 128 * 64) + 2 * 2 * 129 / 2 * 128
    assert fam.fwd_flops_per_token(small, 128) == pytest.approx(
        conv + 3 * 2 * 128 * 256 + attn + experts + 3 * (conv + experts)
        + 2 * 128 * 256)


# a traced window of 0..10 s holding two train steps: per step 3 gmm calls
# of 0.2 s and 1 tgmm call of 0.5 s; one gmm call outside the window and a
# fusion that only shares the prefix
FORM = {"devices": {"/device:TPU:0": (
    [[f"moe_gmm.{i} custom-call bf16[131072,1792]", float(i), 0.2, True,
      "custom-call"] for i in range(6)]
    + [[f"moe_tgmm.{i} custom-call f32[8,2048,1792]", 7.0 + i, 0.5, True,
        "custom-call"] for i in range(2)]
    + [["moe_gmm.9 custom-call bf16[131072,1792]", 11.0, 0.2, True,
        "custom-call"],
       ["moe_gmm_cast.1 fusion:kLoop bf16[8,2048,1792]", 9.0, 0.3, False,
        "fusion"]])},
    "async": {}, "host": [[trace.WINDOW_SPAN, 0.0, 10.0]]}


def _run(form, traced):
    traffic = {k: v for k, v in TRAFFIC.items() if k != "rehearsal"}
    return SimpleNamespace(
        cell=SimpleNamespace(name=CELL, family=fam, config=CONFIG,
                             traffic=traffic),
        rehearse=False, device_kind="TPU v5 lite", window={}, traced=traced,
        trace_form=form, trace=trace.reduce(form) if form else None)


@pytest.mark.parametrize("metric, want", [
    ("kernel_ms.moe_gmm", 1e3 * 6 * 0.2 / 2),
    ("kernel_ms.moe_tgmm", 1e3 * 2 * 0.5 / 2),
    # one product of the expected rows: tokens a step x 4 x 8 / 32 rows x
    # 2,048 x 1,792 x 2 operations at 197 TFLOP/s (more than its bytes at
    # 819 GB/s take), per call, over the calls' seconds
    ("moe_gmm_roofline", 100 * 6 * (2 * 32768 * 2048 * 1792 / 197e12)
     / (6 * 0.2)),
    ("moe_tgmm_roofline", 100 * 2 * (2 * 32768 * 2048 * 1792 / 197e12)
     / (2 * 0.5))])
def test_a_grouped_kernel_reader_on_a_hand_made_trace(metric, want):
    assert TRAFFIC["per_chip_batch"] * TRAFFIC["seq_len"] == 32768
    read = load_module("metrics", metric).read
    assert read(_run(FORM, {"steps": 2, "samples": 1})) == pytest.approx(want)
    ops, nbytes = fam.grouped_product_counts(CONFIG, TRAFFIC)
    assert ops / 197e12 > nbytes / 819e9  # the MXU bounds it, not the HBM


@pytest.mark.parametrize("metric", ["kernel_ms.moe_gmm", "kernel_ms.moe_tgmm",
                                    "moe_gmm_roofline", "moe_tgmm_roofline"])
def test_a_grouped_kernel_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, no such kernel in it (a program without the expert layer),
    or a family without the counts: None, never an exception."""
    read = load_module("metrics", metric).read
    assert read(_run(None, None)) is None
    bare = {"devices": {"/device:TPU:0": [FORM["devices"]["/device:TPU:0"][-1]]},
            "async": {}, "host": FORM["host"]}
    assert read(_run(bare, {"steps": 2, "samples": 1})) is None
    other = _run(FORM, {"steps": 2, "samples": 1})
    other.cell.family = SimpleNamespace()
    if "roofline" in metric:
        assert read(other) is None


# what the cell reports: the entries this file knows, each list in the
# order BENCHMARK.json has them among themselves
END_TO_END = ["train_throughput_per_chip", "setup_s"]
PER_LAYER = [
    "window_compiles", "data_wait_share", "reading_rate_median",
    "step_device_ms", "mfu", "kernel_share.train", "kernel_ms.flash_fwd",
    "kernel_ms.flash_bwd_dq", "kernel_ms.flash_bwd_dkv", "kernel_ms.moe_gmm",
    "kernel_ms.moe_tgmm", "moe_gmm_roofline", "moe_tgmm_roofline"]


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_cell_reports_what_benchmark_json_says(kind, tmp_path):
    cell = Cell(CELL, root=contracts.checkout(kind, tmp_path))
    assert cell.chips == 1 and cell.traffic["driver"] == "train_stream_routed"
    assert contracts.subsequence(
        END_TO_END, [m["name"] for m in cell.metrics("end_to_end")])
    assert contracts.subsequence(
        PER_LAYER, [m["name"] for m in cell.metrics("per_layer")])
    # the limits of `correct` that this cell brings, each with its readings;
    # the base class's two free-routing numbers are read, not held (their
    # readings say why)
    assert [limit for _, limit in cell.driver.CHECKS] == [
        "route_tie_margin", "routed_token_loss_atol", "routed_grad_rtol"]
    for _, limit in cell.driver.CHECKS:
        assert cell.traffic[limit] > 0 and limit in cell.traffic["limits"]
    for key in ("token_loss_atol", "loss_atol"):
        assert key not in cell.traffic and key in cell.traffic["limits"], key


@pytest.fixture(scope="module")
def rehearsal():
    """``--trace 1``: a traced run has a trace directory of its own (PR
    33), so test_layer_readers.py's traced rehearsals may run beside this
    one under xdist."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         CELL, "--seed", "3000000019", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=600)


def test_the_cell_rehearses_on_the_cpu(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    line = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device",
            "compared"} <= set(line)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    # what a CPU can read of the cell's per-layer metrics: no device plane,
    # no kernel; no peaks, no share of them
    assert {"window_compiles", "data_wait_share", "reading_rate_median"} \
        <= set(line["metrics"]) <= set(PER_LAYER)
    assert not any(name.startswith("kernel_") or name.endswith("_roofline")
                   or name == "mfu" for name in line["metrics"])
    assert "compiles in window=0 " in rehearsal.stdout
    assert '"train_throughput_per_chip": ' in rehearsal.stdout
    # the three limits this cell brings, each beside its reading; the two
    # the base class only reads are not among the numbers compared
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "choice_gap", "routed_token_gap", "routed_grad_gap", "failed",
        "window_compiles"}


def test_the_rehearsal_makes_the_routing_aware_comparisons(rehearsal):
    notes = [ln for ln in rehearsal.stdout.splitlines()
             if ln.startswith("[perf] expert choices")]
    assert len(notes) == 1 and notes[0].endswith(" ok"), rehearsal.stdout
    assert "margin" in notes[0] and "choices forced" in notes[0]
    assert "gradients" in notes[0]
    free = [ln for ln in rehearsal.stdout.splitlines()
            if ln.startswith("[perf] one sequence's token losses")]
    assert len(free) == 1 and free[0].count("bound inf") == 2  # read, not held


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_benchmark_json_holds_the_cells_entries_in_order(kind, tmp_path):
    """By name and by order among themselves, never a whole list nor its
    end (benchmark_contracts.py): the configuration after ``gpt2_medium``,
    the cell after the two that were there, and after ``gpt2m.train`` on
    every ``workloads`` the two share; the four grouped-kernel entries (PR
    33; PR 29 had to leave them out) list the cell."""
    b = contracts.load(contracts.checkout(kind, tmp_path))
    assert contracts.subsequence(["gpt2_medium", "lfm2_8b_a1b"],
                                 [c["name"] for c in b["configs"]])
    assert contracts.subsequence(["gpt2m.train", "gpt2m.serve_closed", CELL],
                                 [w["name"] for w in b["workloads"]])
    reported = {m["name"]: m["workloads"] for m in
                b["end_to_end"] + b["per_layer"] if CELL in m.get(
                    "workloads", [])}
    assert set(END_TO_END + PER_LAYER) - {"setup_s", "window_compiles"} \
        <= set(reported)
    for name, cells in reported.items():
        if "gpt2m.train" in cells:
            assert contracts.subsequence(["gpt2m.train", CELL], cells), name
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in PER_LAYER[-4:]:
        m = per_layer[name]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "Kernels", "train_throughput_per_chip"), name
        assert (m["unit"], m["better"]) == (
            ("ms", "lower") if name.startswith("kernel_ms.")
            else ("%", "higher")), name
    assert b["run_seconds"] == 20


# ------------------------------------------ controls of the routed checks


@pytest.fixture(scope="module")
def driver():
    """The cell's driver after its own ``setup()`` at the rehearsal's
    sizes: seeded weights, the checks made once on them."""
    import jax

    from perf.run import Run

    cell = Cell(CELL, root=ROOT, rehearse=True)
    drv = cell.driver.Driver(cell, Run(cell, 3000000019, jax.devices()[:1],
                                       True, False))
    drv.setup()
    return drv


def _float8(params):
    """Every matrix rounded to float8_e4m3fn and back, eagerly (under one
    ``jit`` XLA cancels the round trip)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                        if a.ndim >= 2 else a, params)


def _with_experts(params, layer, change):
    out = dict(params)
    out[layer] = dict(out[layer])
    out[layer]["moe"] = {k: change(k, v) if k in ("w1", "w2", "w3") else v
                         for k, v in out[layer]["moe"].items()}
    return out


def _dropped_layer(params):
    import jax.numpy as jnp

    return _with_experts(params, "h_4", lambda k, v: jnp.zeros_like(v)
                         if k == "w2" else v)


def _neighbours_function(params):
    import jax.numpy as jnp

    return _with_experts(params, "h_4", lambda _k, v: jnp.roll(v, 1, axis=0))


def test_the_routed_checks_pass_the_seeded_weights(driver):
    assert driver.correct is True
    r = driver.routed_readings(driver.state.params)
    assert driver.routed_ok(r), r
    assert 0 < r["routed_grad_gap"] < 0.25 and r["routed_grad_leaf"]


@pytest.mark.parametrize("damage, fails", [
    (_float8, {"choice_gap", "routed_token_gap", "routed_grad_gap"}),
    (_dropped_layer, {"routed_token_gap", "routed_grad_gap"}),
    (_neighbours_function, {"routed_token_gap", "routed_grad_gap"})])
def test_the_routed_checks_fail_a_damaged_system(driver, damage, fails):
    """The system computing on damaged parameters, the reference on the
    sound ones, through the driver's own comparison: not ``ok``, and by
    the limits named (the precision control, float8, by every one)."""
    params = driver.state.params
    r = driver.routed_readings(params, damage(params))
    assert not driver.routed_ok(r), r
    failed = {name for name, limit in driver.cell.driver.CHECKS
              if not r[name] <= driver.traffic[limit]}
    assert fails <= failed, r
