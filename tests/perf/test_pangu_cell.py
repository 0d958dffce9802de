"""The ``pangu_moe.serve_closed_2k`` cell's own files (PR 34), written as
contracts (benchmark_contracts.py): its configuration keeps the published
widths and states its cut, its serving costs equal a hand count, its three
readers are right on a hand-made run (both branches of the roofline's
``max``) and silent where there is nothing to read, its entries are in
``BENCHMARK.json`` (on the tree and on a grown copy), the cell rehearses on
the CPU through ``perf/run.py`` with the routed half of ``correct``, and
that half comes out false on float8 matrices, a dropped shared expert,
experts that compute a neighbour's function and a wrong token from the
engine."""

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

CELL, CONFIG_NAME = "pangu_moe.serve_closed_2k", "pangu_ultra_moe_718b"
CONFIG = load_json(os.path.join(ROOT, "perf", "configs", CONFIG_NAME + ".json"))
TRAFFIC = load_json(os.path.join(ROOT, "perf", "traffic", "chat_closed_2k.json"))
fam = load_module("families", "pangu_moe")

# the catalog row's ``config`` (openPangu-Ultra-MoE-718B config.json)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 19200,
           "num_nextn_predict_layers": 0}


def test_the_configuration_keeps_every_published_width():
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert CONFIG["published"][key] == value, key
    # the floors of a cut: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary; and what the cut stands for
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert (CONFIG["num_experts_routed"], CONFIG["first_expert"]) == (256, 0)
    for key in ("source", "deployment", "assumed", "published"):
        assert CONFIG[key], key
    assert "16 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["serve"]["weight_dtype"] == CONFIG["compute_dtype"] \
        == "bfloat16"


def test_the_held_parameters_are_the_hand_count():
    import jax
    import jax.numpy as jnp

    full = {k: v for k, v in CONFIG.items() if k != "rehearsal"}
    model = fam.build_model(full)
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros(fam.init_input_shape(full), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    held = sum(a.size for a in jax.tree.leaves(shapes))
    d, h = 7680, 128
    attn = d * 1536 + 1536 * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    expert = 3 * d * 2048
    matrices = (attn + 3 * d * 18432) + 4 * (attn + d * 256 + 17 * expert) \
        + 2 * 19200 * d
    norms = 5 * (4 * d + 1536 + 512) + d
    assert held == matrices + norms == CONFIG["parameters_held"]
    assert attn == pytest.approx(196.6e6, rel=1e-3)
    assert held == pytest.approx(4.92e9, rel=1e-3)
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}


def test_serve_costs_are_the_hand_count():
    c = fam.serve_costs({k: v for k, v in CONFIG.items() if k != "rehearsal"})
    d, h = 7680, 128
    attn = d * 1536 + 1536 * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    expert = 3 * d * 2048
    around = attn + d * 256 + expert
    assert c["flops_per_token"] == pytest.approx(
        2 * ((attn + 3 * d * 18432) + 4 * (around + 8 * 16 / 256 * expert)))
    assert c["flops_per_logit"] == 2 * 19200 * d
    assert c["flops_per_attended"] == 2 * 5 * h * 320
    touched = 1 - (1 - 8 / 256) ** 32
    assert touched == pytest.approx(0.638, abs=5e-4)
    assert c["bytes_per_run"] == pytest.approx(2 * (
        (attn + 3 * d * 18432) + 4 * (around + touched * 16 * expert)
        + 5 * (4 * d + 1536 + 512) + d + 19200 * d))
    assert c["bytes_per_cache_token"] == 5 * 1152
    # a decode run is bound by its bytes (9.0 ms at 819 GB/s against 1.1
    # ms of operations); a 512-row chunk's operations take as long as its
    # bytes before its attention is counted (8.8 ms)
    assert c["bytes_per_run"] / 819e9 > 8 * 64 * c["flops_per_token"] / 197e12
    assert 512 * c["flops_per_token"] / 197e12 == pytest.approx(
        c["bytes_per_run"] / 819e9, rel=0.05)


# a traced window of 0..10 s holding 4 engine steps (3 of the loop and one
# that drains): 8 moe_gmm calls of 0.01 s inside, one outside, and a fusion
# that only shares the prefix; a prefill chunk's and a decode run's
# attention loops (0.2 s and 0.04 s, a product nested in the first), a
# third outside the window and the expert layer's row-gather loop
_CARRY = "(s32[], f32[{0},128], f32[{0},128], f32[{0},128,512], s32[], "
FORM = {"devices": {"/device:TPU:0": (
    [[f"moe_gmm.{i} custom-call bf16[512,2048]", float(i), 0.01, True,
      "custom-call"] for i in range(8)]
    + [["moe_gmm.9 custom-call bf16[512,2048]", 11.0, 0.01, True,
        "custom-call"],
       ["moe_gmm_cast.1 fusion:kLoop bf16[16,7680,2048]", 9.0, 0.3, False,
        "fusion"],
       ["while.132 while " + _CARRY.format("1,512"), 2.5, 0.2, False,
        "while"],
       ["bitcast_add_fusion.27 fusion:kOutput f32[1,512,128,512]", 2.55,
        0.1, False, "fusion"],
       ["while.52 while " + _CARRY.format("64,1"), 3.5, 0.04, False,
        "while"],
       ["while.53 while " + _CARRY.format("64,1"), 12.0, 0.04, False,
        "while"],
       ["while.122 while (s32[], bf16[4096,7680], s32[], s32[4096], ", 4.5,
        0.05, False, "while"]])},
    "async": {}, "host": [[trace.WINDOW_SPAN, 0.0, 10.0]]}
TRACED = {"steps": 3, "steps_with_drain": 4}


def _run(form, traced, stats):
    """A run whose TRACED segment counted ``stats`` (the measured window
    counted something else: the share must not read it)."""
    config = {k: v for k, v in CONFIG.items() if k != "rehearsal"}
    return SimpleNamespace(
        cell=SimpleNamespace(name=CELL, family=fam, config=config,
                             traffic=TRAFFIC),
        rehearse=False, device_kind="TPU v5 lite",
        window={"engine_stats": {"steps": 7, "moe_experts_touched": 1,
                                 "moe_rows_held": 1}},
        traced=traced and {**traced, "engine_stats": stats},
        trace_form=form, trace=trace.reduce(form) if form else None)


EACH = 3 * 7680 * 2048  # one expert's three matrices


@pytest.mark.parametrize("stats, least_ms", [
    # 100 steps that touched 110 experts each and held 300 rows: the
    # matrices' bytes bound it (110 x 94.4 MB at 819 GB/s = 12.7 ms;
    # the rows' products at 197 TFLOP/s are 0.14 ms)
    ({"steps": 100, "moe_experts_touched": 11000, "moe_rows_held": 30000},
     1e3 * 110 * EACH * 2 / 819e9),
    # 100 steps that touched 64 experts and held 30,000 rows each: the
    # MXU bounds it (14.4 ms against 7.4 of bytes)
    ({"steps": 100, "moe_experts_touched": 6400, "moe_rows_held": 3000000},
     1e3 * 30000 * 2 * EACH / 197e12)],
    ids=["bytes", "operations"])
def test_the_two_readers_on_a_hand_made_run(stats, least_ms):
    took = 1e3 * 8 * 0.01 / 4  # ms an engine step, the drain's included
    kernel = load_module("metrics", "kernel_ms.serve_moe_gmm").read
    share = load_module("metrics", "serve_moe_gmm_roofline").read
    run = _run(FORM, TRACED, stats)
    assert kernel(run) == pytest.approx(took) == pytest.approx(20.0)
    assert share(run) == pytest.approx(100 * least_ms / took)
    assert 0 < share(run) < 100


def test_the_attention_reader_on_a_hand_made_run():
    """The two loops inside the window, whole (the product nested in the
    first is part of it, not added to it), over the four steps; not the
    loop after the window, not the row gather's."""
    read = load_module("metrics", "latent_attn_ms").read
    run = _run(FORM, TRACED, {})
    assert read(run) == pytest.approx(1e3 * (0.2 + 0.04) / 4)
    run.cell.config = {**run.cell.config, "kv_lora_rank": 256}
    assert read(run) is None  # another model's carry
    run.cell.config = {"n_layer": 24}  # a family without latent attention
    assert read(run) is None


@pytest.mark.parametrize("metric", ["kernel_ms.serve_moe_gmm",
                                    "serve_moe_gmm_roofline",
                                    "latent_attn_ms"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, no such kernel in it, an engine without the counters (the
    parent of this PR), or a family without the count: None, never an
    exception."""
    read = load_module("metrics", metric).read
    stats = {"steps": 100, "moe_experts_touched": 6400, "moe_rows_held": 9}
    assert read(_run(None, None, stats)) is None
    bare = {"devices": {"/device:TPU:0": [FORM["devices"]["/device:TPU:0"][-1]]},
            "async": {}, "host": FORM["host"]}
    assert read(_run(bare, TRACED, stats)) is None
    if "roofline" in metric:
        assert read(_run(FORM, TRACED, {"steps": 100})) is None
        assert read(_run(FORM, TRACED, {})) is None
        untraced = _run(FORM, TRACED, stats)
        del untraced.traced["engine_stats"]  # the window's is not read
        assert read(untraced) is None
        other = _run(FORM, TRACED, stats)
        other.cell.family = SimpleNamespace()
        assert read(other) is None


# what the cell reports: the entries this file knows, each list in the
# order BENCHMARK.json has them among themselves
END_TO_END = ["serve_tokens_per_s", "ttft_p95_ms", "setup_s"]
PER_LAYER = [
    "window_compiles", "slot_occupancy", "decode_device_ms",
    "kernel_share.serve", "engine_fetch_wait_ms", "first_token_sync_ms",
    "ttft_prefill_wait_share", "serve_mfu", "kernel_ms.serve_moe_gmm",
    "serve_moe_gmm_roofline", "latent_attn_ms"]
# Left out of this cell, with the two per-layer metrics that move it: the
# 95th percentile of its token gaps sits on a lump of steps (PERF.md
# section 6), so sets of six spread by more than half its bound
NOT_REPORTED = ["itl_p95_ms", "engine_step_ms", "engine_host_ms"]


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_cell_reports_what_benchmark_json_says(kind, tmp_path):
    cell = Cell(CELL, root=contracts.checkout(kind, tmp_path))
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_closed_routed"
    assert contracts.subsequence(
        END_TO_END, [m["name"] for m in cell.metrics("end_to_end")])
    reported = [m["name"] for m in cell.metrics("per_layer")]
    assert contracts.subsequence(PER_LAYER, reported)
    # kernels that do not run here, and a reader that divides by train steps
    assert not {"kernel_ms.paged_decode", "kernel_ms.paged_prefill",
                "kernel_ms.moe_gmm", *NOT_REPORTED} & set(reported)
    assert "itl_p95_ms" not in [m["name"] for m in cell.metrics("end_to_end")]
    # the limits of `correct` that this cell brings, each with its readings
    assert [limit for _, limit in cell.driver.CHECKS] == [
        "route_tie_margin", "routed_logit_atol", "engine_logit_gap"]
    for _, limit in cell.driver.CHECKS:
        assert cell.traffic[limit] > 0 and limit in cell.traffic["limits"]
    # the base class's oracle is held too
    assert 0 < cell.traffic["oracle"]["logit_gap"] < float("inf")
    assert "oracle.logit_gap" in cell.traffic["limits"]
    # the check's request is served among others, more than it alone
    beside = cell.traffic["check"]["beside"]
    assert beside["before"] >= 16 and beside["after"] >= 4
    assert beside["new_tokens_before"] > 2 * cell.traffic["check"][
        "decode_positions"]
    assert cell.traffic["engine"] == {
        "num_slots": 64, "max_len": 8192, "prefill_chunk": 512,
        "kv_pages": cell.traffic["engine"]["kv_pages"]}
    assert cell.traffic["engine"]["kv_pages"] % 64 == 0 \
        and cell.traffic["engine"]["kv_pages"] >= 640
    assert cell.traffic["callers"] == 64 and cell.traffic["ramp_steps"] == 400
    assert cell.traffic["check"]["prompt_len"] > 4 * 512


def test_the_traffic_is_the_issues():
    from perf.harness.loadgen import request_pool

    pool = request_pool(TRAFFIC)
    prompts, outputs = zip(*pool)
    assert len(pool) == 256
    assert (min(prompts), max(prompts)) == (512, 6144)
    assert (min(outputs), max(outputs)) == (48, 512)
    assert sorted(prompts)[128] == pytest.approx(2048, rel=0.01)
    assert sorted(outputs)[128] == pytest.approx(192, rel=0.01)
    assert max(p + o for p, o in pool) <= TRAFFIC["engine"]["max_len"]


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_benchmark_json_holds_the_cells_entries_in_order(kind, tmp_path):
    """By name and by order among themselves, never a whole list nor its
    end: the configuration after the two that were there, the cell after
    the three, and after ``gpt2m.serve_closed`` on every ``workloads``
    the two share; its own two per-layer entries list it."""
    b = contracts.load(contracts.checkout(kind, tmp_path))
    assert contracts.subsequence(
        ["gpt2_medium", "lfm2_8b_a1b", CONFIG_NAME],
        [c["name"] for c in b["configs"]])
    entry = {c["name"]: c for c in b["configs"]}[CONFIG_NAME]
    assert entry["source"] == CONFIG["source"] and sorted(
        entry["reduced"]) == sorted(REDUCED)
    assert entry["file"] == f"perf/configs/{CONFIG_NAME}.json"
    assert contracts.subsequence(
        ["gpt2m.train", "gpt2m.serve_closed", "lfm2moe.train_8k", CELL],
        [w["name"] for w in b["workloads"]])
    work = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG_NAME, "chat_closed_2k", 1) and len(work["why"]) <= 200
    reported = {m["name"]: m["workloads"] for m in
                b["end_to_end"] + b["per_layer"] if CELL in m.get(
                    "workloads", [])}
    assert set(END_TO_END + PER_LAYER) - {"setup_s", "window_compiles"} \
        == set(reported) - {contracts.NEW_METRIC}
    for name, cells in reported.items():
        if "gpt2m.serve_closed" in cells:
            assert contracts.subsequence(["gpt2m.serve_closed", CELL],
                                         cells), name
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in PER_LAYER[-3:]:
        m = per_layer[name]
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "Latent attention" if name == "latent_attn_ms"
            else "Kernels", "serve_tokens_per_s"), name
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if name.endswith("_roofline")
            else ("ms", "lower")), name
        assert CELL in m["workloads"] and "gpt2m.serve_closed" not in \
            m["workloads"]
    assert b["run_seconds"] == 20


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         CELL, "--seed", "3400000019", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=900)


def test_the_cell_rehearses_on_the_cpu(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    line = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    # what a CPU can read of the cell's per-layer metrics: no device plane,
    # no kernel; no peaks, no share of them
    assert {"window_compiles", "slot_occupancy", "engine_fetch_wait_ms",
            "first_token_sync_ms", "ttft_prefill_wait_share"} \
        <= set(line["metrics"]) <= set(PER_LAYER)
    assert not any(name.startswith("kernel_") or name.endswith("_roofline")
                   or name in ("serve_mfu", "latent_attn_ms")
                   for name in line["metrics"])
    assert "compiles in window=0 " in rehearsal.stdout
    for name in END_TO_END:
        assert f'"{name}": ' in rehearsal.stdout, name
    # the three limits this cell brings and the base class's oracle, each
    # beside its reading
    assert list(line)[-1] == "compared" and set(line["compared"]) == {
        "oracle_logit_gap", "choice_gap", "routed_logit_gap",
        "engine_logit_gap", "failed", "window_compiles"}
    for name, held in line["compared"].items():
        assert held["value"] <= held["limit"] < float("inf"), name


def test_the_rehearsal_makes_the_routed_comparisons(rehearsal):
    notes = [ln for ln in rehearsal.stdout.splitlines()
             if ln.startswith("[perf] routed check")]
    assert len(notes) == 1 and notes[0].endswith(" ok"), rehearsal.stdout
    assert "margin" in notes[0] and "choices forced" in notes[0]
    assert "the engine's 7 tokens, decoded beside " in notes[0]
    free = [ln for ln in rehearsal.stdout.splitlines()
            if ln.startswith("[perf] oracle:")]
    assert len(free) == 1 and "(bound 0.3) ok" in free[0]  # held
    assert "resolved einsum" in free[0]


def test_the_rehearsal_does_not_report_kernel_metrics_on_stdout(rehearsal):
    line = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device",
            "compared"} <= set(line)


# ------------------------------------------ controls of the routed checks


@pytest.fixture(scope="module")
def driver():
    """The cell's driver after its own ``setup()`` at the rehearsal's
    sizes (bfloat16, as the cell): seeded weights, the checks made once on
    them."""
    import jax

    from perf.run import Run

    cell = Cell(CELL, root=ROOT, rehearse=True)
    cell.traffic = {**cell.traffic, "callers": 2, "ramp_steps": 2}
    drv = cell.driver.Driver(cell, Run(cell, 3400000019, jax.devices()[:1],
                                       True, False))
    drv.setup()
    yield drv
    drv.close()


def _float8(params):
    """Every matrix rounded to float8_e4m3fn and back, eagerly (under one
    ``jit`` XLA cancels the round trip)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
                        if a.ndim >= 2 else a, params)


def _with_layer(params, layer, change):
    out = dict(params)
    out[layer] = change(dict(out[layer]))
    return out


def _dropped_shared_expert(params):
    import jax

    def change(blk):
        blk["shared"] = jax.tree.map(lambda a: a * 0, blk["shared"])
        return blk

    return _with_layer(params, "h_2", change)


def _neighbours_experts(params):
    import jax.numpy as jnp

    def change(blk):
        blk["moe"] = {k: jnp.roll(v, 1, axis=0) if k in ("w1", "w2", "w3")
                      else v for k, v in blk["moe"].items()}
        return blk

    return _with_layer(params, "h_2", change)


def _readings(driver, damage=None, hook=None):
    from tpudp.serve import Engine

    params = driver.engine.params
    engine = driver.engine if damage is None else Engine(
        driver.engine.model, damage(params), **driver.traffic["engine"])
    try:
        system = driver.system_pass(engine.params, engine,
                                    token_fault_hook=hook)
    finally:
        if engine is not driver.engine:
            engine.close()
    return driver.reference_pass(params, system)


def test_the_routed_checks_pass_the_seeded_weights(driver):
    assert driver.correct is True
    r = _readings(driver)
    assert driver.routed_ok(r), r
    assert r["positions"] == 76 and r["engine_tokens"] == 7
    # it decoded beside the four requests submitted before it at the least
    assert r["rows_beside"] >= 4


@pytest.mark.parametrize("damage, fails", [
    (_float8, {"choice_gap", "routed_logit_gap"}),
    (_dropped_shared_expert, {"routed_logit_gap"}),
    (_neighbours_experts, {"routed_logit_gap"})])
def test_the_routed_checks_fail_a_damaged_system(driver, damage, fails):
    """The system (engine and library forward) on damaged parameters, the
    reference on the sound ones, through the driver's own two passes: not
    ``ok``, and by the limits named.  The engine's own reading stays
    sound: the engine and the library forward share the damage."""
    r = _readings(driver, damage)
    assert not driver.routed_ok(r), r
    failed = {name for name, limit in driver.cell.driver.CHECKS
              if not r[name] <= driver.traffic[limit]}
    assert fails <= failed and "engine_logit_gap" not in failed, r


def test_a_wrong_token_from_the_engine_fails_the_engine_check(driver):
    """A token the scheduler commits that the forward did not choose (the
    engine's own silent-corruption seam) is what ``engine_logit_gap``
    holds: the other two readings stay sound."""
    seen = []

    def hook(slot, tok, request):
        if len(request.prompt) != driver.traffic["check"]["prompt_len"]:
            return tok  # a request beside the check's
        seen.append(tok)
        return (tok + 1) % 256 if len(seen) == 3 else tok

    r = _readings(driver, hook=hook)
    failed = {name for name, limit in driver.cell.driver.CHECKS
              if not r[name] <= driver.traffic[limit]}
    assert failed == {"engine_logit_gap"}, r
