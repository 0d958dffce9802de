"""The ``laguna.serve_mixed_8k`` cell's own files (PR 36), written as
contracts (benchmark_contracts.py): its configuration keeps the published
widths and states its cut, its held parameters and serving costs equal a
hand count, its four readers are right on a hand-made run (both branches of
the roofline's ``max``) and silent where there is nothing to read, its
entries are in ``BENCHMARK.json`` (on the tree and on a grown copy), the
cell rehearses on the CPU through ``perf/run.py`` and, in process, under
both presets (page smaller than the window, page equal to it) with the
routed half of ``correct``, and that half comes out false on float8
matrices, on sliding layers masked at half their window, on the full
layers' RoPE applied to the sliding layers, and on a wrong token from the
engine."""

import dataclasses
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

CELL, CONFIG_NAME, TRAFFIC_NAME = ("laguna.serve_mixed_8k", "laguna_xs2",
                                   "chat_mixed_8k")
CONFIG = load_json(os.path.join(ROOT, "perf", "configs", CONFIG_NAME + ".json"))
TRAFFIC = load_json(os.path.join(ROOT, "perf", "traffic",
                                 TRAFFIC_NAME + ".json"))
FULL_CONFIG = {k: v for k, v in CONFIG.items() if k != "rehearsal"}
fam = load_module("families", "laguna_moe")

# the catalog row's ``config`` (poolside/Laguna-XS.2 config.json); its
# three per-layer lists by their rule
FULL, SLIDING = "full_attention", "sliding_attention"
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
REDUCED = {"num_hidden_layers": 5,
           "layer_types": PUBLISHED["layer_types"][:5],
           "mlp_layer_types": PUBLISHED["mlp_layer_types"][:5],
           "num_attention_heads_per_layer":
               PUBLISHED["num_attention_heads_per_layer"][:5]}
D, DH, KV, V = 2048, 128, 8, 100352
ATTN = {h: D * h * DH * 2 + D * KV * DH * 2 + D * h for h in (48, 64)}
EXPERT = 3 * D * 512  # one expert's three matrices


def test_the_configuration_keeps_every_published_width():
    assert sorted(CONFIG["reduced"]) == sorted(REDUCED)
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == REDUCED.get(key, value), key
        if key in REDUCED:
            assert CONFIG["published"][key], key
    assert CONFIG["published"]["num_hidden_layers"] == 40
    # the floors of a cut: a whole period and four layers after the dense
    # one, every expert, the whole vocabulary; and what the cut stands for
    assert CONFIG["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    for key in ("source", "deployment", "assumed", "published"):
        assert CONFIG[key], key
    assert "no layer is shared between chips" in CONFIG["deployment"]
    assert "eight pipeline stages of five layers" in CONFIG["deployment"]
    assert {"gating", "router", "rope", "initialisers"} <= set(
        CONFIG["assumed"])
    assert "33.44B" in CONFIG["assumed"]["gating"]
    assert CONFIG["serve"]["weight_dtype"] == CONFIG["compute_dtype"] \
        == "bfloat16"
    # the two CPU presets: the page (the rehearsal traffic's 16) smaller
    # than the window, and equal to it; both windows under the prompts
    page = TRAFFIC["rehearsal"]["engine"]["prefill_chunk"]
    assert CONFIG["rehearsal"]["sliding_window"] > page \
        == CONFIG["rehearsal_page_is_window"]["sliding_window"]
    assert CONFIG["rehearsal"]["sliding_window"] < \
        TRAFFIC["rehearsal"]["prompt_len"]["median"]


def test_the_held_parameters_are_the_hand_count():
    import jax
    import jax.numpy as jnp

    model = fam.build_model(FULL_CONFIG)
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros(fam.init_input_shape(FULL_CONFIG), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    held = sum(a.size for a in jax.tree.leaves(shapes))
    layer = D * 256 + 256 * EXPERT + EXPERT  # router, experts, shared
    matrices = (ATTN[48] + 3 * D * 8192) + 3 * (ATTN[64] + layer) \
        + (ATTN[48] + layer) + 2 * V * D
    norms = 5 * 2 * D + D
    assert held == matrices + norms == CONFIG["parameters_held"] \
        == fam.parameters_held(FULL_CONFIG) == 3869857792
    assert ATTN[48] == pytest.approx(29.46e6, rel=1e-3)
    assert ATTN[64] + 2 * D + layer == pytest.approx(846.9e6, rel=1e-4)
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    # the published whole, by the same count: 33.44 B
    whole = 10 * ATTN[48] + 30 * ATTN[64] + 3 * D * 8192 + 39 * layer \
        + 2 * V * D
    assert whole == pytest.approx(33.44e9, rel=1e-3)


def test_serve_costs_are_the_hand_count():
    c = fam.serve_costs(FULL_CONFIG)
    attn = 2 * ATTN[48] + 3 * ATTN[64]
    around = D * 256 + EXPERT  # router and shared expert
    assert c["flops_per_token"] == 2 * (
        attn + 3 * D * 8192 + 4 * (around + 8 * EXPERT))
    assert c["flops_per_token"] == pytest.approx(675e6, rel=0.01)
    assert c["flops_per_logit"] == 2 * V * D
    # the two GLOBAL layers only: the window layers' bounded reads are
    # left out, so the share stays a lower bound
    assert c["flops_per_attended"] == 2 * 2 * 48 * 128 * 2
    assert c["bytes_per_cache_token"] == 2 * (2 * 8 * 128 * 2) == 8192
    touched = 1 - (1 - 8 / 256) ** 32
    assert touched == pytest.approx(0.638, abs=5e-4)
    assert c["bytes_per_run"] == pytest.approx(2 * (
        attn + 3 * D * 8192 + 4 * (around + touched * 256 * EXPERT)
        + 11 * D + V * D))
    # a decode run is bound by its bytes (5.0 GB = 6.1 ms at 819 GB/s
    # against 0.2 ms of operations); so is a 512-row chunk before its
    # attention is counted (1.8 ms of operations)
    assert c["bytes_per_run"] / 819e9 > 10 * 64 * c["flops_per_token"] / 197e12
    assert c["bytes_per_run"] / 819e9 > 512 * c["flops_per_token"] / 197e12
    assert fam.window_pool_pages(FULL_CONFIG, TRAFFIC["engine"]) == 128
    assert fam.window_pool_pages(
        {"sliding_window": 32}, {"num_slots": 8, "prefill_chunk": 16}) == 24


# a traced window of 0..10 s holding 4 engine steps (3 of the loop and one
# that drains): the two window kernels' calls inside it (3 of 0.02 s and 3
# of 0.01 s), one after it, and the full layers' calls, which share the
# prefix of the name and must not be counted
FORM = {"devices": {"/device:TPU:0": (
    [[f"paged_decode_window.{i} custom-call bf16[64,64,128]", 1.0 + i, 0.02,
      True, "custom-call"] for i in range(3)]
    + [[f"paged_prefill_window.{i} custom-call bf16[1,512,64,128]", 5.0 + i,
        0.01, True, "custom-call"] for i in range(3)]
    + [["paged_decode_window.7 custom-call bf16[64,64,128]", 11.0, 0.02,
        True, "custom-call"],
       ["paged_decode.2 custom-call bf16[64,48,128]", 8.0, 0.5, True,
        "custom-call"],
       ["paged_prefill.3 custom-call bf16[1,512,48,128]", 9.0, 0.3, True,
        "custom-call"]])},
    "async": {}, "host": [[trace.WINDOW_SPAN, 0.0, 10.0]]}
TRACED = {"steps": 3, "steps_with_drain": 4}
READERS = ["kernel_ms.paged_decode_window", "kernel_ms.paged_prefill_window",
           "paged_window_roofline", "window_pages_live_share"]


def _run(form, traced, stats, window_stats=None):
    """A run whose TRACED segment counted ``stats`` (the measured window
    counted ``window_stats``)."""
    return SimpleNamespace(
        cell=SimpleNamespace(name=CELL, family=fam, config=FULL_CONFIG,
                             traffic=TRAFFIC),
        rehearse=False, device_kind="TPU v5 lite",
        window={"engine_stats": window_stats if window_stats is not None
                else {"steps": 7, "window_rows_read": 1, "window_pairs": 1}},
        traced=traced and {**traced, "engine_stats": stats},
        trace_form=form, trace=trace.reduce(form) if form else None)


ROW = 2 * 8 * 128 * 2  # a cached token's K and V in one layer, bf16


@pytest.mark.parametrize("stats, least_ms", [
    # 100 steps whose window-layer calls read 60,000 rows and attended
    # 500,000 pairs each: the rows' bytes bound it (0.30 ms against 0.08)
    ({"steps": 100, "window_rows_read": 6000000, "window_pairs": 50000000},
     1e3 * 60000 * ROW / 819e9),
    # 100 steps of prefill-heavy calls, 4,000,000 pairs a step on 60,000
    # rows: the MXU bounds it (0.67 ms against 0.30)
    ({"steps": 100, "window_rows_read": 6000000, "window_pairs": 400000000},
     1e3 * 4000000 * 2 * 2 * 128 * 64 / 197e12)],
    ids=["bytes", "operations"])
def test_the_kernel_readers_on_a_hand_made_run(stats, least_ms):
    decode = load_module("metrics", READERS[0]).read
    prefill = load_module("metrics", READERS[1]).read
    share = load_module("metrics", READERS[2]).read
    run = _run(FORM, TRACED, stats)
    assert decode(run) == pytest.approx(1e3 * 3 * 0.02 / 4)
    assert prefill(run) == pytest.approx(1e3 * 3 * 0.01 / 4)
    took = decode(run) + prefill(run)
    assert share(run) == pytest.approx(100 * least_ms / took)
    assert 0 < share(run) < 100
    # the full layers' calls are the accepted readers', not these
    assert load_module("metrics", "kernel_ms.paged_decode").read(run) \
        == pytest.approx(1e3 * 0.5 / 4)


def test_the_live_share_reader_on_a_hand_made_run():
    read = load_module("metrics", READERS[3]).read
    stats = {"steps": 50, "decode_steps": 40, "window_pages_live": 40 * 96}
    assert read(_run(None, None, {}, stats)) == pytest.approx(75.0)  # of 128
    assert read(_run(None, None, {}, {"steps": 50, "decode_steps": 40})) \
        is None  # an engine without the counter
    assert read(_run(None, None, {}, {})) is None
    other = _run(None, None, {}, stats)
    other.cell.family = SimpleNamespace()  # a family without a window pool
    assert read(other) is None


@pytest.mark.parametrize("metric", READERS[:3])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, no such kernel in it, an engine without the counters (the
    parent of this PR), or a family without the count: None, never an
    exception."""
    read = load_module("metrics", metric).read
    stats = {"steps": 100, "window_rows_read": 6400, "window_pairs": 9}
    assert read(_run(None, None, stats)) is None
    bare = {"devices": {"/device:TPU:0": FORM["devices"]["/device:TPU:0"][-2:]},
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
END_TO_END = ["serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"]
PER_LAYER = [
    "window_compiles", "slot_occupancy", "engine_step_ms",
    "decode_device_ms", "kernel_share.serve", "engine_host_ms",
    "engine_fetch_wait_ms", "first_token_sync_ms",
    "ttft_prefill_wait_share", "kernel_ms.paged_decode",
    "kernel_ms.paged_prefill", "serve_mfu", "kernel_ms.serve_moe_gmm",
    "serve_moe_gmm_roofline", *READERS]
# (`itl_p95_ms` IS reported here, with the two per-layer metrics that move
# it: two sets of six seeds spread it 0.8%, PERF.md section 6)


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_cell_reports_what_benchmark_json_says(kind, tmp_path):
    cell = Cell(CELL, root=contracts.checkout(kind, tmp_path))
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_closed_routed"
    assert contracts.subsequence(
        END_TO_END, [m["name"] for m in cell.metrics("end_to_end")])
    reported = [m["name"] for m in cell.metrics("per_layer")]
    assert contracts.subsequence(PER_LAYER, reported)
    # another family's attention, a train cell's kernel
    assert not {"latent_attn_ms", "kernel_ms.moe_gmm"} & set(reported)
    # the limits of `correct` that this cell brings, each with its readings
    for _, limit in cell.driver.CHECKS:
        assert cell.traffic[limit] > 0 and limit in cell.traffic["limits"]
    assert 0 < cell.traffic["oracle"]["logit_gap"] < float("inf")
    assert "oracle.logit_gap" in cell.traffic["limits"]
    for fault in ("float8", "window", "RoPE"):
        assert fault in cell.traffic["limits"]["_readings"], fault
    # the engine the issue names: every slot's full reservation of global
    # pages; the window pool's size is not an option
    assert cell.traffic["engine"] == {
        "num_slots": 64, "max_len": 8192, "prefill_chunk": 512,
        "kv_pages": 1024}
    assert cell.traffic["callers"] == 64 and cell.traffic["ramp_steps"] == 400
    # the watched request crosses four pages, so that the window layers
    # free three while it is watched
    check = cell.traffic["check"]
    assert check["prompt_len"] + check["decode_positions"] > 4 * 512
    assert check["beside"]["before"] >= 16 and check["beside"]["after"] >= 4


def test_the_traffic_is_the_issues():
    from perf.harness.loadgen import request_pool

    pool = request_pool(TRAFFIC)
    prompts, outputs = zip(*pool)
    assert len(pool) == 256
    assert (min(prompts), max(prompts)) == (128, 7680)
    assert (min(outputs), max(outputs)) == (48, 512)
    assert sorted(prompts)[128] == pytest.approx(1536, rel=0.01)
    assert sorted(outputs)[128] == pytest.approx(192, rel=0.01)
    # short and long in one queue: a tenth under 430 tokens, a tenth over
    # 5,500, one in twenty at the cap
    assert sum(p < 430 for p in prompts) == pytest.approx(25.6, abs=1.5)
    assert sum(p > 5500 for p in prompts) == pytest.approx(25.6, abs=1.5)
    assert sum(p == 7680 for p in prompts) == pytest.approx(12.8, abs=1.5)
    assert max(p + o for p, o in pool) <= TRAFFIC["engine"]["max_len"]
    # ~4.9 prefill turns of 512 a request in the mean, up to 15
    turns = [-(-p // 512) for p in prompts]
    assert sum(turns) / 256 == pytest.approx(4.9, abs=0.15)
    assert max(turns) == 15


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_benchmark_json_holds_the_cells_entries_in_order(kind, tmp_path):
    """By name and by order among themselves, never a whole list nor its
    end: the configuration after the three that were there, the cell after
    the four, and after the other serving cells on every ``workloads`` it
    shares with them; its own four per-layer entries list it alone."""
    b = contracts.load(contracts.checkout(kind, tmp_path))
    assert contracts.subsequence(
        ["gpt2_medium", "lfm2_8b_a1b", "pangu_ultra_moe_718b", CONFIG_NAME],
        [c["name"] for c in b["configs"]])
    entry = {c["name"]: c for c in b["configs"]}[CONFIG_NAME]
    assert entry["source"] == CONFIG["source"] and sorted(
        entry["reduced"]) == sorted(REDUCED)
    assert entry["file"] == f"perf/configs/{CONFIG_NAME}.json"
    assert contracts.subsequence(
        ["gpt2m.train", "gpt2m.serve_closed", "lfm2moe.train_8k",
         "pangu_moe.serve_closed_2k", CELL],
        [w["name"] for w in b["workloads"]])
    work = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (work["config"], work["traffic"], work["chips"]) == (
        CONFIG_NAME, TRAFFIC_NAME, 1) and len(work["why"]) <= 200
    reported = {m["name"]: m["workloads"] for m in
                b["end_to_end"] + b["per_layer"] if CELL in m.get(
                    "workloads", [])}
    assert set(END_TO_END + PER_LAYER) - {"setup_s", "window_compiles"} \
        == set(reported) - {contracts.NEW_METRIC}
    for name, cells in reported.items():
        for other in ("gpt2m.serve_closed", "pangu_moe.serve_closed_2k"):
            if other in cells:
                assert contracts.subsequence([other, CELL], cells), name
    per_layer = {m["name"]: m for m in b["per_layer"]}
    assert contracts.subsequence(["latent_attn_ms", *READERS],
                                 list(per_layer))
    for name in READERS:
        m = per_layer[name]
        assert m["moves"] == "serve_tokens_per_s", name
        assert (m["source"], m["layer"]) == (
            ("program_counter", "Window layers")
            if name == "window_pages_live_share"
            else ("device_trace", "Kernels")), name
        assert (m["unit"], m["better"]) == (
            ("ms", "lower") if name.startswith("kernel_ms.")
            else ("%", "higher" if name.endswith("_roofline") else "lower")), \
            name
        cells = set(m["workloads"]) - {contracts.NEW_CELL}
        assert cells == {CELL}, name
    assert b["run_seconds"] == 20


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         CELL, "--seed", "3600001002", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=900)


def test_the_cell_rehearses_on_the_cpu(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    line = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    # what a CPU can read of the cell's per-layer metrics: no device plane,
    # no kernel; no peaks, no share of them; the window pool's counter
    assert {"window_compiles", "slot_occupancy", "engine_step_ms",
            "engine_host_ms", "engine_fetch_wait_ms",
            "first_token_sync_ms", "ttft_prefill_wait_share",
            "window_pages_live_share"} \
        <= set(line["metrics"]) <= set(PER_LAYER)
    assert not any(name.startswith("kernel_") or name.endswith("_roofline")
                   or name == "serve_mfu" for name in line["metrics"])
    assert 0 < line["metrics"]["window_pages_live_share"]["value"] <= 100
    assert "compiles in window=0 " in rehearsal.stdout
    for name in END_TO_END:
        assert f'"{name}": ' in rehearsal.stdout, name
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
    assert len(free) == 1 and "(bound 0.8) ok" in free[0]  # held
    assert "resolved einsum" in free[0]


# ------------------------------------------ controls of the routed checks


def _preset(name):
    """The rehearsal cell; under ``page=window`` with the configuration's
    second preset laid over the first."""
    cell = Cell(CELL, root=ROOT, rehearse=True)
    if name == "page=window":
        cell.config = {**cell.config, **{
            k: v for k, v in CONFIG["rehearsal_page_is_window"].items()
            if not k.startswith("_")}}
    cell.traffic = {**cell.traffic, "callers": 2, "ramp_steps": 2}
    return cell


@pytest.fixture(scope="module", params=["page<window", "page=window"])
def driver(request):
    """The cell's driver after its own ``setup()`` at a rehearsal preset's
    sizes (bfloat16, as the cell): seeded weights, the checks made once on
    them."""
    import jax

    from perf.run import Run

    cell = _preset(request.param)
    drv = cell.driver.Driver(cell, Run(cell, 3600001002, jax.devices()[:1],
                                       True, False))
    drv.setup()
    yield drv
    drv.close()


def _float8(model, params):
    """Every matrix rounded to float8_e4m3fn and back, eagerly (under one
    ``jit`` XLA cancels the round trip)."""
    import jax
    import jax.numpy as jnp

    return model, jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)


def _window_halved(model, params):
    """The sliding layers masked at half their window."""
    cfg = model.config
    return type(model)(dataclasses.replace(
        cfg, sliding_window=cfg.sliding_window // 2)), params


def _rope_swapped(model, params):
    """The full layers' RoPE applied on the sliding layers."""
    cfg = model.config
    return type(model)(dataclasses.replace(
        cfg, rope_sliding=cfg.rope_full)), params


def _readings(driver, damage=None, hook=None):
    """The driver's two passes: the system (engine and library forward)
    on the damaged model or weights, the reference on the sound weights
    under the configuration file."""
    from tpudp.serve import Engine

    params = driver.engine.params
    engine = driver.engine
    if damage is not None:
        model, damaged = damage(driver.engine.model, params)
        engine = Engine(model, damaged, **driver.traffic["engine"])
    try:
        system = driver.system_pass(engine.params, engine,
                                    token_fault_hook=hook)
        freed = engine.metrics()["stats"].get("window_pages_freed", 0)
    finally:
        if engine is not driver.engine:
            engine.close()
    return {**driver.reference_pass(params, system), "freed": freed}


def test_the_routed_checks_pass_the_seeded_weights(driver):
    assert driver.correct is True, driver.compared
    r = _readings(driver)
    assert driver.routed_ok(r), r
    assert r["positions"] == 76 and r["engine_tokens"] == 7
    assert r["rows_beside"] >= 4
    # the engine freed window pages behind the watched request (and the
    # requests beside it) while the library pass, with every page mapped,
    # gave the logits its tokens are held to
    assert r["freed"] >= 3


@pytest.mark.parametrize("damage, fails", [
    (_float8, {"choice_gap", "routed_logit_gap"}),
    (_window_halved, {"routed_logit_gap"}),
    (_rope_swapped, {"routed_logit_gap"})])
def test_the_routed_checks_fail_a_damaged_system(driver, damage, fails):
    """Not ``ok``, and by the limits named.  The engine's own reading
    stays sound: the engine and the library forward share the damage."""
    r = _readings(driver, damage)
    assert not driver.routed_ok(r), r
    failed = {name for name, limit in driver.cell.driver.CHECKS
              if not r[name] <= driver.traffic[limit]}
    assert fails <= failed and "engine_logit_gap" not in failed, r


def test_a_wrong_token_from_the_engine_fails_the_engine_check(driver):
    """A token the scheduler commits that the forward did not choose is
    what ``engine_logit_gap`` holds: the other two readings stay sound."""
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
