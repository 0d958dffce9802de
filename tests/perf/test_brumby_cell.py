"""The ``brumby.serve_closed_longout`` cell's own files, written as
contracts (benchmark_contracts.py): its configuration keeps every published
number and states its cut, its held parameters and serving costs equal a
hand count, its two readers are right on a hand-made run (both branches of
the roofline's ``max``) and silent where there is nothing to read, its
entries are in ``BENCHMARK.json`` (on the tree and on a grown copy), a
system without the family fails the cell at once with a ``CellError``, the
cell rehearses on the CPU through ``perf/run.py``, and in process the
logits half of ``correct`` comes out false on float8 matrices, without the
gate, at degree 1, and on a wrong token from the engine; set-up freezes the
heap for the window and ``close`` thaws it."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]

import benchmark_contracts as contracts  # noqa: E402
from perf.harness import trace  # noqa: E402
from perf.harness.cells import Cell, CellError, load_json, load_module  # noqa: E402

CELL, CONFIG_NAME, TRAFFIC_NAME = ("brumby.serve_closed_longout",
                                   "brumby_14b_base", "chat_closed_longout")
CONFIG = load_json(os.path.join(ROOT, "perf", "configs", CONFIG_NAME + ".json"))
TRAFFIC = load_json(os.path.join(ROOT, "perf", "traffic",
                                 TRAFFIC_NAME + ".json"))
FULL_CONFIG = {k: v for k, v in CONFIG.items() if k != "rehearsal"}
fam = load_module("families", "brumby_retention")

# the catalog row's ``config`` (manifestai/Brumby-14B-Base config.json)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4}
D, F, H, KV, DH, V = 5120, 17408, 40, 8, 128, 151936
LAYER = 2 * D * H * DH + 2 * D * KV * DH + 2 * DH + D * KV + 3 * D * F + 2 * D
PHI = DH * (DH + 1) // 2


def test_the_configuration_keeps_every_published_number():
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == REDUCED.get(key, value), key
    assert CONFIG["published"]["num_hidden_layers"] == 40
    for key in ("source", "deployment", "assumed", "published"):
        assert CONFIG[key], key
    assert "ten pipeline stages of four layers" in CONFIG["deployment"]
    assert {"normaliser", "gate", "qk_norm", "rope", "initialisers"} <= set(
        CONFIG["assumed"])
    assert CONFIG["serve"] == {"weight_dtype": "bfloat16",
                               "state_dtype": "float32", "fold_tokens": 128}
    assert CONFIG["compute_dtype"] == "bfloat16"
    # the rehearsal preset: width 128, 4 / 2 heads of 16
    # (D = 136), two layers, vocabulary 256; its fold block divides the
    # rehearsal traffic's chunk, as the cell's 128 divides 512
    r = CONFIG["rehearsal"]
    assert (r["hidden_size"], r["num_attention_heads"],
            r["num_key_value_heads"], r["head_dim"], r["num_hidden_layers"],
            r["vocab_size"]) == (128, 4, 2, 16, 2, 256)
    for preset, tr in ((CONFIG, TRAFFIC), (r, TRAFFIC["rehearsal"])):
        assert tr["engine"]["prefill_chunk"] % preset["serve"][
            "fold_tokens"] == 0


def test_the_held_parameters_are_the_hand_count():
    import jax
    import jax.numpy as jnp

    model = fam.build_model(FULL_CONFIG)
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros(fam.init_input_shape(FULL_CONFIG), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    held = sum(a.size for a in jax.tree.leaves(shapes))
    assert LAYER == fam.layer_parameters(FULL_CONFIG) == 330_352_896
    assert held == 4 * LAYER + 2 * V * D + D == CONFIG["parameters_held"] \
        == fam.parameters_held(FULL_CONFIG) == 2_877_241_344
    assert {str(a.dtype) for a in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert 40 * LAYER + 2 * V * D + D == 14_769_945_600  # the whole: "14B"
    assert model.config.state_features == PHI == 8256


def test_serve_costs_are_the_hand_count():
    c = fam.serve_costs(FULL_CONFIG)
    matmuls = 2 * D * H * DH + 2 * D * KV * DH + D * KV + 3 * D * F
    assert c["flops_per_token"] == 4 * (2 * matmuls
                                        + (H + KV) * 2 * PHI * (DH + 1))
    assert c["flops_per_logit"] == 2 * V * D
    # retention keeps no K/V by token: the state's bytes are not priced
    assert c["flops_per_attended"] == 0 == c["bytes_per_cache_token"]
    assert c["bytes_per_run"] == 2 * (4 * LAYER + V * D + D)
    assert c["bytes_per_run"] == pytest.approx(4.20e9, rel=0.01)


def test_the_decode_least_time_is_the_state_bytes():
    """48 decoding slots of four layers: 192 slot-layers of 8 KV heads'
    ``(8,256, 128)`` + ``(8,256,)`` float32 = 6.54 GB, 7.99 ms at 819 GB/s;
    the operations are 0.08 ms at the MXU's peak."""
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    state = 192 * KV * PHI * (DH + 1) * 4
    assert state == pytest.approx(6.54e9, rel=1e-3)
    rows = 192 * 64
    want = 1e3 * (state + rows * KV * (2 * DH * 2 + 4)) / 819e9
    assert fam.retention_decode_least_ms(FULL_CONFIG, 192, rows, peak) \
        == pytest.approx(want)
    assert 7.9 < want < 8.2
    # where the products bound it: a tiny state, many tail rows
    tiny = {**FULL_CONFIG, "head_dim": 2}
    ops = 1e3 * (1e9 * H * 4 * 2) / 197e12
    assert fam.retention_decode_least_ms(tiny, 0, 1e9, peak) \
        == pytest.approx(max(ops, 1e3 * 1e9 * KV * (2 * 2 * 2 + 4) / 819e9))


# a traced window of 0..10 s holding 4 engine steps (3 of the loop and one
# that drains): three retention_decode calls of 0.02 s inside it, one after
# it, and another kernel
FORM = {"devices": {"/device:TPU:0": (
    [[f"retention_decode.{i} custom-call f32[48,8,5,128]", 1.0 + i, 0.02,
      True, "custom-call"] for i in range(3)]
    + [["retention_decode.9 custom-call f32[48,8,5,128]", 11.0, 0.02, True,
        "custom-call"],
       ["paged_decode.2 custom-call bf16[64,48,128]", 8.0, 0.5, True,
        "custom-call"]])},
    "async": {}, "host": [[trace.WINDOW_SPAN, 0.0, 10.0]]}
TRACED = {"steps": 3, "steps_with_drain": 4}
READERS = ["kernel_ms.retention_decode", "retention_decode_roofline"]


def _run(form, traced, stats):
    return SimpleNamespace(
        cell=SimpleNamespace(name=CELL, family=fam, config=FULL_CONFIG,
                             traffic=TRAFFIC),
        rehearse=False, device_kind="TPU v5 lite",
        window={"engine_stats": {"steps": 7}},
        traced=traced and {**traced, "engine_stats": stats},
        trace_form=form, trace=trace.reduce(form) if form else None)


def test_the_readers_on_a_hand_made_run():
    kernel = load_module("metrics", READERS[0]).read
    share = load_module("metrics", READERS[1]).read
    stats = {"steps": 100, "retention_state_reads": 100 * 1.5,
             "retention_tail_rows": 100 * 96}
    run = _run(FORM, TRACED, stats)
    took = 1e3 * 3 * 0.02 / 4
    assert kernel(run) == pytest.approx(took)
    least = fam.retention_decode_least_ms(
        FULL_CONFIG, 1.5, 96, {"hbm_bytes_per_s": 819e9,
                               "bf16_flops_per_s": 197e12})
    assert share(run) == pytest.approx(100 * least / took)
    assert 0 < share(run) < 100


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    """No trace, no such kernel in it, an engine without the counters (the
    parent of this PR), or a family without the count: None."""
    read = load_module("metrics", metric).read
    stats = {"steps": 100, "retention_state_reads": 150,
             "retention_tail_rows": 9600}
    assert read(_run(None, None, stats)) is None
    bare = {"devices": {
        "/device:TPU:0": FORM["devices"]["/device:TPU:0"][-1:]},
            "async": {}, "host": FORM["host"]}
    assert read(_run(bare, TRACED, stats)) is None
    if "roofline" in metric:
        assert read(_run(FORM, TRACED, {"steps": 100})) is None
        assert read(_run(FORM, TRACED, {})) is None
        other = _run(FORM, TRACED, stats)
        other.cell.family = SimpleNamespace()
        assert read(other) is None


END_TO_END = ["serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"]
PER_LAYER = [
    "window_compiles", "slot_occupancy", "engine_step_ms",
    "decode_device_ms", "kernel_share.serve", "engine_host_ms",
    "engine_fetch_wait_ms", "first_token_sync_ms",
    "ttft_prefill_wait_share", "serve_mfu", *READERS]


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_cell_reports_what_benchmark_json_says(kind, tmp_path):
    cell = Cell(CELL, root=contracts.checkout(kind, tmp_path))
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_closed_logits"
    assert contracts.subsequence(
        END_TO_END, [m["name"] for m in cell.metrics("end_to_end")])
    reported = [m["name"] for m in cell.metrics("per_layer")]
    assert contracts.subsequence(PER_LAYER, reported)
    assert not {"kernel_ms.paged_decode", "latent_attn_ms",
                "kernel_ms.serve_moe_gmm"} & set(reported)
    for _, limit in cell.driver.CHECKS:
        assert cell.traffic[limit] > 0 and limit in cell.traffic["limits"]
    assert "oracle.logit_gap" in cell.traffic["limits"]
    for control in ("float8", "gate", "degree 1", "wrong token",
                    "bfloat16"):
        assert control in cell.traffic["limits"]["_readings"], control
    # the engine: nothing a slot holds grows with max_len
    assert cell.traffic["engine"] == {
        "num_slots": 48, "max_len": 32768, "prefill_chunk": 512}
    assert cell.traffic["callers"] == 48 \
        and cell.traffic["ramp_steps"] >= 1000
    # several chunks and folds, then decoded positions, in company
    check = cell.traffic["check"]
    assert check["prompt_len"] >= 2100 and check["decode_positions"] >= 32
    assert check["beside"]["before"] >= 20 and check["beside"]["after"] >= 6


def test_the_traffic_is_the_cells():
    from perf.harness.loadgen import request_pool

    pool = request_pool(TRAFFIC)
    prompts, outputs = zip(*pool)
    assert len(pool) == 256
    assert (min(prompts), max(prompts)) == (128, 6144)
    assert (min(outputs), max(outputs)) == (128, 2048)
    assert sorted(prompts)[128] == pytest.approx(768, rel=0.01)
    assert sorted(outputs)[128] == pytest.approx(512, rel=0.01)
    assert (TRAFFIC["prompt_len"]["sigma"], TRAFFIC["output_len"]["sigma"]) \
        == (0.8, 0.6)
    # ~600 decode steps a request in the mean: outputs dominate
    assert sum(outputs) / 256 == pytest.approx(610, rel=0.05)
    assert max(p + o for p, o in pool) <= 8192 < TRAFFIC["engine"]["max_len"]


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_benchmark_json_holds_the_cells_entries_in_order(kind, tmp_path):
    b = contracts.load(contracts.checkout(kind, tmp_path))
    assert contracts.subsequence(
        ["gpt2_medium", "laguna_xs2", CONFIG_NAME],
        [c["name"] for c in b["configs"]])
    entry = {c["name"]: c for c in b["configs"]}[CONFIG_NAME]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == list(
        REDUCED) and entry["file"] == f"perf/configs/{CONFIG_NAME}.json"
    assert contracts.subsequence(
        ["gpt2m.serve_closed", "laguna.serve_mixed_8k", CELL],
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
        for other in ("gpt2m.serve_closed", "laguna.serve_mixed_8k"):
            if other in cells:
                assert contracts.subsequence([other, CELL], cells), name
    per_layer = {m["name"]: m for m in b["per_layer"]}
    for name in READERS:
        m = per_layer[name]
        assert (m["moves"], m["source"], m["layer"]) == (
            "serve_tokens_per_s", "device_trace", "Kernels"), name
        assert (m["unit"], m["better"]) == (
            ("ms", "lower") if name.startswith("kernel_ms.")
            else ("%", "higher")), name
        assert set(m["workloads"]) - {contracts.NEW_CELL} == {CELL}, name
    assert b["run_seconds"] == 20


def test_a_system_without_the_family_fails_the_cell_at_once(tmp_path):
    """The benchmark's files over a checkout whose program has no
    power-retention family (the parent of the PR that brings it): loading
    the cell raises ``CellError``, which ``perf/run.py`` turns into a
    prompt non-zero exit."""
    root = tmp_path / "parent"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    (root / "tpudp" / "models").mkdir(parents=True)
    with pytest.raises(CellError, match="power-retention"):
        Cell(CELL, root=str(root))


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"), "--workload",
         CELL, "--seed", "4100001002", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env, timeout=900)


def test_the_cell_rehearses_on_the_cpu(rehearsal):
    assert rehearsal.returncode == 0, rehearsal.stderr[-2000:]
    line = json.loads(rehearsal.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert {"window_compiles", "slot_occupancy", "engine_step_ms",
            "engine_host_ms", "engine_fetch_wait_ms"} \
        <= set(line["metrics"]) <= set(PER_LAYER)
    assert not any(name.startswith("kernel_") or name.endswith("_roofline")
                   or name == "serve_mfu" for name in line["metrics"])
    assert "compiles in window=0 " in rehearsal.stdout
    for name in END_TO_END:
        assert f'"{name}": ' in rehearsal.stdout, name
    assert set(line["compared"]) == {
        "oracle_logit_gap", "logit_gap", "late_logit_gap",
        "engine_logit_gap", "state_gap", "failed", "window_compiles"}
    for name, held in line["compared"].items():
        assert held["value"] <= held["limit"] < float("inf"), name
    notes = [ln for ln in rehearsal.stdout.splitlines()
             if ln.startswith("[perf] logit check")]
    assert len(notes) == 1 and notes[0].endswith(" ok"), rehearsal.stdout


# --------------------------------------------- controls of the logit check


@pytest.fixture(scope="module")
def driver():
    """The cell's driver after its own ``setup()`` at the rehearsal preset
    (bfloat16, as the cell), and the engine's pass of the check."""
    import jax

    from perf.run import Run

    cell = Cell(CELL, root=ROOT, rehearse=True)
    cell.traffic = {**cell.traffic, "callers": 2, "ramp_steps": 2}
    drv = cell.driver.Driver(cell, Run(cell, 4100001003, jax.devices()[:1],
                                       True, False))
    drv.setup()
    drv.served = drv.engine_pass(drv.engine)
    yield drv
    drv.close()


def _failed(driver, r):
    return {name for name, limit in driver.cell.driver.CHECKS
            if not r[name] <= driver.traffic[limit]}


def _degree_one(x):
    """``phi`` of degree 1 in the degree-2 state's rows: ``x / dh^(1/4)``
    and zeros, so ``phi(q) . phi(k) = q . k / sqrt(dh)``."""
    import jax.numpy as jnp

    dh = x.shape[-1]
    x = x.astype(jnp.float32) * dh ** -0.25
    return jnp.concatenate(
        [x, jnp.zeros((*x.shape[:-1], dh * (dh - 1) // 2), jnp.float32)], -1)


def _library(driver, damage, monkeypatch=None):
    """The library pass on a fresh arena of the engine's slots, damaged as
    named, through the harness's own comparison."""
    import jax
    import jax.numpy as jnp

    from tpudp.models.generate import RetentionState
    from tpudp.ops import retention as ret

    cfg, params, impl = driver.engine.config, driver.engine.params, None
    arena = RetentionState.zeros(cfg, driver.engine.num_slots)
    if damage == "float8":
        params = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(
            a.dtype) if a.ndim >= 2 else a, params)
    elif damage == "gate dropped":
        cfg = dataclasses.replace(cfg, gate_timescale=float("inf"))
    elif damage == "degree 1":
        monkeypatch.setattr(ret, "sym_features", _degree_one)
        monkeypatch.setattr(ret, "_power", lambda s: s)
        impl = "einsum"
    elif damage == "state in bfloat16":
        arena = arena._replace(S=arena.S.astype(jnp.bfloat16),
                               z=arena.z.astype(jnp.bfloat16))
    system, _arena = driver.library_pass(driver.served, cfg, params, arena,
                                         impl)
    return driver.reference_pass(driver.engine.params, system)


def test_the_checks_pass_the_seeded_weights(driver):
    assert driver.correct is True, driver.compared
    r = _library(driver, None)
    assert not _failed(driver, r), r
    assert r["positions"] == 82 and r["engine_tokens"] == 13
    # two decode folds of the check's slot held, every other slot of the
    # other seven live
    assert (r["folds_held"], r["slots"], r["slots_live"]) == (2, 8, 4)


@pytest.mark.parametrize("damage", ["float8", "gate dropped", "degree 1"])
def test_the_logit_check_fails_a_damaged_forward(driver, damage,
                                                 monkeypatch):
    assert "logit_gap" in _failed(driver, _library(driver, damage,
                                                   monkeypatch))


def test_a_state_in_bfloat16_fails_the_state_check(driver):
    """The state the configuration states is float32: a bfloat16 one reads
    inside the logits' limit (PERF.md) and fails on the state."""
    assert _failed(driver, _library(driver, "state in bfloat16")) == {
        "state_gap"}


def test_a_wrong_token_from_the_engine_fails_the_engine_check(driver):
    seen = []

    def hook(slot, tok, request):
        if len(request.prompt) != driver.traffic["check"]["prompt_len"]:
            return tok  # a request beside the check's
        seen.append(tok)
        return (tok + 1) % 256 if len(seen) == 3 else tok

    r = driver.reference_pass(driver.engine.params, driver.system_pass(
        driver.engine.params, driver.engine, token_fault_hook=hook))
    assert _failed(driver, r) == {"engine_logit_gap"}, r


def test_set_up_freezes_the_heap_and_close_thaws_it(driver):
    """The window starts with set-up's heap out of the collector (a full
    collection over it would otherwise fall inside some windows), and the
    driver leaves the process's collector as it found it."""
    import gc

    import jax

    from perf.run import Run

    assert gc.get_freeze_count() > 0
    assert any(n.startswith("heap frozen after set-up") for n in driver.notes)
    cell = Cell(CELL, root=ROOT, rehearse=True)
    drv = cell.driver.Driver(cell, Run(cell, 4100001004, jax.devices()[:1],
                                       True, False))
    drv._freeze_heap()
    drv.close()
    assert gc.get_freeze_count() == 0
    driver._freeze_heap()  # the module's driver as its set-up left it
