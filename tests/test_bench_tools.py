"""The watcher's measurement-granularity resume logic (tools/bench_gaps.py):
error rows don't count as measured, banked history rows do, and a complete
set reports no gaps — the properties the TPU-window accumulation depends on."""

import json
import os

from tools.bench_gaps import (FLASH_TS, MATRIX_CONFIGS, collective_missing,
                              epoch_missing, flash_missing, history_path,
                              matrix_missing, mfu_missing)


def _write(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_matrix_gaps_ignore_errors_and_merge_history(tmp_path):
    d = str(tmp_path)
    assert matrix_missing(d) == list(MATRIX_CONFIGS)  # nothing measured yet
    _write(os.path.join(d, "matrix.history.jsonl"), [
        {"config": "dp_psum", "value": 90000.0, "unit": "images/sec/chip"},
        {"config": "dp_ring", "error": "RuntimeError: device unavailable"},
    ])
    _write(os.path.join(d, "matrix.jsonl"), [
        {"config": "part1_single", "value": 88000.0},
        {"config": "resnet50", "value": 0},  # zero isn't a measurement
    ])
    with open(os.path.join(d, "matrix.jsonl"), "a") as f:
        f.write("{not json at all\n")  # malformed lines must be skipped
    missing = matrix_missing(d)
    assert "dp_psum" not in missing          # banked row counts
    assert "part1_single" not in missing     # current row counts
    assert "dp_ring" in missing              # error row must be retried
    assert "resnet50" in missing             # zero value must be retried
    assert "gpt2_small" in missing


def test_matrix_gap_refuses_unstamped_dp_ring(tmp_path):
    """Round-4 advisor: the 'ring' label flipped bidirectional->uni, so a
    banked dp_ring row with no ring_direction stamp (or the stamp of the
    OTHER direction) measured a different algorithm and must not close
    the rung's gap."""
    d = str(tmp_path)
    _write(os.path.join(d, "matrix.history.jsonl"), [
        {"config": "dp_ring", "value": 90000.0, "sync": "ring"}])
    assert "dp_ring" in matrix_missing(d)
    _write(os.path.join(d, "matrix.jsonl"), [
        {"config": "dp_ring", "value": 90000.0, "sync": "ring",
         "ring_direction": "bidir"}])  # wrong-direction stamp: still owed
    assert "dp_ring" in matrix_missing(d)
    _write(os.path.join(d, "matrix.jsonl"), [
        {"config": "dp_ring", "value": 90000.0, "sync": "ring",
         "ring_direction": "uni"}])
    assert "dp_ring" not in matrix_missing(d)


def test_gap_gate_constants_pin_the_sync_module():
    """bench_gaps must stay stdlib-only (the watcher polls it cheaply),
    so its 'uni' literal and the attribution variant list are duplicated
    from / consumed by jax-importing modules — pin them together."""
    from tools.bench_gaps import MFU_VARIANTS

    from tpudp.parallel.sync import RING_DIRECTION

    assert RING_DIRECTION["ring"] == "uni"  # matrix_missing's literal
    # every variant the gap gate can report must be one the attribution
    # bench accepts (it validates MFU_VARIANTS strictly and single-sources
    # this tuple, so equality here means the watcher pipe can't stall)
    assert MFU_VARIANTS == ("full", "fwd_bwd", "fwd_only", "no_bn",
                            "bf16_params")


def test_flash_gaps(tmp_path):
    d = str(tmp_path)
    assert flash_missing(d) == list(FLASH_TS)
    _write(os.path.join(d, "flash.jsonl"), [
        {"t": 4096, "flash_ms": 11.2, "dense_ms": 15.0},
        {"t": 8192, "error": "XlaRuntimeError: UNAVAILABLE"},
        {"flash_done": [4096, 8192, 16384]},
    ])
    assert flash_missing(d) == [8192, 16384]


def test_history_path_maps_json_too():
    """bench.json is banked by bench.py itself (round-2 advisor finding:
    the watcher's > redirect truncates before the process starts)."""
    assert history_path("x/bench.json") == "x/bench.history.jsonl"
    assert history_path("x/matrix.jsonl") == "x/matrix.history.jsonl"
    assert history_path("x/other.txt") == "x/other.txt"


def test_epoch_gap(tmp_path):
    d = str(tmp_path)
    assert epoch_missing(d)
    _write(os.path.join(d, "epoch.json"), [
        {"metric": "vgg11_epoch_images_per_sec", "value": 0.0,
         "error": "trainer hung"}])
    assert epoch_missing(d)  # error row must be retried
    _write(os.path.join(d, "epoch.history.jsonl"), [
        {"metric": "vgg11_epoch_images_per_sec", "value": 88000.0}])
    assert not epoch_missing(d)  # banked history row counts


def test_record_bench_renders_freshest_rows(tmp_path):
    """tools/record_bench.py: the newest measured headline wins over file
    order; banked re-emissions are annotated; epoch and MFU rows render;
    a missing resident-batch number never prints a literal 'None%'."""
    import subprocess
    import sys

    d = str(tmp_path)
    _write(os.path.join(d, "bench.history.jsonl"), [
        {"metric": "vgg11_cifar10_images_per_sec_per_chip", "value": 90000.0,
         "unit": "images/sec/chip", "vs_baseline": 340.0, "mfu": 0.41,
         "sec_per_step": 0.00285, "device_kind": "TPU v5 lite",
         "dtype": "bfloat16", "global_batch": 256,
         "measured_at_utc": "2026-07-30T04:00:00Z"},
        {"metric": "vgg11_cifar10_images_per_sec_per_chip", "value": 92469.2,
         "unit": "images/sec/chip", "vs_baseline": 349.4, "mfu": 0.43,
         "sec_per_step": 0.00277, "device_kind": "TPU v5 lite",
         "dtype": "bfloat16", "global_batch": 256,
         "measured_at_utc": "2026-07-30T04:36:00Z"},
    ])
    _write(os.path.join(d, "bench.json"), [
        {"metric": "vgg11_cifar10_images_per_sec_per_chip", "value": 92469.2,
         "unit": "images/sec/chip", "vs_baseline": 349.4, "mfu": 0.43,
         "sec_per_step": 0.00277, "device_kind": "TPU v5 lite",
         "dtype": "bfloat16", "global_batch": 256,
         "measured_at_utc": "2026-07-30T04:36:00Z",
         "source": "last_known_good", "stale_reason": "device unavailable"},
    ])
    _write(os.path.join(d, "epoch.json"), [
        {"metric": "vgg11_epoch_images_per_sec", "value": 88000.0,
         "epoch_seconds": 0.29, "input_pipeline_gap_pct": None},
    ])
    _write(os.path.join(d, "mfu.jsonl"), [
        {"variant": "full", "sec_per_step": 0.00277, "mfu": 0.43,
         "device_kind": "TPU v5 lite"},
        {"variant": "no_bn", "sec_per_step": 0.0023,
         "bn_share_of_full": 0.17, "device_kind": "TPU v5 lite"},
    ])
    _write(os.path.join(d, "serve.jsonl"), [
        {"metric": "serve_tokens_per_sec", "concurrency": 8,
         "value": 5120.5, "unit": "tokens/sec",
         "speedup_vs_sequential": 3.8, "p50_token_latency_ms": 4.2,
         "p99_token_latency_ms": 11.0, "mean_slot_occupancy": 0.93,
         "device_kind": "TPU v5 lite"},
        {"metric": "serve_tokens_per_sec", "concurrency": 4,
         "error": "device unavailable"},
    ])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "record_bench.py"),
         "--dir", d], capture_output=True, text=True, cwd=repo).stdout
    assert "92,469.2" in out          # newest measured row wins
    assert "last-known-good" in out   # re-emission annotated
    assert "88,000.0" in out          # epoch row renders
    assert "BatchNorm 17.0%" in out   # MFU attribution row renders
    assert "5,120.5 tokens/sec" in out  # serving row renders
    assert "serve c=4 | ERROR" in out   # serving error row surfaces
    assert "None%" not in out         # missing gap never prints literally


def test_mfu_gap_requires_all_variants_on_tpu(tmp_path):
    """A window dying after the FIRST row must not mark the sweep done;
    CPU-smoke rows never satisfy the gate; bf16_params counts attempted
    even as an error row (the bench tolerates its failure)."""
    d = str(tmp_path)
    assert mfu_missing(d)
    rows = [{"variant": v, "sec_per_step": 0.003,
             "device_kind": "TPU v5 lite"}
            for v in ("full", "fwd_bwd", "fwd_only")]
    _write(os.path.join(d, "mfu.jsonl"), rows)
    assert mfu_missing(d)  # no_bn + bf16_params still missing
    rows.append({"variant": "no_bn", "sec_per_step": 0.003,
                 "device_kind": "cpu"})  # smoke row: must not count
    _write(os.path.join(d, "mfu.jsonl"), rows)
    assert mfu_missing(d)
    rows[-1]["device_kind"] = "TPU v5 lite"
    # a CPU-smoke bf16_params row must not count as the attempt either
    rows.append({"variant": "bf16_params", "sec_per_step": 0.1,
                 "device_kind": "cpu"})
    _write(os.path.join(d, "mfu.jsonl"), rows)
    assert mfu_missing(d)
    rows.append({"variant": "bf16_params", "error": "donation clash"})
    _write(os.path.join(d, "mfu.jsonl"), rows)
    assert not mfu_missing(d)  # all measured + bf16 attempted (error row)


def test_mfu_gap_reports_missing_variants_for_resume(tmp_path):
    """Round-5 micro battery: the first window measures only
    full+bf16_params; the gap list is what the full stage passes to
    MFU_VARIANTS, so it must name exactly the remaining ablations."""
    d = str(tmp_path)
    assert mfu_missing(d) == ["full", "fwd_bwd", "fwd_only", "no_bn",
                              "bf16_params"]
    _write(os.path.join(d, "mfu.history.jsonl"), [
        {"variant": "full", "sec_per_step": 0.003,
         "device_kind": "TPU v5 lite"},
        {"variant": "bf16_params", "sec_per_step": 0.002,
         "device_kind": "TPU v5 lite"},
    ])
    assert mfu_missing(d) == ["fwd_bwd", "fwd_only", "no_bn"]


def test_lever_gap_gate(tmp_path):
    """VERDICT r4 #2 automation: the bf16-params headline capture is owed
    exactly when a measured TPU attribution row proves the lever wins
    (speedup >= 1.03); a below-threshold measurement closes the stage
    (the ablation row documents why the headline stays fp32), and a
    fresh bf16-params headline row — in the lever file or banked in the
    shared headline history — satisfies it."""
    from tools.bench_gaps import lever_missing

    d = str(tmp_path)
    assert not lever_missing(d)  # no attribution evidence yet -> nothing owed
    _write(os.path.join(d, "mfu.jsonl"), [
        {"variant": "bf16_params", "sec_per_step": 0.002,
         "device_kind": "TPU v5 lite", "speedup_vs_full": 1.01}])
    assert not lever_missing(d)  # measured, but below threshold: closed
    _write(os.path.join(d, "mfu.jsonl"), [
        {"variant": "bf16_params", "sec_per_step": 0.002,
         "device_kind": "cpu", "speedup_vs_full": 1.4}])
    assert not lever_missing(d)  # smoke row never owes a TPU capture
    _write(os.path.join(d, "mfu.jsonl"), [
        {"variant": "bf16_params", "sec_per_step": 0.002,
         "device_kind": "TPU v5 lite", "speedup_vs_full": 1.12}])
    assert lever_missing(d)  # proven on-chip win, no capture yet
    _write(os.path.join(d, "bench.history.jsonl"), [
        {"metric": "vgg11_cifar10_images_per_sec_per_chip", "value": 99000.0,
         "device_kind": "TPU v5 lite", "param_dtype": "bfloat16"}])
    assert not lever_missing(d)  # banked bf16 headline row satisfies it


def test_collective_gap_gate(tmp_path):
    """The ring-default evidence stage (VERDICT r3 #5): complete on real
    multi-device TPU rows for all three key schedules, or on a labeled
    1-device skip row — but a probe that sees a multi-chip slice re-opens
    the stage, and simulated CPU-mesh rows never satisfy it."""
    d = str(tmp_path)
    assert collective_missing(d)  # nothing measured yet

    # simulated CPU-mesh sweep rows must NOT satisfy the gate
    _write(os.path.join(d, "collective.jsonl"), [
        {"strategy": s, "wall_time_s": 0.1, "devices": 8,
         "device_kind": "cpu"}
        for s in ("allreduce", "ring", "ring_bidir")])
    assert collective_missing(d)

    # the labeled 1-device skip row completes the stage on a 1-chip host
    _write(os.path.join(d, "collective.jsonl"), [
        {"skipped": "1 device", "devices": 1, "device_kind": "TPU v5 lite"}])
    assert not collective_missing(d)

    # ... until a probe records a multi-chip slice: the head-to-head is
    # owed again and the skip row must not mask it
    with open(os.path.join(d, "probe.json"), "w") as f:
        json.dump({"devices": 8, "device_kind": "TPU v4"}, f)
    assert collective_missing(d)

    # real multi-device TPU rows do NOT close it while the 'ring' row is
    # unstamped: a pre-flip capture measured the bidirectional schedule
    # (round-4 advisor), so the renamed rung is still owed its number
    _write(os.path.join(d, "collective.history.jsonl"), [
        {"strategy": s, "wall_time_s": 0.01, "devices": 8,
         "device_kind": "TPU v4"}
        for s in ("allreduce", "ring", "ring_bidir")])
    assert collective_missing(d)

    # with the post-flip stamp on 'ring', the stage closes for good
    _write(os.path.join(d, "collective.history.jsonl"), [
        {"strategy": "allreduce", "wall_time_s": 0.01, "devices": 8,
         "device_kind": "TPU v4"},
        {"strategy": "ring", "wall_time_s": 0.01, "devices": 8,
         "device_kind": "TPU v4", "ring_direction": "uni"},
        {"strategy": "ring_bidir", "wall_time_s": 0.01, "devices": 8,
         "device_kind": "TPU v4"}])
    assert not collective_missing(d)

    # incomplete schedule coverage keeps the gap open
    _write(os.path.join(d, "collective.history.jsonl"), [
        {"strategy": "allreduce", "wall_time_s": 0.01, "devices": 8,
         "device_kind": "TPU v4"}])
    assert collective_missing(d)


def test_analysis_gap_stage(tmp_path):
    """The correctness-gate stage: a clean tree reports no gaps; a tree
    with an unsuppressed finding owes `lint`, a missing/stale trace
    lock owes `audit` (and, ledger-less, `budget`), and a protocol
    divergence in a multihost module owes `protocol` — all without
    importing jax (the poll-path contract; tests/test_analysis.py
    proves the jax-free load)."""
    from tools.bench_gaps import analysis_missing

    # the real tree is the clean case — tier-1 pins it clean, so the
    # stage must agree
    assert analysis_missing() == []

    # seeded tree: one traced-branch violation + no lockfile at all
    # (which owes both the audit staleness AND the budget ledgers)
    pkg = tmp_path / "tpudp"
    pkg.mkdir()
    (tmp_path / "tools").mkdir()       # configured lint paths must
    (tmp_path / "benchmarks").mkdir()  # exist, or that alone is a gap
    (pkg / "bad.py").write_text(
        "import jax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")
    assert analysis_missing(str(tmp_path)) == ["lint", "audit", "budget"]

    # fixing the violation (suppression counts: it is explicit in the
    # diff) leaves only the missing lock owed
    (pkg / "bad.py").write_text(
        "import jax\n"
        "from jax import lax\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return jax.numpy.where(x > 0, x, -x)\n")
    assert analysis_missing(str(tmp_path)) == ["audit", "budget"]

    # a protocol divergence in a module the verifier scopes (the PR 7
    # entry-probe shape, in a file named like a multihost module) adds
    # the protocol gap — INTERPROCEDURAL on purpose, so the lexical
    # lint rule stays silent and the gap is the verifier's alone
    (pkg / "resilience.py").write_text(
        "import os\n\n\n"
        "def probe(root):\n"
        "    dirs = sorted(os.listdir(root))\n"
        "    return dirs[0] if dirs else None\n\n\n"
        "def resume(root):\n"
        "    if probe(root) is not None:\n"
        "        gather_host_values(1)  # noqa: F821\n")
    assert analysis_missing(str(tmp_path)) == ["audit", "protocol",
                                               "budget"]
    (pkg / "resilience.py").unlink()

    # a configured lint path vanishing must read as a lint gap, not as
    # "clean" — the CLI exits 2 on the same condition and the two gates
    # must agree
    (tmp_path / "benchmarks").rmdir()
    assert analysis_missing(str(tmp_path)) == ["lint", "audit", "budget"]


def test_sdc_soak_gap_gate(tmp_path):
    """A seed closes only on a TPU row where every verdict column holds:
    clean fit raised nothing, the one-shot flip was detected/localized/
    graded with the persistent flip quarantined, and the repaired params
    matched the clean run bit-exactly.  Any single False keeps the seed
    open — a soak that proved less than the full story must be rerun."""
    from tools.bench_gaps import SDC_SOAK_SEEDS, sdc_soak_missing

    d = str(tmp_path)
    assert sdc_soak_missing(d) == list(SDC_SOAK_SEEDS)
    ok = {"metric": "sdc_soak", "value": 2, "clean_ok": True,
          "parity_ok": True, "accounted": True, "quarantine_ok": True,
          "device_kind": "TPU v4"}
    _write(os.path.join(d, "sdc_soak.jsonl"), [
        dict(ok, seed=0),
        dict(ok, seed=1, device_kind="cpu"),        # CPU smoke: open
        dict(ok, seed=2, parity_ok=False),          # repair not bit-exact
    ])
    assert sdc_soak_missing(d) == [1, 2]
    # banked history closes seeds the current file lacks
    _write(os.path.join(d, "sdc_soak.history.jsonl"), [dict(ok, seed=1)])
    assert sdc_soak_missing(d) == [2]
    # every other verdict column gates too
    for bad in ({"clean_ok": False}, {"accounted": False},
                {"quarantine_ok": False}, {"value": 0},
                {"error": "wedged", "value": None}):
        _write(os.path.join(d, "sdc_soak.jsonl"), [dict(ok, seed=2, **bad)])
        assert 2 in sdc_soak_missing(d), bad
    _write(os.path.join(d, "sdc_soak.jsonl"),
           [dict(ok, seed=0), dict(ok, seed=2)])
    assert sdc_soak_missing(d) == []


def test_tier1_headroom_gap(tmp_path):
    """tier1-headroom fires only when the LAST summary in tier1.log
    burned past TIER1_WARN_S; earlier (slower) runs in the same log are
    history, and a missing log or summary is advisory — not a gap."""
    from tools.bench_gaps import (TIER1_BUDGET_S, TIER1_WARN_S,
                                  tier1_headroom_missing)

    d = str(tmp_path)
    assert TIER1_WARN_S < TIER1_BUDGET_S
    assert tier1_headroom_missing(d) == []          # no log: advisory
    log = os.path.join(d, "tier1.log")
    with open(log, "w") as f:
        f.write("collected 560 items\nnothing like a summary here\n")
    assert tier1_headroom_missing(d) == []          # no summary line
    with open(log, "a") as f:
        f.write("558 passed, 2 skipped in 830.12s\n")
    assert tier1_headroom_missing(d) == ["tier1-headroom"]
    with open(log, "a") as f:                       # later, faster rerun
        f.write("== 560 passed in 641.07s ==\n")
    assert tier1_headroom_missing(d) == []
