"""The benchmark's own tests (fast, CPU): the harness is driven by data,
its output keeps the contract's shape, it refuses to run without a chip,
its trace arithmetic is right on a recorded trace, and its load is
reproducible and never exceeds the model's positions."""

import json
import os
import re
import subprocess
import sys
from itertools import count
from statistics import median, quantiles

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]

import benchmark_contracts as contracts  # noqa: E402
from perf.harness import loadgen, stats, trace  # noqa: E402
from perf.harness.cells import Cell, load_json  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _run(root, *args, env=None):
    e = dict(os.environ, **(env or {}))
    e.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, os.path.join(root, "perf", "run.py"),
                           *args], capture_output=True, text=True, env=e,
                          timeout=300)


@pytest.fixture()
def copy(tmp_path):
    return contracts.copy_checkout(tmp_path)


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_benchmark_json_keeps_the_contract(kind, tmp_path):
    root = contracts.checkout(kind, tmp_path)
    b = contracts.load(root)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    assert len(cells) == len(b["workloads"]) <= 24
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(len(cells) // 4, 1)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(root, "perf", "traffic",
                                           w["traffic"] + ".json"))
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert load_json(os.path.join(root, c["file"]))["reduced"] == c["reduced"]
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(root, "perf", "metrics",
                                           m["name"] + ".py"))
        # the metric it moves is reported in every cell where it is
        mine = set(m.get("workloads", cells))
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert mine <= moved, m["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for name in cells:  # every cell: setup_s, another end-to-end, a layer
        got = [m["name"] for m in b["end_to_end"]
               if name in m.get("workloads", cells)]
        assert "setup_s" in got and len(got) >= 2
        assert any(name in m.get("workloads", cells) for m in b["per_layer"])


def test_files_added_to_a_copy_are_found_with_no_edit(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric come
    in as new files and new entries; no file that was there changes.  The
    new cell is on every metric's ``workloads`` (benchmark_contracts.py):
    a reader with nothing to read in it returns nothing."""
    copy = contracts.grown(tmp_path)

    cell = Cell("other.short", root=str(copy), rehearse=True)
    assert cell.config["n_layer"] == 1 and cell.traffic["per_chip_batch"] == 1
    mine = [m["name"] for m in cell.metrics("per_layer")]
    assert contracts.subsequence(["window_compiles", "steps_total"], mine)
    assert set(mine) == {m["name"] for m in contracts.load(copy)["per_layer"]}

    # ... and the rehearsal runs the new cell; its last line has exactly
    # the contract's keys and says platform=cpu
    p = _run(str(copy), "--workload", "other.short", "--seed", "3000000019",
             "--seconds", "2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "compared"}
    # every number ``correct`` compared, beside its limit: the line's last
    # key and the last lines of standard error
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"token_loss_gap", "first_step_loss_gap",
                                     "failed", "window_compiles"}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert [ln.split()[2].rstrip(":") for ln in p.stderr.strip().splitlines()[
        -len(line["compared"]):]] == list(line["compared"])
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["steps_total"] == {
        "value": float(line["attempted"]), "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    # a device metric has no reading on the CPU, a serving metric none in a
    # training run: their readers returned nothing
    assert {"window_compiles", "steps_total", "data_wait_share",
            "reading_rate_median"} <= set(line["metrics"])
    assert not {"step_device_ms", "mfu", "kernel_ms.flash_fwd", "serve_mfu",
                "engine_host_ms", "slot_occupancy"} & set(line["metrics"])


def test_a_traced_run_has_a_trace_directory_of_its_own():
    """Two ``--trace 1`` runs of one checkout at once (the tests' workers)
    each write and remove a directory named by their process."""
    from perf import run

    assert os.path.split(run.TRACE_DIR) == (
        os.path.join(ROOT, "bench_results", "perf_trace"), str(os.getpid()))
    p = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, sys.argv[1]); "
         "from perf import run; print(run.TRACE_DIR)", ROOT],
        capture_output=True, text=True, timeout=60)
    assert p.stdout.strip() != run.TRACE_DIR
    assert os.path.dirname(p.stdout.strip()) == os.path.dirname(run.TRACE_DIR)


def test_no_chip_means_a_nonzero_exit_and_no_metric():
    p = _run(ROOT, "--workload", "gpt2m.train", "--seed", "1", "--seconds",
             "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout


def test_without_the_program_it_fails_and_prints_no_result(copy):
    os.unlink(copy / "tpudp")  # only BENCHMARK.json and the paths remain
    p = _run(str(copy), "--workload", "gpt2m.train", "--seed", "1",
             "--seconds", "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and "{" not in p.stdout


def test_unknown_device_kind_is_an_error():
    from perf.harness.peaks import peaks

    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("_source")


def test_trace_reduction_on_a_hand_made_trace():
    """Busy/idle, nesting, collectives and gap attribution with answers
    that can be checked by eye (seconds)."""
    form = {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 1.0, 0.5, False, "fusion"],
            ["while.2", 2.0, 1.0, False, "while"],    # holds the next two
            ["flash_fwd", 2.0, 0.4, True, "custom-call"],
            ["fusion.3", 2.5, 0.5, False, "fusion"],
            ["all-reduce-done.4", 3.0, 0.5, False, "all-reduce-done"],
            ["fusion.9", 9.0, 1.0, False, "fusion"],  # outside the window
        ]},
        # the all-reduce ran from 2.6: 0.4 s of it hid behind fusion.3
        "async": {"/device:TPU:0": [
            ["all-reduce-start.4", 2.6, 0.9, False, "all-reduce-start"]]},
        "host": [
            ["perf.traced_window", 0.0, 5.0],
            ["perf.next_batch", 0.0, 1.0],
            ["perf.train_epoch", 1.5, 3.5],
            ["perf.epoch_turnover", 1.5, 0.5],    # nested: innermost wins
        ],
    }
    r = trace.reduce(form)
    assert r["window_s"] == 5.0 and r["devices"] == 1
    assert r["busy_s"] == pytest.approx(2.0)      # 0.5 + 1.0 + 0.5
    assert r["collective_s"] == pytest.approx(0.9)
    assert r["collective_exposed_s"] == pytest.approx(0.5)
    assert r["kernel_s"] == pytest.approx(0.4)
    ops = dict(r["device_ops"])
    assert ops["while.2"] == pytest.approx(0.1)   # self time: 1.0 - 0.4 - 0.5
    assert "fusion.9" not in ops
    gaps = dict(r["idle_gaps"])
    assert gaps["perf.next_batch"] == pytest.approx(1.0)
    assert gaps["perf.epoch_turnover"] == pytest.approx(0.5)
    assert gaps["perf.train_epoch"] == pytest.approx(1.5)   # 3.5 .. 5.0
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert trace.reduce({"devices": {}, "host": form["host"]}) is None


def test_trace_reduction_on_a_recorded_chip_trace():
    """Two iterations of gpt2m.train's loop, cut from a TPU v5e trace
    (the file says how it was trimmed)."""
    form = load_json(os.path.join(DATA, "gpt2m_train_two_steps.trace.json"))
    r = trace.reduce(form)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.6923046)
    assert r["busy_s"] == pytest.approx(0.5943617, rel=1e-6)
    assert r["kernel_s"] == pytest.approx(0.4232994, rel=1e-6)  # 72 flash calls
    assert r["collective_s"] == 0.0                             # one chip
    gaps = dict(r["idle_gaps"])
    # the device is idle where the host waits at the barrier (the small
    # operations trimmed away) and hardly at all while it draws a batch
    assert max(gaps, key=gaps.get) == "perf.barrier"
    assert gaps["perf.next_batch"] == pytest.approx(0.002355, rel=1e-3)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["device_ops"][0][0].startswith("fusion.13 fusion:kOutput")
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10


def test_an_hlo_instruction_becomes_a_short_label():
    text = ('%attn.117 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[128,1024,'
            '64]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[128,1024,64]{2,1,0:T(8,'
            '128)(2,1)} %bitcast.2498), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={bf16[128,1024,64]{2,1,0}}')
    assert trace.parse_op(text) == (
        "attn.117 custom-call (bf16[128,1024,64], bf16[128,1024,64])",
        "custom-call", True)
    label, op, kernel = trace.parse_op(
        "%fusion.13 = f32[8,16]{1,0:T(8,128)} fusion(f32[8,16]{1,0} "
        "%all-reduce-done.3), kind=kLoop, calls=%fused_computation.15")
    assert (label, op, kernel) == ("fusion.13 fusion:kLoop f32[8,16]",
                                   "fusion", False)
    assert not trace.COLLECTIVE.match(op)  # an operand's name is no opcode
    assert trace.COLLECTIVE.match("all-reduce-start")


def test_train_rate_is_all_the_samples_over_all_the_time(copy):
    """One stalled reading lowers the end-to-end rate and leaves the
    per-layer median where it was; too few readings fail nothing."""
    from types import SimpleNamespace

    cell = Cell("gpt2m.train", root=str(copy), rehearse=True)
    run = SimpleNamespace(devices=[0], spans=None, seed=0)
    driver = cell.driver.Driver(cell, run)
    readings = [(1.0, 1000)] * 4 + [(6.0, 1000)]     # a 5 s stall
    seg = {"t0": 50.0, "t1": 60.0, "steps": 50, "failed": 0, "samples": 5000,
           "readings": readings, "last_loss": 5.0}
    out = driver.report(seg)
    assert out["end_to_end"] == {"train_throughput_per_chip": 500.0}
    assert out["correct"] is True and out["attempted"] == 50
    run.window = seg
    assert cell.reader("reading_rate_median")(run) == 1000.0
    run.window = {}
    assert cell.reader("reading_rate_median")(run) is None
    assert driver.report(dict(seg, failed=10))["correct"] is False


def test_interval_arithmetic():
    u = stats.union([(3, 4), (0, 1), (0.5, 2), (5, 5)])
    assert u == [(0, 2), (3, 4)] and stats.total(u) == 3
    assert stats.subtract([(0, 10)], u) == [(2, 3), (4, 10)]
    assert stats.clip(u, 1, 3.5) == [(1, 2), (3, 3.5)]
    assert stats.percentile(range(1, 101), 95) == 95


def test_closed_loop_load_is_reproducible_and_fits_the_positions():
    tr = load_json(os.path.join(ROOT, "perf", "traffic", "chat_closed.json"))
    cfg = load_json(os.path.join(ROOT, "perf", "configs", "gpt2_medium.json"))
    pool = loadgen.request_pool(tr)
    assert len(pool) == tr["pool_requests"]
    assert max(p + o for p, o in pool) <= cfg["n_positions"]
    assert max(p + o for p, o in pool) <= tr["engine"]["max_len"]
    p_len = sorted(p for p, _ in pool)
    assert tr["prompt_len"]["min"] <= p_len[0] and p_len[-1] <= tr["prompt_len"]["max"]
    assert abs(p_len[len(p_len) // 2] - tr["prompt_len"]["median"]) <= 4
    a = loadgen.RequestStream(tr, 3000000019, cfg["vocab_size"])
    b = loadgen.RequestStream(tr, 3000000019, cfg["vocab_size"])
    c = loadgen.RequestStream(tr, 7, cfg["vocab_size"])
    seq_a = [a.next() for _ in range(300)]
    seq_b = [b.next() for _ in range(300)]
    seq_c = [c.next() for _ in range(300)]
    assert all((x[0] == y[0]).all() and x[1] == y[1]
               for x, y in zip(seq_a, seq_b))
    # another seed: the same sizes in the same order (the file's), other
    # token ids; a seed that changed the sizes would change the work
    sizes = lambda seq: [(len(p), o) for p, o in seq]  # noqa: E731
    assert sizes(seq_a) == sizes(seq_c)
    assert sorted(sizes(seq_a)[:len(pool)]) == sorted(pool)
    assert any((x[0] != y[0]).any() for x, y in zip(seq_a, seq_c))
    assert all(0 <= p.min() and p.max() < cfg["vocab_size"] for p, _ in seq_a)


def _replay(pool, order, ramp, window, slots=64, chunk=128):
    """The engine's scheduler in steps, as ``Engine.step`` has it: one
    prefill chunk a step for the oldest admitted request still
    prefilling, then a decode step for every slot past its prompt; a
    caller submits again as the step that finished its request returns.
    Returns the TTFTs (in steps) of the requests submitted in the window
    and the share of slots that decoded."""
    feed = count()

    def submit(now):
        p, o = pool[order[next(feed) % len(order)]]
        return [-(-p // chunk), o, now]      # chunks left, tokens left, when

    live = [submit(0) for _ in range(slots)]
    age = list(range(slots))                 # admission order
    admitted = slots
    ttft, decoding, step = [], 0, 0
    while True:
        step += 1
        waiting = [(age[s], s) for s, r in enumerate(live) if r[0] > 0]
        if waiting:
            r = live[min(waiting)[1]]
            r[0] -= 1
            if r[0] == 0:                    # the chunk that ends the prompt
                r[1] -= 1                    # yields the first token
                if ramp < r[2] <= ramp + window:
                    ttft.append(step - r[2])
        for r in live:
            if r[0] == 0 and r[1] > 0:
                r[1] -= 1
                decoding += ramp < step <= ramp + window
        for s, r in enumerate(live):
            if r[0] == 0 and r[1] == 0:
                live[s], age[s], admitted = submit(step), admitted, admitted + 1
        if step >= ramp + window and not any(
                r[0] > 0 and ramp < r[2] <= ramp + window for r in live):
            return ttft, decoding / window / slots


def test_the_files_order_gives_a_steady_tail_and_a_seeds_order_does_not():
    """Why the order of request sizes is the traffic file's and not the
    seed's (perf/harness/loadgen.py): replayed in steps, the file's order
    keeps its TTFT tail whatever the window and the ramp, and orders
    drawn from seeds differ by a fifth however long the window is."""
    import numpy as np

    tr = load_json(os.path.join(ROOT, "perf", "traffic", "chat_closed.json"))
    pool = loadgen.request_pool(tr)
    order = np.random.default_rng(tr["order_seed"]).permutation(len(pool))
    ttft, occupancy = _replay(pool, order, tr["ramp_steps"], 230)
    # the chip read slot_occupancy 77.2% and a tail of 5,732 ms at 87.6 ms
    # a step (PERF.md section 6, PR 24): the replay is the cell's loop
    assert 0.770 < occupancy < 0.775 and 75 <= len(ttft) <= 90
    assert stats.percentile(ttft, 95) == 66
    for window, ramp in ((200, 300), (280, 300), (230, 260), (230, 340)):
        assert stats.percentile(_replay(pool, order, ramp, window)[0], 95) == 66
    assert 61 <= stats.percentile(_replay(pool, order, 300, 575)[0], 95) <= 63
    for window in (230, 575):                # 20 s and 50 s of 87 ms steps
        tails = [stats.percentile(_replay(
            pool, np.random.default_rng(seed).permutation(len(pool)),
            300, window)[0], 95) for seed in range(5, 11)]
        q = quantiles(tails, n=4)
        assert (q[2] - q[0]) / median(tails) > 0.10, tails
