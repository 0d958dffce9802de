"""Smoke tests for the benchmark harnesses: their plumbing is CI-guarded on
the simulated CPU mesh (tiny steps; real numbers come from chip runs), and
bench.py — which measures an accelerator or nothing — must refuse the CPU.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, env_extra, timeout=900, args=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS",)}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_bench_without_a_chip_exits_nonzero_and_prints_no_value():
    """bench.py measures an accelerator or nothing: on a machine where JAX
    finds only the CPU it must exit non-zero and print NO row — no CPU
    number under a device metric's name, no stored number re-emitted."""
    proc = _run("bench.py", {"JAX_PLATFORMS": "cpu"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no value printed" in proc.stderr


def test_bench_rejects_bad_rung_before_touching_a_device():
    """A typo'd BENCH_SYNC / BENCH_PARAM_DTYPE fails fast (non-zero, no
    row) instead of measuring something other than what was asked."""
    for env in ({"BENCH_SYNC": "rnig"}, {"BENCH_PARAM_DTYPE": "bf16"}):
        proc = _run("bench.py", {"JAX_PLATFORMS": "cpu", **env}, timeout=300)
        assert proc.returncode != 0
        assert "{" not in proc.stdout
        assert next(iter(env)) in proc.stderr


def test_registry_configs_all_gated():
    """Tier-1 guard on the committed smoke-geometry registry
    (tools/bench_gaps.py): every UPPERCASE tuple registry must be
    consumed by a gate function, and every gate must be reachable from
    the CLI the watcher drives.  A registry that grows a config no gate
    reads — or a gate no stage can invoke — burns TPU-window time
    measuring rows nothing ever closes on, silently."""
    import ast
    import inspect

    import tools.bench_gaps as bg

    tree = ast.parse(inspect.getsource(bg))
    registries, gates, main_src = {}, {}, ""
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id.isupper()
                and isinstance(node.value, ast.Tuple)):
            registries[node.targets[0].id] = node
        if isinstance(node, ast.FunctionDef):
            if node.name.endswith("_missing") or node.name.endswith("_rows"):
                gates[node.name] = ast.unparse(node)
            if node.name == "main":
                main_src = ast.unparse(node)
    assert registries and gates and main_src
    gate_blob = "\n".join(gates.values())
    ungated = [n for n in registries if n not in gate_blob]
    assert not ungated, (
        f"smoke-geometry registries with no gate reading them: {ungated}")
    # every gate is dispatchable from the CLI (main() must name it) —
    # the watcher resumes sweeps through `python tools/bench_gaps.py
    # <stage>`, so an undispatchable gate is dead coverage
    undispatched = [g for g in gates if g not in main_src]
    assert not undispatched, (
        f"gates unreachable from bench_gaps main(): {undispatched}")
    # spec-fused configs must parse as k{K}n{N} — serve_bench's strict
    # name validation would reject anything else and wedge the watcher
    import re as _re
    for c in bg.SERVE_SPEC_FUSED_CONFIGS:
        assert _re.fullmatch(r"k\d+n\d+", c), c


def test_train_pipeline_gap_gate(tmp_path):
    """tools/bench_gaps `train_pipeline` stage: a geometry closes only
    on a measured TPU row with parity AND fault accounting intact — a
    fast-but-diverged row, an unaccounted recovery, or a CPU smoke row
    all leave the config in the gap list (same philosophy as the
    train_soak gate)."""
    from tools.bench_gaps import PIPELINE_CONFIGS, train_pipeline_missing

    d = str(tmp_path)
    assert train_pipeline_missing(d) == list(PIPELINE_CONFIGS)
    good = {"metric": "train_pipeline", "config": "pp2dp4",
            "value": 1.0e5, "parity_ok": True, "accounted": True,
            "device_kind": "TPU v5 lite"}
    rows = [good,
            {**good, "config": "pp4dp2", "parity_ok": False},
            {**good, "config": "pp2dp4v2", "device_kind": "cpu"},
            {**good, "config": "unregistered"},
            {**good, "config": "pp4dp2", "accounted": False}]
    with open(os.path.join(d, "train_pipeline.jsonl"), "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    assert train_pipeline_missing(d) == ["pp4dp2", "pp2dp4v2"]
    # the bench's config-name parser agrees with the registry format
    from benchmarks.pipeline_bench import parse_config

    assert [parse_config(c) for c in PIPELINE_CONFIGS] == [
        (2, 4, 1), (4, 2, 1), (2, 4, 2)]
    with pytest.raises(ValueError, match="bad pipeline config"):
        parse_config("pp2xdp4")


def test_stale_tpu_row_gap(tmp_path):
    """tools/bench_gaps `stale` stage: a result file whose current
    artifact is a last-known-good re-emission reports a NAMED
    stale-tpu-row gap — honest staleness instead of a silently re-dated
    number — while fresh rows and absent files report nothing."""
    from tools.bench_gaps import stale_tpu_rows

    d = str(tmp_path)
    assert stale_tpu_rows(d) == []  # no files, no gap
    fresh = {"metric": "vgg11_cifar10_images_per_sec_per_chip",
             "value": 92469.2, "device_kind": "TPU v5 lite",
             "measured_at_utc": "2026-08-01T00:00:00Z"}
    with open(os.path.join(d, "bench.json"), "w") as f:
        f.write(json.dumps(fresh) + "\n")
    assert stale_tpu_rows(d) == []  # fresh measurement, no gap
    stale = {**fresh, "source": "last_known_good", "fresh": False,
             "stale_since": "2026-07-30T04:36:00Z",
             "stale_reason": "device unavailable"}
    with open(os.path.join(d, "bench.json"), "w") as f:
        f.write(json.dumps(stale) + "\n")
    assert stale_tpu_rows(d) == ["stale-tpu-row:bench.json"]


# Demoted to slow (PR 20 durations audit): the matrix row schema and
# gap/history logic are covered fast by tests/test_bench_tools.py and
# tools/record_bench.py's render test; the end-to-end subprocess run
# stays in the slow tier and the TPU matrix stage.
@pytest.mark.slow
def test_matrix_bench_rows_parse():
    # Two configs, not three (r4 #8): part1_single covers the
    # single-device row shape, dp_ring covers the DP row shape + the
    # measured collective wall time + the ring_direction stamp; a third
    # config added a whole extra shard_map VGG compile for no new
    # row-shape coverage (dp_psum's program is compiled all over the
    # rest of the suite).
    proc = _run("benchmarks/matrix_bench.py", {
        "MATRIX_PLATFORM": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "MATRIX_STEPS": "1", "MATRIX_WARMUP": "1", "MATRIX_VGG_BATCH": "16",
        "MATRIX_CONFIGS": "part1_single,dp_ring",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    configs = {r["config"]: r for r in rows if "config" in r}
    assert set(configs) == {"part1_single", "dp_ring"}, (
        proc.stderr[-800:])
    assert configs["part1_single"]["devices"] == 1
    assert configs["dp_ring"]["devices"] == 4
    # the DP row carries the measured collective wall time and the
    # wire-schedule stamp (round-4 advisor)
    assert configs["dp_ring"]["grad_allreduce_wall_time_s"] > 0
    assert configs["dp_ring"]["ring_direction"] == "uni"


# Demoted to slow (PR 20 durations audit): prefix-cache semantics are
# covered fast by tests/test_prefix_cache.py and the serve_prefix gap
# gate by tests/test_bench_tools.py; the subprocess smoke runs slow-tier.
@pytest.mark.slow
def test_serve_prefix_bench_rows_parse():
    """The serve_prefix stage's CPU smoke (tier-1's guard on the bench
    path the TPU watcher resumes): both registered workloads emit a
    parseable row with real cache traffic (prefix_hit_tokens > 0) and
    bit-exact parity between the cached and uncached engines."""
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_PREFIX": "shared_prefix,multiturn",
        "SERVE_LAYERS": "1", "SERVE_DMODEL": "64", "SERVE_VOCAB": "128",
        "SERVE_REQUESTS": "4", "SERVE_MAX_NEW": "8", "SERVE_CHUNK": "8",
        "SERVE_PREFIX_LEN": "24", "SERVE_PREFIX_TURNS": "2",
        "SERVE_PREFIX_USERS": "2", "SERVE_PREFIX_CONCURRENCY": "2",
        "SERVE_PREFIX_BLOCKS": "16",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byw = {r["workload"]: r for r in rows
           if r.get("metric") == "serve_prefix" and "workload" in r}
    assert set(byw) == {"shared_prefix", "multiturn"}, proc.stderr[-800:]
    for r in byw.values():
        assert "error" not in r, r
        assert r["value"] > 0
        assert r["prefix_hit_tokens"] > 0   # the cache actually served
        assert r["prefix_lookups"] > 0
        assert r["parity_ok"] is True       # bit-exact vs the uncached run
        assert r["ttft_p50_ms"] > 0 and r["ttft_p50_off_ms"] > 0
    # unregistered workload names fail fast, like BENCH_PARAM_DTYPE typos
    bad = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_PREFIX": "shared_prefx"},
        timeout=300)
    assert bad.returncode != 0
    assert "prefix workloads" in (bad.stderr + bad.stdout)


def test_serve_prefix_gap_gate(tmp_path):
    """tools/bench_gaps serve_prefix stage: CPU smoke rows, error rows,
    parity-broken rows, and zero-hit rows never close a workload;
    banked TPU rows with real cache traffic do (the watcher's
    window-accumulation contract, same rules as the serve stage)."""
    from tools.bench_gaps import SERVE_PREFIX_WORKLOADS, serve_prefix_missing

    d = str(tmp_path)
    assert serve_prefix_missing(d) == list(SERVE_PREFIX_WORKLOADS)
    rows = [
        {"metric": "serve_prefix", "workload": "shared_prefix",
         "value": 1.4, "prefix_hit_tokens": 640, "parity_ok": True,
         "device_kind": "cpu"},                       # smoke: no
        {"metric": "serve_prefix", "workload": "multiturn",
         "error": "device unavailable"},                    # error: no
        {"metric": "serve_prefix", "workload": "multiturn",
         "value": 2.0, "prefix_hit_tokens": 0, "parity_ok": True,
         "device_kind": "TPU v5 lite"},               # no hits: no
        {"metric": "serve_prefix", "workload": "shared_prefix",
         "value": 2.0, "prefix_hit_tokens": 512, "parity_ok": False,
         "device_kind": "TPU v5 lite"},               # parity broken: no
        {"metric": "serve_prefix", "workload": "shared_prefix",
         "value": 1.8, "prefix_hit_tokens": 512, "parity_ok": True,
         "device_kind": "TPU v5 lite"},               # real: yes
    ]
    with open(os.path.join(d, "serve_prefix.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_prefix_missing(d) == ["multiturn"]
    with open(os.path.join(d, "serve_prefix.history.jsonl"), "w") as f:
        f.write(json.dumps(
            {"metric": "serve_prefix", "workload": "multiturn",
             "value": 1.2, "prefix_hit_tokens": 96, "parity_ok": True,
             "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_prefix_missing(d) == []  # banked history row counts


@pytest.mark.slow  # ~33s (L4/d128 deep geometry x two engines); the
# serve_bench paged row path, schema, and bit-exact parity stay fast-tier
# via test_serve_paged_traffic_rows_parse (three engines, same emit/gap
# machinery at tiny geometry) — this row's unique deltas, the >=1.5x
# capacity margin and the gather-free >= gather timing margin, are
# timing-margin gates the bench referees for real on TPU rows only
# (the ISSUE 17 demotion pattern).
def test_serve_paged_bench_rows_parse():
    """The serve_paged stage's CPU smoke (the guard on the
    paged-attention bench the TPU watcher resumes): the registered
    workload emits a parseable row where the paged engine sustained
    >= 1.5x the dense copy engine's co-resident contexts at the same
    KV byte budget (capacity_ok, zero page-pressure vacates), with
    real table-indirected cache traffic and bit-exact parity."""
    # The geometry is larger than the other serve smokes on purpose:
    # the serve_paged_kernel row's gather-free >= gather gate measures
    # a CONTEXT-proportional saving (the gather streamed every live
    # page per step), so the smoke needs enough layers x width x depth
    # for the margin to clear the smoke host's timing noise — at
    # L4/d128 with ~160-token contexts the gather-free engine measures
    # a stable ~1.03-1.11x over the gather baseline (best-of-reps,
    # interleaved, warmup rep discarded); at the tiny L1/d64 geometry
    # the two are within noise of each other and the gate would be a
    # coin flip.
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_PAGED": "shared_prefix",
        "SERVE_LAYERS": "4", "SERVE_DMODEL": "128", "SERVE_VOCAB": "128",
        "SERVE_REQUESTS": "8", "SERVE_MAX_NEW": "48", "SERVE_CHUNK": "16",
        "SERVE_PREFIX_LEN": "48", "SERVE_PREFIX_TURNS": "2",
        "SERVE_PREFIX_USERS": "2", "SERVE_PREFIX_CONCURRENCY": "2",
        "SERVE_PREFIX_BLOCKS": "16", "SERVE_PAGED_KERNEL_SLOTS": "4",
        # The per-traffic kernel rows have their own smoke
        # (test_serve_paged_traffic_rows_parse) at a tiny geometry —
        # their parity gate holds at any size, while THIS row's
        # gather-free >= gather margin needs the L4/d128 depth; running
        # the traffic rows here too would pay three interpret-mode
        # kernel engines at the deep geometry for nothing.
        "SERVE_PAGED_TRAFFIC_ROWS": "0",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byw = {r["workload"]: r for r in rows
           if r.get("metric") == "serve_paged" and "workload" in r}
    assert set(byw) == {"shared_prefix"}, proc.stderr[-800:]
    r = byw["shared_prefix"]
    assert "error" not in r, r
    assert r["value"] >= 1.5                # the capacity bar itself
    assert r["capacity_ok"] is True
    assert r["page_pressure_vacates"] == 0  # the pool genuinely held them
    assert r["contexts_paged"] > r["contexts_dense"]
    assert r["prefix_hit_tokens"] > 0       # hits were table writes
    assert r["parity_ok"] is True           # bit-exact vs the copy engine
    assert r["ttft_p50_ms"] > 0 and r["ttft_p50_copy_ms"] > 0
    assert r["pool_bytes"] > 0 and r["kv_pages"] > 0
    # ... and the SAME invocation emits the gather-free-vs-gather
    # throughput row (serve_paged_kernel), passing its CPU-smoke gate:
    # gather-free decode at least as fast as the PR 13 gather baseline
    # with all three engines bit-identical.
    byk = {r["workload"]: r for r in rows
           if r.get("metric") == "serve_paged_kernel"
           and "workload" in r and "traffic" not in r}
    assert set(byk) == {"shared_prefix"}, proc.stderr[-800:]
    assert not [r for r in rows if "traffic" in r]  # knob honored
    k = byk["shared_prefix"]
    assert "error" not in k, k
    assert k["gather_free_ok"] is True
    assert k["parity_ok"] is True
    assert k["value"] >= 1.0               # gather-free >= gather-paged
    assert k["tokens_per_sec_gather_free"] >= k["tokens_per_sec_gather"]
    assert k["tokens_per_sec_dense"] > 0
    assert k["tokens_per_sec_kernel"] is None  # opt-in column, off here
    # unregistered workload names fail fast, like the prefix stage
    bad = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_PAGED": "shared_prefx"},
        timeout=300)
    assert bad.returncode != 0
    assert "paged workloads" in (bad.stderr + bad.stdout)


def test_serve_paged_gap_gate(tmp_path):
    """tools/bench_gaps serve_paged stage: CPU smoke rows, error rows,
    parity-broken rows, capacity-missed rows, and zero-hit rows never
    close the workload; a banked TPU row passing every gate does."""
    from tools.bench_gaps import SERVE_PAGED_WORKLOADS, serve_paged_missing

    d = str(tmp_path)
    assert serve_paged_missing(d) == list(SERVE_PAGED_WORKLOADS)
    rows = [
        {"metric": "serve_paged", "workload": "shared_prefix",
         "value": 2.0, "capacity_ok": True, "prefix_hit_tokens": 320,
         "parity_ok": True, "device_kind": "cpu"},     # smoke: no
        {"metric": "serve_paged", "workload": "shared_prefix",
         "error": "device unavailable"},                     # error: no
        {"metric": "serve_paged", "workload": "shared_prefix",
         "value": 1.2, "capacity_ok": False, "prefix_hit_tokens": 320,
         "parity_ok": True,
         "device_kind": "TPU v5 lite"},                # capacity: no
        {"metric": "serve_paged", "workload": "shared_prefix",
         "value": 2.0, "capacity_ok": True, "prefix_hit_tokens": 0,
         "parity_ok": True,
         "device_kind": "TPU v5 lite"},                # no hits: no
        {"metric": "serve_paged", "workload": "shared_prefix",
         "value": 2.0, "capacity_ok": True, "prefix_hit_tokens": 320,
         "parity_ok": False,
         "device_kind": "TPU v5 lite"},                # parity broken: no
    ]
    with open(os.path.join(d, "serve_paged.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_paged_missing(d) == ["shared_prefix"]
    with open(os.path.join(d, "serve_paged.history.jsonl"), "w") as f:
        f.write(json.dumps(
            {"metric": "serve_paged", "workload": "shared_prefix",
             "value": 1.8, "capacity_ok": True, "prefix_hit_tokens": 96,
             "parity_ok": True,
             "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_paged_missing(d) == []  # banked history row counts


def test_serve_paged_kernel_gap_gate(tmp_path):
    """tools/bench_gaps serve_paged_kernel stage: CPU smoke rows,
    error rows, and gate-failing rows never close the workload; a TPU
    row with gather_free_ok does.  serve_paged rows in the same file
    never leak into this stage (and vice versa — two metrics, one
    file, one SERVE_PAGED resume list)."""
    from tools.bench_gaps import (SERVE_PAGED_WORKLOADS,
                                  serve_paged_kernel_missing,
                                  serve_paged_missing)

    d = str(tmp_path)
    assert serve_paged_kernel_missing(d) == list(SERVE_PAGED_WORKLOADS)
    rows = [
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "value": 1.1, "gather_free_ok": True, "parity_ok": True,
         "device_kind": "cpu"},                        # smoke: no
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "error": "device unavailable"},                     # error: no
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "value": 0.8, "gather_free_ok": False, "parity_ok": True,
         "device_kind": "TPU v5 lite"},                # slower: no
        # a passing capacity row must NOT close the kernel stage
        {"metric": "serve_paged", "workload": "shared_prefix",
         "value": 2.0, "capacity_ok": True, "prefix_hit_tokens": 320,
         "parity_ok": True, "device_kind": "TPU v5 lite"},
        # nor a passing per-traffic row, even one that (nonsensically)
        # carries gather_free_ok — the traffic field routes it to the
        # serve_paged_traffic stage
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "traffic": "fused", "value": 1.4, "kernel_ok": True,
         "gather_free_ok": True, "parity_ok": True,
         "device_kind": "TPU v5 lite"},
    ]
    with open(os.path.join(d, "serve_paged.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_paged_kernel_missing(d) == ["shared_prefix"]
    assert serve_paged_missing(d) == []  # the capacity row still counts
    with open(os.path.join(d, "serve_paged.history.jsonl"), "w") as f:
        f.write(json.dumps(
            {"metric": "serve_paged_kernel", "workload": "shared_prefix",
             "value": 1.2, "gather_free_ok": True, "parity_ok": True,
             "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_paged_kernel_missing(d) == []  # banked history counts


def test_serve_paged_traffic_rows_parse():
    """The per-traffic kernel-vs-einsum rows' CPU smoke (tier-1's
    guard on the serve_paged_kernel traffic rows the TPU watcher
    resumes): SERVE_PAGED_TRAFFIC_ROWS=only emits one row per traffic
    kind — prefill, verify (k=2), fused (N=4) — each with three-engine
    parity (einsum / gather oracle / Pallas kernel, greedy tokens
    bit-identical over the over-subscribed burst's fragmented tables)
    and the kernel dispatch table recorded.  Off-TPU the kernel lowers
    in interpret mode, so tokens/sec stays unmeasured (value null —
    smoke rows can never close the bench_gaps stage) and the kernel_ok
    gate reads parity alone.  The tiny geometry is deliberate: parity
    is size-independent, unlike the capacity row's margin (see
    test_serve_paged_bench_rows_parse)."""
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_PAGED": "shared_prefix",
        "SERVE_PAGED_TRAFFIC_ROWS": "only",
        "SERVE_LAYERS": "1", "SERVE_DMODEL": "64", "SERVE_VOCAB": "128",
        "SERVE_MAX_NEW": "17", "SERVE_CHUNK": "8",
        "SERVE_PREFIX_LEN": "16", "SERVE_PREFIX_TURNS": "2",
        "SERVE_PREFIX_USERS": "2", "SERVE_PREFIX_CONCURRENCY": "2",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byt = {r["traffic"]: r for r in rows
           if r.get("metric") == "serve_paged_kernel" and "traffic" in r}
    assert set(byt) == {"prefill", "verify", "fused"}, proc.stderr[-800:]
    # ... and ONLY the traffic rows: the capacity + gather-free halves
    # were skipped, that's the "only" contract.
    assert not [r for r in rows if "metric" in r and "traffic" not in r]
    for traffic, r in byt.items():
        assert "error" not in r, r
        assert r["parity_ok"] is True   # einsum == gather == kernel
        assert r["kernel_ok"] is True   # parity-only off-TPU
        assert r["value"] is None       # no interpret-mode timings
        assert r["tokens_per_sec_kernel"] is None
        assert r["fallbacks"] == []     # every family dispatched
        assert r["prefix_hit_tokens"] > 0  # shared pages + COW covered
        assert r["dispatch"]["prefill_paged"] == "kernel"
        assert r["dispatch"]["verify_paged"] == "kernel"
        assert r["dispatch"]["fused_decode_paged"] == "kernel"
    assert byt["prefill"]["max_new_tokens"] == 1
    assert byt["verify"]["speculate_k"] == 2
    assert byt["fused"]["decode_fuse"] == 4


def test_serve_paged_traffic_gap_gate(tmp_path):
    """tools/bench_gaps serve_paged_traffic stage: CPU smoke rows
    (value null), error rows, and gate-failing rows never close a
    (workload, traffic) pair; a measured TPU row with kernel_ok does.
    Base serve_paged_kernel rows (no traffic field) never leak into
    this stage and traffic rows never close the base stage — three row
    kinds, one file, one SERVE_PAGED resume list."""
    from tools.bench_gaps import (SERVE_PAGED_TRAFFIC,
                                  serve_paged_kernel_missing,
                                  serve_paged_traffic_missing)

    d = str(tmp_path)
    want = [f"shared_prefix:{t}" for t in SERVE_PAGED_TRAFFIC]
    assert serve_paged_traffic_missing(d) == want
    rows = [
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "traffic": "prefill", "value": None, "kernel_ok": True,
         "parity_ok": True, "device_kind": "cpu"},     # smoke: no
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "traffic": "verify", "error": "device unavailable"},  # error: no
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "traffic": "fused", "value": 0.7, "kernel_ok": False,
         "parity_ok": True,
         "device_kind": "TPU v5 lite"},                # slower: no
        # a passing BASE kernel row must not close any traffic pair
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "value": 1.1, "gather_free_ok": True, "parity_ok": True,
         "device_kind": "TPU v5 lite"},
        # a passing traffic row closes exactly its own pair...
        {"metric": "serve_paged_kernel", "workload": "shared_prefix",
         "traffic": "verify", "value": 1.3, "kernel_ok": True,
         "parity_ok": True, "device_kind": "TPU v5 lite"},
    ]
    with open(os.path.join(d, "serve_paged.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_paged_traffic_missing(d) == [
        "shared_prefix:prefill", "shared_prefix:fused"]
    # ... and never the base stage (the base row above does that)
    assert serve_paged_kernel_missing(d) == []
    with open(os.path.join(d, "serve_paged.history.jsonl"), "w") as f:
        for t in ("prefill", "fused"):
            f.write(json.dumps(
                {"metric": "serve_paged_kernel",
                 "workload": "shared_prefix", "traffic": t,
                 "value": 1.2, "kernel_ok": True, "parity_ok": True,
                 "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_paged_traffic_missing(d) == []  # banked history counts


@pytest.mark.slow  # ~8s; the fused serve_bench path now runs in the fast
# tier via test_serve_paged_traffic_rows_parse (decode_fuse=4 engines
# end-to-end through serve_bench) and fused-vs-generate parity stays via
# test_serve_fused.py::test_greedy_parity_fused_vs_generate
# (fast-tier margin, r4 #8)
def test_serve_fused_bench_rows_parse():
    """The serve_fused stage's CPU smoke (tier-1's guard on the
    fused-decode bench the TPU watcher resumes): every registered
    window size emits a parseable row with bit-exact parity against
    the single-step engine and the host dispatch count actually
    amortized (dispatch_ok — per-token for N=1, <= 1/N x 1.25 for the
    fused rows, with real fused windows recorded)."""
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_DECODE_FUSE": "1,4,8",
        "SERVE_LAYERS": "1", "SERVE_DMODEL": "64", "SERVE_VOCAB": "128",
        "SERVE_REQUESTS": "3", "SERVE_MAX_NEW": "17", "SERVE_CHUNK": "8",
        "SERVE_PROMPT_LEN": "8",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byn = {r["decode_fuse"]: r for r in rows
           if r.get("metric") == "serve_fused" and "decode_fuse" in r}
    assert set(byn) == {1, 4, 8}, proc.stderr[-800:]
    for n, r in byn.items():
        assert "error" not in r, r
        assert r["value"] > 0
        assert r["parity_ok"] is True   # bit-exact vs the single-step run
        assert r["dispatch_ok"] is True
        assert r["host_dispatches_per_token"] <= (1 / n) * 1.25
    # Unified serve-row schema: every serve row carries accept_rate,
    # null when speculation is off (the spec_fused rows pin the
    # non-null side of the contract).
    assert all(r["accept_rate"] is None for r in byn.values())
    assert byn[1]["fused_windows"] == 0   # N=1 never builds the program
    for n in (4, 8):
        assert byn[n]["fused_windows"] > 0   # the loop actually engaged
        assert byn[n]["fused_steps"] >= byn[n]["fused_windows"]
    # unregistered window sizes fail fast, like the spec-k registry
    bad = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_DECODE_FUSE": "7",
        "SERVE_STRICT_LEVELS": "1"}, timeout=300)
    assert bad.returncode != 0
    assert "decode_fuse" in (bad.stderr + bad.stdout)


# Demoted to slow (PR 20 durations audit): the obs exposition contract
# is covered fast by tests/test_obs.py and the sidecar/gap logic by
# tests/test_bench_tools.py; the A/B subprocess row runs slow-tier.
@pytest.mark.slow
def test_serve_bench_obs_check_row_and_sidecar(tmp_path):
    """The tpudp.obs exposition contract on the bench: --obs-check
    emits the spans+counters-on vs off A/B row (the acceptance bar is
    'within 3% on the CPU smoke host' — the row records the measured
    ratio and the within_3pct verdict; the smoke test pins the
    CONTRACT: parity intact, a real ratio measured, and the per-stage
    metrics sidecar written with live device counters)."""
    sidecar = tmp_path / "serve_bench_metrics.json"
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_OBS_CHECK": "1",
        "SERVE_LAYERS": "1", "SERVE_DMODEL": "64", "SERVE_VOCAB": "128",
        "SERVE_REQUESTS": "6", "SERVE_MAX_NEW": "8", "SERVE_CHUNK": "8",
        "SERVE_PROMPT_LEN": "8", "SERVE_OBS_TRIES": "2",
        "SERVE_METRICS_SIDECAR": str(sidecar),
    }, timeout=600)
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    row = next((r for r in rows
                if r.get("metric") == "serve_obs_overhead"), None)
    assert row is not None, proc.stderr[-800:]
    assert row["parity_ok"] is True  # obs never perturbs outputs
    assert row["value"] is not None and row["value"] > 0
    assert row["tokens_per_sec_obs_on"] > 0
    assert row["tokens_per_sec_obs_off"] > 0
    assert isinstance(row["within_3pct"], bool)
    doc = json.loads(sidecar.read_text())
    assert doc["kind"] == "serve_bench_metrics"
    on = doc["stages"]["obs_check"]["on"]
    assert on["device_counters"]["tokens"] > 0
    assert on["spans"]  # span rollup rode along


def test_serve_fused_gap_gate(tmp_path):
    """tools/bench_gaps serve_fused stage: CPU smoke rows, error rows,
    parity-broken rows, and dispatch-bound-blown rows never close a
    window size; banked TPU rows that passed both gates do (the
    watcher's window-accumulation contract, same rules as the
    serve_spec stage)."""
    from tools.bench_gaps import SERVE_FUSED_NS, serve_fused_missing

    d = str(tmp_path)
    assert serve_fused_missing(d) == list(SERVE_FUSED_NS)
    ok = {"metric": "serve_fused", "value": 9000.0, "parity_ok": True,
          "dispatch_ok": True}
    rows = [
        {**ok, "decode_fuse": 1, "device_kind": "cpu"},   # smoke: no
        {"metric": "serve_fused", "decode_fuse": 4,
         "error": "device unavailable"},                        # error: no
        {**ok, "decode_fuse": 4, "parity_ok": False,
         "device_kind": "TPU v5 lite"},                   # parity: no
        {**ok, "decode_fuse": 8, "dispatch_ok": False,
         "device_kind": "TPU v5 lite"},                   # dispatch: no
        {**ok, "decode_fuse": 1, "device_kind": "TPU v5 lite"},  # yes
    ]
    with open(os.path.join(d, "serve_fused.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_fused_missing(d) == [4, 8]
    with open(os.path.join(d, "serve_fused.history.jsonl"), "w") as f:
        f.write(json.dumps(
            {**ok, "decode_fuse": 8,
             "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_fused_missing(d) == [4]  # banked history row counts


@pytest.mark.slow  # ~35s (4-layer target x 64-token decode x 3 engines);
# the speculative serve_bench path now runs in the fast tier via
# test_serve_paged_traffic_rows_parse (speculate_k=2 engines end-to-end
# through serve_bench) and fused-spec parity/accounting stays via
# test_spec_fused.py::test_fused_spec_greedy_parity_and_accounting;
# the gap-gate logic keeps its own fast synthetic test
# (fast-tier margin, r4 #8)
def test_serve_spec_fused_bench_rows_parse():
    """The serve_spec_fused stage's CPU smoke (tier-1's guard on the
    on-device fused-speculation bench the TPU watcher resumes): every
    registered k{K}n{N} config emits a parseable row that beat BOTH
    referees at identical geometry — the host-drafted speculative
    engine and the plain fused engine — with greedy outputs bit-exact
    across all three, sampled outputs bit-exact vs the host-drafted
    engine under the same per-slot PRNG chains, and real acceptance
    accounting (the zero-tree ceiling workload drafts at ~1.0).  The
    4-layer target gives the 1-layer draft model a real cost edge; at
    SERVE_LAYERS=1 draft and target forwards cost the same and fusion
    has nothing to amortize, and at 3 layers the thin k2n4 margin
    (1.02x) flaked under full-suite load on the 1-core host — 4 layers
    + the longer 64-token decode measure 1.04-1.2x vs the host-drafted
    referee and hold >=1.14x even under two busy-loop CPU hogs."""
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_SPEC_FUSED": "k2n4,k4n8",
        "SERVE_SPEC_FUSED_TRIES": "4",
        "SERVE_LAYERS": "4", "SERVE_DMODEL": "64", "SERVE_VOCAB": "128",
        "SERVE_REQUESTS": "3", "SERVE_MAX_NEW": "17",
        "SERVE_SPEC_MAX_NEW": "64", "SERVE_CHUNK": "8",
        "SERVE_PROMPT_LEN": "8",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byc = {r["config"]: r for r in rows
           if r.get("metric") == "serve_spec_fused" and "config" in r}
    assert set(byc) == {"k2n4", "k4n8"}, proc.stderr[-800:]
    for r in byc.values():
        assert "error" not in r, r
        assert r["value"] > 0
        assert r["parity_ok"] is True          # greedy, all three engines
        assert r["sampled_parity_ok"] is True  # same PRNG chains as host
        assert r["spec_fused_ok"] is True
        assert r["fused_spec_windows"] > 0     # the fused window engaged
        assert r["value"] >= r["host_spec_tokens_per_sec"]
        assert r["value"] >= r["plain_fused_tokens_per_sec"]
        # acceptance accounting is real, not vestigial: the ceiling
        # workload's constant greedy stream drafts at ~1.0
        assert r["accept_rate"] is not None and r["accept_rate"] > 0.5
        assert r["draft_accepted"] > 0
    # unregistered configs fail fast, like the workload-name registries
    bad = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_SPEC_FUSED": "k3n5"}, timeout=300)
    assert bad.returncode != 0
    assert "spec-fused" in (bad.stderr + bad.stdout)


def test_serve_spec_fused_gap_gate(tmp_path):
    """tools/bench_gaps serve_spec_fused stage: CPU smoke rows, error
    rows, parity-broken rows, and rows that lost to a baseline
    (spec_fused_ok False) never close a config; banked TPU rows that
    passed the full gate do (the watcher's config-accumulation
    contract, same rules as the serve_fused stage)."""
    from tools.bench_gaps import (SERVE_SPEC_FUSED_CONFIGS,
                                  serve_spec_fused_missing)

    d = str(tmp_path)
    assert serve_spec_fused_missing(d) == list(SERVE_SPEC_FUSED_CONFIGS)
    ok = {"metric": "serve_spec_fused", "value": 9000.0,
          "parity_ok": True, "spec_fused_ok": True}
    rows = [
        {**ok, "config": "k2n4", "device_kind": "cpu"},   # smoke: no
        {"metric": "serve_spec_fused", "config": "k2n4",
         "error": "device unavailable"},                        # error: no
        {**ok, "config": "k2n4", "parity_ok": False,
         "device_kind": "TPU v5 lite"},                   # parity: no
        {**ok, "config": "k4n8", "spec_fused_ok": False,
         "device_kind": "TPU v5 lite"},                   # lost: no
        {**ok, "config": "k2n4", "device_kind": "TPU v5 lite"},  # yes
    ]
    with open(os.path.join(d, "serve_spec_fused.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_spec_fused_missing(d) == ["k4n8"]
    with open(os.path.join(d, "serve_spec_fused.history.jsonl"),
              "w") as f:
        f.write(json.dumps(
            {**ok, "config": "k4n8",
             "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_spec_fused_missing(d) == []  # banked history row counts


@pytest.mark.slow  # ~10s; every property this row asserts is pinned
# fast-tier in-process by tests/test_tenancy.py (preemption storm
# no-leak/parity, stride fair shares, per-tier shedding) — the bench
# subprocess re-derives them through serve_bench's emit path, whose row
# schema and seed-closing rules test_serve_tenancy_gap_gate keeps fast.
def test_serve_tenancy_bench_row_parses():
    """The serve_tenancy stage's CPU smoke (the guard on the
    multi-tenant bench the TPU watcher resumes): at a trimmed geometry
    the mixed-priority workload must emit a parseable row where the
    high tier's p99 held under low-tier overload (p99_ok), preemptions
    actually fired and resumed bit-exactly (parity_ok covers them), the
    low tiers shed past their per-class bounds, measured fair shares
    landed within 10% of the configured 3:1 weights, and the engine
    ended empty."""
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_TENANCY": "0",
        "TENANCY_STEPS": "60", "TENANCY_HIGH": "6",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byseed = {r["seed"]: r for r in rows
              if r.get("metric") == "serve_tenancy" and "seed" in r}
    assert set(byseed) == {0}, proc.stderr[-800:]
    r = byseed[0]
    assert "error" not in r, r
    assert r["value"] > 0                      # a real p99 was measured
    assert r["p99_ok"] is True                 # high tier held its SLO
    assert r["parity_ok"] is True              # preempted+resumed bit-exact
    assert r["no_leak"] is True and r["wedged"] is False
    assert r["preempted"] > 0                  # the storm actually evicted
    assert r["shed"] > 0                       # overload actually shed
    assert r["fairness_ok"] is True
    assert abs(r["fairness_share_measured"]
               - r["fairness_share_configured"]) <= 0.10
    assert r["completed_high"] == r["high_requests"]
    # unregistered seeds fail fast, like the soak's seed registry
    bad = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_TENANCY": "9",
        "SERVE_STRICT_LEVELS": "1"}, timeout=300)
    assert bad.returncode != 0
    assert "tenancy seeds" in (bad.stderr + bad.stdout)


def test_serve_tenancy_gap_gate(tmp_path):
    """tools/bench_gaps serve_tenancy stage: CPU smoke rows, error rows,
    p99-blown rows, parity-broken rows, and leaking rows never close a
    seed; banked TPU rows that passed every gate do (the watcher's
    window-accumulation contract, same rules as the serve_soak
    stage)."""
    from tools.bench_gaps import SERVE_TENANCY_SEEDS, serve_tenancy_missing

    d = str(tmp_path)
    assert serve_tenancy_missing(d) == list(SERVE_TENANCY_SEEDS)
    ok = {"metric": "serve_tenancy", "value": 9.1, "p99_ok": True,
          "parity_ok": True, "no_leak": True}
    rows = [
        {**ok, "seed": 0, "device_kind": "cpu"},      # smoke: no
        {"metric": "serve_tenancy", "seed": 1,
         "error": "device unavailable"},                    # error: no
        {**ok, "seed": 1, "p99_ok": False,
         "device_kind": "TPU v5 lite"},               # p99 blown: no
        {**ok, "seed": 2, "parity_ok": False,
         "device_kind": "TPU v5 lite"},               # parity broken: no
        {**ok, "seed": 2, "no_leak": False,
         "device_kind": "TPU v5 lite"},               # leak: no
        {**ok, "seed": 0, "device_kind": "TPU v5 lite"},  # real pass: yes
    ]
    with open(os.path.join(d, "serve_tenancy.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_tenancy_missing(d) == [1, 2]
    with open(os.path.join(d, "serve_tenancy.history.jsonl"), "w") as f:
        f.write(json.dumps(
            {**ok, "seed": 2, "device_kind": "TPU v5 lite"}) + "\n")
    assert serve_tenancy_missing(d) == [1]  # banked history row counts


@pytest.mark.slow  # ~27s (three subprocess workers each paying the full
# jax import); the handoff protocol this drives is pinned fast-tier
# in-process by tests/test_disagg.py (migration/failover/quarantine/
# parity edge matrix) + the protocol verifier and migration model
# checker in test_analysis_clean/test_protocol, and the row schema +
# seed-closing rules by test_serve_disagg_gap_gate — the two-process
# bench run itself is the watcher battery's job (CPU rows close this
# stage's seeds, so the slow tier still runs it pre-battery).
def test_serve_disagg_bench_row_parses():
    """The serve_disagg stage's CPU smoke (the guard on the
    two-process prefill/decode split the TPU watcher resumes): rank 0
    must prefill and ship every request's pages, rank 1 must adopt and
    decode them bit-identically to the colocated baseline (parity_ok +
    split_ok), both processes must end empty and leak-free, and the
    TTFT/decode-gap gates vs the colocated percentiles must hold at
    their documented CPU-smoke bounds.  Trimmed workload: the contract
    under test is the handoff protocol, not throughput."""
    proc = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu",
        "SERVE_DISAGG": "0",
        "DISAGG_REQUESTS": "4", "DISAGG_BURST": "2",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byseed = {r["seed"]: r for r in rows
              if r.get("metric") == "serve_disagg" and "seed" in r}
    assert set(byseed) == {0}, proc.stderr[-800:]
    r = byseed[0]
    assert "error" not in r, r
    assert r["value"] > 0                      # pages actually moved
    assert r["parity_ok"] is True              # bit-exact vs colocated
    assert r["split_ok"] is True               # all jobs crossed hosts
    assert r["no_leak"] is True
    assert r["ttft_ok"] is True and r["p99_ok"] is True
    assert r["migrated"] == r["requests"] + r["burst"] == 6
    assert r["migrated_pages"] >= r["migrated"]
    # unregistered seeds fail fast, like the soak's seed registry
    bad = _run("benchmarks/serve_bench.py", {
        "SERVE_PLATFORM": "cpu", "SERVE_DISAGG": "9",
        "SERVE_STRICT_LEVELS": "1"}, timeout=300)
    assert bad.returncode != 0
    assert "disagg seeds" in (bad.stderr + bad.stdout)


def test_serve_disagg_gap_gate(tmp_path):
    """tools/bench_gaps serve_disagg stage: error rows, split-incomplete
    rows, parity-broken rows, leaking rows, and latency-blown rows never
    close a seed; passing rows do — INCLUDING on device_kind=cpu,
    because unlike every other serve stage the two ranks are CPU
    processes by construction (two processes cannot share one libtpu)
    and the handoff protocol is platform-independent."""
    from tools.bench_gaps import SERVE_DISAGG_SEEDS, serve_disagg_missing

    d = str(tmp_path)
    assert serve_disagg_missing(d) == list(SERVE_DISAGG_SEEDS)
    ok = {"metric": "serve_disagg", "value": 9043.2, "split_ok": True,
          "parity_ok": True, "no_leak": True, "ttft_ok": True,
          "p99_ok": True, "device_kind": "cpu"}
    rows = [
        {"metric": "serve_disagg", "seed": 0,
         "error": "worker died"},                    # error: no
        {**ok, "seed": 1, "split_ok": False},        # split short: no
        {**ok, "seed": 1, "parity_ok": False},       # parity broken: no
        {**ok, "seed": 2, "no_leak": False},         # leak: no
        {**ok, "seed": 2, "ttft_ok": False},         # ttft blown: no
        {**ok, "seed": 2, "p99_ok": False},          # p99 blown: no
        {**ok, "seed": 0},                           # cpu pass: YES
    ]
    with open(os.path.join(d, "serve_disagg.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert serve_disagg_missing(d) == [1, 2]
    with open(os.path.join(d, "serve_disagg.history.jsonl"), "w") as f:
        f.write(json.dumps({**ok, "seed": 1}) + "\n")
    assert serve_disagg_missing(d) == [2]  # banked history row counts


# Demoted to slow (PR 20 durations audit): the fault/resume machinery is
# covered fast by tests/test_resilience.py and tests/test_sdc.py, the
# gap gate by tests/test_bench_tools.py; the FULL 2-kill menu already
# runs slow-tier as test_train_soak_full_menu.
@pytest.mark.slow
def test_train_soak_bench_row_parses():
    """The train_soak stage's CPU smoke (tier-1's guard on the kill/
    resume soak the TPU watcher resumes): a reduced 1-kill plan (loader
    fault + raising step + SIGKILL + corrupt-checkpoint fallback + loss
    spike) must complete with zero human intervention, final params
    bit-identical to the uninterrupted run (parity_ok), and every planned
    recovery accounted in the typed event log (accounted).  The FULL
    2-kill menu (adds NaN rollback + stall-under-watchdog) runs in the
    slow tier (test_train_soak_full_menu) and on the TPU stage."""
    proc = _run("benchmarks/resilience_bench.py", {
        "TRAIN_SOAK_PLATFORM": "cpu",
        "TRAIN_SOAK": "0",
        "TRAIN_SOAK_KILLS": "1",
        "TRAIN_SOAK_PACE_S": "0.05",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    byseed = {r["seed"]: r for r in rows
              if r.get("metric") == "train_soak"}
    assert set(byseed) == {0}, proc.stderr[-800:]
    r = byseed[0]
    assert "error" not in r, r
    assert r["value"] > 0                      # recoveries happened
    assert r["parity_ok"] is True              # bit-exact vs uninterrupted
    assert r["accounted"] is True              # every planned fault recovered
    assert r["kills"] == 1 and r["relaunches"] >= r["kills"] + 1
    assert r["spike_rollbacks"] >= 1 and r["loader_restarts"] >= 1
    assert r["step_retries"] >= 1 and r["ckpt_fallbacks"] >= 1
    # unregistered seeds fail fast, like the serve soak's seed registry
    bad = _run("benchmarks/resilience_bench.py", {
        "TRAIN_SOAK_PLATFORM": "cpu", "TRAIN_SOAK": "7"}, timeout=300)
    assert bad.returncode != 0
    assert "soak seeds" in (bad.stderr + bad.stdout)


@pytest.mark.slow
def test_train_soak_full_menu():
    """The full 2-kill chaos schedule — NaN, spike, stall-under-watchdog,
    step-raise, loader-raise, 2 SIGKILLs, corrupt checkpoint — with the
    bit-exact + fully-accounted referee (the acceptance oracle for
    docs/RESILIENCE.md)."""
    proc = _run("benchmarks/resilience_bench.py", {
        "TRAIN_SOAK_PLATFORM": "cpu",
        "TRAIN_SOAK": "0",
        "TRAIN_SOAK_WD_TIMEOUT": "6",
    })
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    r = next(r for r in rows if r.get("metric") == "train_soak")
    assert "error" not in r, r
    assert r["parity_ok"] is True and r["accounted"] is True
    assert r["kills"] == 2 and r["relaunches"] >= 3
    assert r["nan_rollbacks"] >= 1 and r["spike_rollbacks"] >= 1
    assert r["hang_retries"] >= 1 and r["loader_restarts"] >= 1
    assert r["ckpt_fallbacks"] >= 1


def test_train_soak_gap_gate(tmp_path):
    """tools/bench_gaps train_soak stage: CPU smoke rows, error rows,
    parity-broken rows, and unaccounted rows never close a seed; banked
    TPU rows that passed do (the watcher's window-accumulation contract,
    same rules as the serve_soak stage)."""
    from tools.bench_gaps import TRAIN_SOAK_SEEDS, train_soak_missing

    d = str(tmp_path)
    assert train_soak_missing(d) == list(TRAIN_SOAK_SEEDS)
    rows = [
        {"metric": "train_soak", "seed": 0, "value": 9,
         "parity_ok": True, "accounted": True,
         "device_kind": "cpu"},                       # smoke: no
        {"metric": "train_soak", "seed": 1,
         "error": "device unavailable", "value": 0},        # error: no
        {"metric": "train_soak", "seed": 1, "value": 8,
         "parity_ok": False, "accounted": True,
         "device_kind": "TPU v5 lite"},               # diverged: no
        {"metric": "train_soak", "seed": 2, "value": 7,
         "parity_ok": True, "accounted": False,
         "device_kind": "TPU v5 lite"},               # unaccounted: no
        {"metric": "train_soak", "seed": 0, "value": 9,
         "parity_ok": True, "accounted": True,
         "device_kind": "TPU v5 lite"},               # real pass: yes
    ]
    with open(os.path.join(d, "train_soak.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert train_soak_missing(d) == [1, 2]
    with open(os.path.join(d, "train_soak.history.jsonl"), "w") as f:
        f.write(json.dumps(
            {"metric": "train_soak", "seed": 2, "value": 6,
             "parity_ok": True, "accounted": True,
             "device_kind": "TPU v5 lite"}) + "\n")
    assert train_soak_missing(d) == [1]  # banked history row counts


@pytest.mark.slow
def test_train_soak_multihost_row():
    """The pod-scale soak end-to-end on the CPU smoke geometry (2 hosts
    x 2 virtual devices): NaN -> coordinated rollback, SIGKILL one
    worker, shard byte-flip, coordinated hang recovery, second kill,
    reduced-geometry (1-host) elastic resume with a spike — final params
    bit-identical to an uninterrupted run and every fault accounted
    (the acceptance oracle for docs/RESILIENCE.md "Multi-host
    recovery")."""
    proc = _run("benchmarks/resilience_bench.py", {
        "TRAIN_SOAK_PLATFORM": "cpu",
        "TRAIN_SOAK_EPOCHS": "3",
        "TRAIN_SOAK_PER_EPOCH": "4",
        "TRAIN_SOAK_WD_TIMEOUT": "6",
        "TRAIN_SOAK_VOTE_TIMEOUT": "20",
        "TRAIN_SOAK_MULTIHOST": "0",
    }, args=["--multihost"], timeout=900)
    rows = [json.loads(l) for l in proc.stdout.strip().splitlines()
            if l.startswith("{")]
    r = next(r for r in rows if r.get("metric") == "train_soak_multihost")
    assert "error" not in r, r
    assert r["parity_ok"] is True and r["accounted"] is True
    assert r["kills"] == 2 and r["hosts"] == 2
    assert r["nan_rollbacks"] >= 1 and r["hang_retries"] >= 1
    assert r["coordinated_recoveries"] >= 2
    assert r["ckpt_fallbacks"] >= 1 and r["spike_rollbacks"] >= 1
    assert r["elastic_resumes"] >= 1          # 2-host ckpt resumed at 1


def test_train_soak_multihost_gap_gate(tmp_path):
    """tools/bench_gaps train_soak_multihost stage: same closing rules
    as train_soak (no error/diverged/unaccounted rows) plus the elastic
    rung — a row that never resumed at a reduced geometry does not close
    its seed.  Unlike the other stages, cpu rows DO close it: the pod
    workers run the CPU backend by construction (co-located processes
    cannot share one libtpu), and the protocol the soak certifies is
    platform-independent."""
    from tools.bench_gaps import (TRAIN_SOAK_MULTIHOST_SEEDS,
                                  train_soak_multihost_missing)

    d = str(tmp_path)
    assert (train_soak_multihost_missing(d)
            == list(TRAIN_SOAK_MULTIHOST_SEEDS))
    ok = {"metric": "train_soak_multihost", "value": 6, "parity_ok": True,
          "accounted": True, "elastic_resumes": 1, "device_kind": "cpu"}
    rows = [
        {"metric": "train_soak_multihost", "seed": 1,
         "error": "pod wedged", "value": 0},              # error: no
        {**ok, "seed": 1, "parity_ok": False},            # diverged: no
        {**ok, "seed": 2, "elastic_resumes": 0},          # no elastic: no
        {**ok, "seed": 0},                                # cpu pass: yes
    ]
    with open(os.path.join(d, "train_soak_multihost.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert train_soak_multihost_missing(d) == [1, 2]
    with open(os.path.join(d, "train_soak_multihost.history.jsonl"),
              "w") as f:
        f.write(json.dumps({**ok, "seed": 2}) + "\n")
    assert train_soak_multihost_missing(d) == [1]  # banked row counts
