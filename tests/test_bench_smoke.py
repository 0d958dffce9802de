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


# Demoted to slow (PR 20 durations audit): prefix-cache semantics are
# covered fast by tests/test_prefix_cache.py; the subprocess smoke runs
# slow-tier.
@pytest.mark.slow
def test_serve_prefix_bench_rows_parse():
    """The --prefix-cache mode's CPU smoke: both default workloads emit a
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


@pytest.mark.slow  # ~33s (L4/d128 deep geometry x two engines); the
# serve_bench paged row path, schema, and bit-exact parity stay fast-tier
# via test_serve_paged_traffic_rows_parse (three engines, same emit
# machinery at tiny geometry) — this row's unique deltas, the >=1.5x
# capacity margin and the gather-free >= gather timing margin, are
# timing-margin gates the bench referees for real on TPU rows only
# (the ISSUE 17 demotion pattern).
def test_serve_paged_bench_rows_parse():
    """The --paged mode's CPU smoke: the default workload emits a parseable row where the paged engine sustained
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


def test_serve_paged_traffic_rows_parse():
    """The per-traffic kernel-vs-einsum rows' CPU smoke (tier-1's
    guard on the serve_paged_kernel rows): SERVE_PAGED_TRAFFIC_ROWS=only emits one row per traffic
    kind — prefill, verify (k=2), fused (N=4) — each with three-engine
    parity (einsum / gather oracle / Pallas kernel, greedy tokens
    bit-identical over the over-subscribed burst's fragmented tables)
    and the kernel dispatch table recorded.  Off-TPU the kernel lowers
    in interpret mode, so tokens/sec stays unmeasured (value null: an
    interpreter's rate is never written as a speed) and the kernel_ok
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


@pytest.mark.slow  # ~8s; the fused serve_bench path now runs in the fast
# tier via test_serve_paged_traffic_rows_parse (decode_fuse=4 engines
# end-to-end through serve_bench) and fused-vs-generate parity stays via
# test_serve_fused.py::test_greedy_parity_fused_vs_generate
# (fast-tier margin, r4 #8)
def test_serve_fused_bench_rows_parse():
    """The --decode-fuse mode's CPU smoke: every default
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
# is covered fast by tests/test_obs.py; the A/B subprocess row runs
# slow-tier.
@pytest.mark.slow
def test_serve_bench_obs_check_row_and_sidecar(tmp_path):
    """The tpudp.obs exposition contract on the bench: --obs-check
    emits the spans+counters-on vs off A/B row (the acceptance bar is
    'within 3% on the CPU smoke host' — the row records the measured
    ratio and the within_3pct verdict; the smoke test pins the
    CONTRACT: parity intact, a real ratio measured, and the per-stage
    metrics sidecar written with live host counters)."""
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
    assert on["stats"]["tokens"] > 0
    assert on["spans"]  # span rollup rode along
    assert sum(on["exposed"].values()) == pytest.approx(
        on["stats"]["exposed_s"])


@pytest.mark.slow  # ~35s (4-layer target x 64-token decode x 3 engines);
# the speculative serve_bench path now runs in the fast tier via
# test_serve_paged_traffic_rows_parse (speculate_k=2 engines end-to-end
# through serve_bench) and fused-spec parity/accounting stays via
# test_spec_fused.py::test_fused_spec_greedy_parity_and_accounting
# (fast-tier margin, r4 #8)
def test_serve_spec_fused_bench_rows_parse():
    """The --spec-fused mode's CPU smoke: every
    default k{K}n{N} config emits a parseable row that beat BOTH
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


@pytest.mark.slow  # ~10s; every property this row asserts is pinned
# fast-tier in-process by tests/test_tenancy.py (preemption storm
# no-leak/parity, stride fair shares, per-tier shedding) — the bench
# subprocess re-derives them through serve_bench's emit path.
def test_serve_tenancy_bench_row_parses():
    """The --tenants mode's CPU smoke: at a trimmed geometry
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


@pytest.mark.slow  # ~27s (three subprocess workers each paying the full
# jax import); the handoff protocol this drives is pinned fast-tier
# in-process by tests/test_disagg.py (migration/failover/quarantine/
# parity edge matrix) + the protocol verifier and migration model
# checker in test_analysis_clean/test_protocol; the two-process run
# itself stays in the slow tier.
def test_serve_disagg_bench_row_parses():
    """The --disagg mode's CPU smoke (the two-process prefill/decode
    split): rank 0
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


# Demoted to slow (PR 20 durations audit): the fault/resume machinery is
# covered fast by tests/test_resilience.py and tests/test_sdc.py; the
# FULL 2-kill menu already runs slow-tier as test_train_soak_full_menu.
@pytest.mark.slow
def test_train_soak_bench_row_parses():
    """The kill/resume soak's CPU smoke: a reduced 1-kill plan (loader
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


