"""Which benchmark measurements are still missing from bench_results/?

Chip time is budgeted, so a run should spend it ONLY on measurements that
have not landed yet.  This helper reads the current + banked (.history)
result files and prints the missing work as arguments the benches accept:

    python tools/bench_gaps.py matrix   -> comma-separated MATRIX_CONFIGS
    python tools/bench_gaps.py flash    -> space-separated t values (argv)
    python tools/bench_gaps.py epoch    -> "epoch" if the epoch-throughput
                                           row is still missing
    python tools/bench_gaps.py mfu      -> comma-separated MFU_VARIANTS
                                           (ablations still unmeasured)
    python tools/bench_gaps.py serve    -> comma-separated concurrency
                                           levels (serving rows missing)
    python tools/bench_gaps.py serve_spec -> comma-separated speculate_k
                                           values (speculative-serving
                                           rows missing)
    python tools/bench_gaps.py serve_fused -> comma-separated fused
                                           decode window sizes (on-device
                                           decode-loop rows missing)
    python tools/bench_gaps.py serve_prefix -> comma-separated prefix-
                                           caching workloads (TTFT
                                           cache-on/off rows missing)
    python tools/bench_gaps.py serve_tenancy -> comma-separated multi-
                                           tenant serving seeds (priority/
                                           fairness rows missing)
    python tools/bench_gaps.py train_soak -> comma-separated kill/resume
                                           soak seeds (training-resilience
                                           rows missing)
    python tools/bench_gaps.py train_soak_multihost -> comma-separated
                                           pod-scale kill-one-host soak
                                           seeds (multi-host resilience
                                           rows missing)
    python tools/bench_gaps.py analysis -> any of "lint" (unsuppressed
                                           findings), "audit" (tools/
                                           trace_lock.json stale against
                                           the pinned hot-path sources),
                                           "protocol" (cross-host
                                           protocol verifier findings
                                           over the multihost modules),
                                           "budget" (lockfile missing
                                           resource ledgers/geometry)
                                           (correctness gates, not TPU
                                           measurements — they key off
                                           the TREE, not bench_results/)
    python tools/bench_gaps.py obs      -> "sidecar" if serve-bench rows
                                           were measured without the
                                           tpudp.obs metrics sidecar
                                           (serve_bench_metrics.json)
                                           landing next to them

Empty output means the stage is complete.  Error rows do not count as
measured: a config that crashed in one run is retried in the next.  Pure
stdlib (no jax import) so it is cheap to call (and never touches a
device) — the analysis stage keeps that true by
loading tpudp/analysis by FILE PATH under a synthetic package name (its
lint half is stdlib by design), never importing the jax-heavy `tpudp`
parent package.
"""

import argparse
import importlib.util
import json
import os
import sys

MATRIX_CONFIGS = ("part1_single", "dp_psum", "dp_ring", "dp_coordinator",
                  "dp_gspmd", "resnet50", "gpt2_small", "gpt2_flash",
                  "llama_gqa")
FLASH_TS = (4096, 8192, 16384)
# Concurrency levels the serving bench (benchmarks/serve_bench.py) must
# measure — the canonical registry the bench imports, same contract as
# MATRIX_CONFIGS (a level added on one side but not the other would
# silently never be measured).
SERVE_CONCURRENCIES = (1, 4, 8)
# Speculation depths the speculative-serving rows (serve_bench.py
# --speculate-k, n-gram drafting vs the non-speculative baseline) must
# measure on the TPU; same registry contract.
SERVE_SPEC_KS = (2, 4, 8)
# Prefix-caching workloads (serve_bench.py --prefix-cache: TTFT with the
# block-pool + radix-tree cache on vs off on shared-system-prompt and
# multi-turn traffic) that must be measured on the TPU; same registry
# contract.  A row closes its workload only with real cache traffic
# (prefix_hit_tokens > 0) and bit-exact parity between the cached and
# uncached engines.
SERVE_PREFIX_WORKLOADS = ("shared_prefix", "multiturn")
# Paged-attention workloads (serve_bench.py --paged: the TRUE paged
# engine — per-slot block tables into one shared page pool,
# Engine(kv_pages=N) — vs the dense copy-cache engine at the SAME KV
# byte budget) that must be measured on the TPU; same registry
# contract.  A row closes its workload only when the paged engine
# sustained >= 1.5x the dense engine's co-resident contexts at fixed
# pool bytes without a single page-pressure vacate (capacity_ok), the
# cache actually served (prefix_hit_tokens > 0), and greedy outputs
# were bit-identical between the two engines (parity_ok).
SERVE_PAGED_WORKLOADS = ("shared_prefix",)
# Paged-attention traffic kinds whose kernel-vs-einsum throughput the
# same --paged invocation must measure on the TPU (serve_bench.py
# emits one serve_paged_kernel row per kind with a ``traffic`` field:
# prefill = chunked prompt ingestion through the flash-prefill kernel,
# verify = k=2 host speculation through the multi-token verify-window
# kernel, fused = 4-token in-loop decode windows dispatching the
# decode kernel inside the while body).  A row closes its
# (workload, traffic) pair only when the kernel at least matched the
# einsum fallback's tokens/sec with all three engines — einsum, the
# PR 13 gather oracle, and the kernel — bit-identical over fragmented
# tables (kernel_ok, which folds in parity_ok).
SERVE_PAGED_TRAFFIC = ("prefill", "verify", "fused")
# Fused decode window sizes (serve_bench.py --decode-fuse: one
# lax.while_loop program runs up to N decode steps on device per host
# dispatch — the on-device decode loop, ROADMAP "kill the per-token
# host round-trip") that must be measured on the TPU; same registry
# contract.  A row closes its N only when it measured something
# (tokens/sec > 0), the fused engine's outputs were bit-identical to
# the single-step engine's (parity_ok), and the measured
# host-dispatches-per-decoded-token landed within the fused bound
# (dispatch_ok: <= 1/N x 1.25) — a fused run that dispatched per token
# proved the loop never engaged.  N=1 is the single-step control row.
SERVE_FUSED_NS = (1, 4, 8)
# On-device fused speculation configs (serve_bench.py --spec-fused:
# ONE lax.while_loop program per dispatch runs up to N iterations of
# [k draft-model forwards + one k+1-wide verify + rejection sampling],
# draft KV living in its own in-carry arena — the draft never leaves
# the device).  Each config name is "k{K}n{N}".  A config closes only
# when the fused-spec engine measured something (tokens/sec > 0), its
# greedy outputs were bit-identical to BOTH referees — the host-drafted
# speculative engine and the plain fused engine — AND its sampled
# outputs matched the host-drafted engine under identical per-slot PRNG
# chains (parity_ok), and the full gate held (spec_fused_ok: the fused
# window actually engaged and tokens/sec >= max(host-drafted spec,
# plain fused) — on-device speculation that loses to either baseline
# proved the fusion isn't paying for itself).
SERVE_SPEC_FUSED_CONFIGS = ("k2n4", "k4n8")
# Fault-injection soak seeds (serve_bench.py --soak: random cancels,
# deadline mix, injected drafter/step faults — and, since the tenancy
# PR, a deterministic preemption storm — against the serve engine's
# robustness layer) that must PASS on the TPU — a seed is closed only by
# a row that completed with parity intact and no slot/queue leak; same
# registry contract.
SERVE_SOAK_SEEDS = (0, 1, 2)
# Multi-tenant serving seeds (serve_bench.py --tenants: mixed-priority
# workload with per-tier latency percentiles, weighted fair shares, and
# per-class shedding under overload) that must PASS on the TPU — a seed
# is closed only by a row where the high tier's p99 TTFT under overload
# stayed within TENANCY_P99_BOUND x its no-overload p99 (p99_ok), every
# surviving output was bit-exact (parity_ok), and the engine ended
# empty (no_leak); same registry contract.
SERVE_TENANCY_SEEDS = (0, 1, 2)
# Disaggregated-serving seeds (serve_bench.py --disagg: two OS
# processes — prefill host and decode host — driving the real
# DisaggHost handshake over jax.distributed against a colocated
# baseline on the same Poisson+burst mixed-tenant workload).  A seed
# closes only on a row where every request actually split (split_ok),
# outputs were bit-exact vs colocated (parity_ok), both processes
# ended leak-free (no_leak), and TTFT/decode-gap p99 held within
# their bounds (ttft_ok/p99_ok).  Like TRAIN_SOAK_MULTIHOST_SEEDS
# there is NO real-TPU device gate: the two ranks are co-located CPU
# processes by construction (two processes cannot share one host's
# libtpu), and what the row certifies — the handoff protocol and its
# per-page cost — is platform-independent.
SERVE_DISAGG_SEEDS = (0, 1, 2)
# Kill/resume soak seeds for the TRAINING resilience layer
# (benchmarks/resilience_bench.py: SIGKILL + relaunch, injected NaN/
# spike/stall/step-raise/loader faults, checkpoint corruption against
# tpudp/resilience.py) that must PASS on the TPU — a seed is closed only
# by a row whose final params were bit-identical to the uninterrupted
# run (parity_ok) with every recovery accounted in the typed event log
# (accounted); same registry contract.
TRAIN_SOAK_SEEDS = (0, 1, 2)
# Pod-scale kill-one-host soak seeds (resilience_bench.py --multihost:
# N worker processes under the coordinated supervisor, SIGKILL one
# mid-epoch, byte-flip one host's checkpoint shard, relaunch at the
# same and at a REDUCED host geometry) that must PASS — same closing
# bar as train_soak (parity_ok + accounted), plus the row must have
# resumed the multi-host checkpoint at the reduced geometry
# (elastic_resumes > 0).  Unlike the other stages there is NO real-TPU
# device gate: the pod is N co-located OS processes on the CPU backend
# by construction (two processes cannot share one host's libtpu; real
# multi-VM TPU pods are launched by a scheduler, not this script), and
# what the soak certifies — the coordination protocol — is
# platform-independent.
TRAIN_SOAK_MULTIHOST_SEEDS = (0, 1, 2)
# Silent-data-corruption soak seeds (resilience_bench.py --sdc: a clean
# fit with in-step replica fingerprints on, a one-shot injected bit
# flip, and a persistent flip, against tpudp/sdc.py + the supervisor's
# graded response) that must PASS on the TPU — a seed is closed only by
# a row where the clean fit raised ZERO detections (clean_ok: the
# false-positive gate), the one-shot flip was detected, localized to
# the injected replica, and repaired BIT-IDENTICAL to the clean run
# (accounted + parity_ok), and the persistent flip escalated to the
# quarantine marker (quarantine_ok); same registry contract.
SDC_SOAK_SEEDS = (0, 1, 2)
# Tier-1 wall-clock headroom: the suite must stay under its 870 s
# ceiling (ROADMAP.md), and a run that burns past 820 s is one flaky
# collection away from timing out on the next PR — surface the gap
# BEFORE the ceiling breaks, not after.
TIER1_BUDGET_S = 870.0
TIER1_WARN_S = 820.0
# Pipeline-parallel training geometries (benchmarks/pipeline_bench.py:
# the unrolled 1F1B MPMD schedule of tpudp/parallel/schedule.py over a
# pp{P}dp{D}[v{V}] mesh — P stages x D replicas, V virtual stages per
# device — with the in-step reduce-scattered optimizer) that must PASS
# on the TPU.  A geometry is closed only by a row that measured real
# throughput, whose loss trajectory tracked the single-stage PP=1
# baseline at equal global batch within ~1 float32 ulp (parity_ok;
# the bit-exact oracle lives in tests/test_schedule.py at the tier-1
# dims — at bench dims the schedule.py docstring's compiler-owned
# last ulp applies, and the row records the bit-exact prefix
# explicitly), and whose injected stage fault took
# the supervisor's voted recovery path with exactly one accounted
# step_retry and bit-exact recovered params (accounted); CPU smoke
# rows never close a geometry.  All three names need the full 8-chip
# slice (P*D = 8); the interleaved v2 geometry additionally proves the
# virtual-stage ring wrap at bench scale.
PIPELINE_CONFIGS = ("pp2dp4", "pp4dp2", "pp2dp4v2")


def history_path(path: str) -> str:
    """Where a result file's earlier rows are banked (copied before a
    retried stage's ``>`` redirect truncates the file)."""
    if path.endswith(".jsonl"):
        return path[: -len(".jsonl")] + ".history.jsonl"
    if path.endswith(".json"):
        return path[: -len(".json")] + ".history.jsonl"
    return path


def rows_with_history(path):
    """JSON rows from a result file, prefixed by its banked history twin;
    malformed lines are skipped.  The single reader shared by the resume
    gates and tools/record_bench.py, so they can never disagree about what
    was measured."""
    hist = history_path(path)
    for p in (hist, path) if hist != path else (path,):
        if not os.path.exists(p):
            continue
        for line in open(p):
            line = line.strip()
            if line.startswith("{"):
                try:
                    yield json.loads(line)
                except json.JSONDecodeError:
                    pass


def measured(r: dict) -> bool:
    """Does this row hold a real measurement?  The single criterion shared
    by the resume gates and the recorder: error rows and zero/absent values
    are NOT measurements (they must be retried / reported as failures)."""
    if "error" in r:
        return False
    if "config" in r:
        return (r.get("value") or 0) > 0
    if "t" in r:
        return bool(r.get("flash_ms"))
    if "metric" in r:  # bench.py headline rows (value may be null: a
        # CPU-smoke traffic row that deliberately skipped timing)
        return (r.get("value") or 0) > 0
    if "variant" in r:  # mfu_attribution.py rows
        return r.get("sec_per_step", 0) > 0
    if "strategy" in r:  # collective_bench.py rows
        return r.get("wall_time_s", 0) > 0
    return False


def matrix_missing(d: str) -> list[str]:
    done = set()
    for r in rows_with_history(os.path.join(d, "matrix.jsonl")):
        if r.get("config") in MATRIX_CONFIGS and measured(r):
            # dp_ring rows must have measured the wire schedule the label
            # CURRENTLY means (round-4 advisor: 'ring' flipped
            # bidirectional -> uni, so an unstamped pre-flip row — or a
            # stamped row for the other direction — is evidence for a
            # different algorithm and the rung is still owed a number).
            # "uni" is duplicated from tpudp.parallel.sync.RING_DIRECTION
            # ["ring"] because this helper must stay stdlib-only (no jax
            # import on the watcher's poll path); a test pins the two.
            if r["config"] == "dp_ring" and r.get("ring_direction") != "uni":
                continue
            done.add(r["config"])
    return [c for c in MATRIX_CONFIGS if c not in done]


def flash_missing(d: str) -> list[int]:
    done = set()
    for r in rows_with_history(os.path.join(d, "flash.jsonl")):
        if r.get("t") in FLASH_TS and measured(r):
            done.add(r["t"])
    return [t for t in FLASH_TS if t not in done]


def serve_missing(d: str) -> list[int]:
    """Serving-bench concurrency levels still lacking a real TPU
    measurement (CPU smoke rows — the tier-1 regression run — must not
    satisfy the gate, same rule as mfu_missing).  Returned comma-ready
    so the watcher passes the gaps straight to SERVE_CONCURRENCY and a
    window resumes the sweep mid-way."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve.jsonl")):
        if (r.get("metric") == "serve_tokens_per_sec"
                and r.get("concurrency") in SERVE_CONCURRENCIES
                and measured(r)
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["concurrency"])
    return [c for c in SERVE_CONCURRENCIES if c not in done]


def serve_spec_missing(d: str) -> list[int]:
    """Speculation depths still lacking a real TPU measurement (CPU
    smoke and error rows never close a level — same rules as
    serve_missing).  Comma-ready for SERVE_SPECULATE_K so a window
    resumes the sweep mid-way."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_spec.jsonl")):
        if (r.get("metric") == "serve_spec_tokens_per_sec"
                and r.get("speculate_k") in SERVE_SPEC_KS
                and measured(r)
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["speculate_k"])
    return [k for k in SERVE_SPEC_KS if k not in done]


def serve_prefix_missing(d: str) -> list[str]:
    """Prefix-caching workloads still lacking a real TPU measurement.
    A row closes its workload only when it measured something (a
    positive TTFT speedup), actually exercised the cache
    (``prefix_hit_tokens > 0`` — a run whose lookups all missed proved
    nothing about reuse), and kept bit-exact parity between the cached
    and uncached engines (``parity_ok``).  CPU smoke and error rows
    never close a workload (same rules as serve_missing).  Comma-ready
    for SERVE_PREFIX so a window resumes the sweep mid-way."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_prefix.jsonl")):
        if (r.get("metric") == "serve_prefix"
                and r.get("workload") in SERVE_PREFIX_WORKLOADS
                and measured(r)
                and r.get("prefix_hit_tokens", 0) > 0
                and r.get("parity_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["workload"])
    return [w for w in SERVE_PREFIX_WORKLOADS if w not in done]


def serve_paged_missing(d: str) -> list[str]:
    """Paged-attention workloads still lacking a real TPU measurement.
    A row closes its workload only when it measured something (a
    positive capacity ratio), the paged engine actually held the extra
    contexts (``capacity_ok`` — >= 1.5x the dense engine's co-resident
    contexts at the same KV byte budget with zero page-pressure
    vacates), prefix reuse actually happened through the tables
    (``prefix_hit_tokens > 0``), and greedy outputs stayed
    bit-identical between the paged and dense-copy engines
    (``parity_ok``).  CPU smoke and error rows never close a workload
    (same rules as serve_missing).  Comma-ready for SERVE_PAGED so a
    window resumes the sweep mid-way."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_paged.jsonl")):
        if (r.get("metric") == "serve_paged"
                and r.get("workload") in SERVE_PAGED_WORKLOADS
                and measured(r)
                and r.get("capacity_ok") is True
                and r.get("prefix_hit_tokens", 0) > 0
                and r.get("parity_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["workload"])
    return [w for w in SERVE_PAGED_WORKLOADS if w not in done]


def serve_paged_kernel_missing(d: str) -> list[str]:
    """Gather-free-vs-gather throughput rows still owed (the
    ``serve_paged_kernel`` rows the same ``--paged`` invocation emits
    alongside ``serve_paged``).  A row closes its workload only when it
    measured a real speedup ratio (``value`` > 0), the gather-free
    engine at least matched the gather baseline's tokens/sec with all
    three engines bit-identical (``gather_free_ok``, which folds in
    ``parity_ok``), and the measurement is from the TPU.  Same file,
    same SERVE_PAGED resume contract — one rerun refills both rows."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_paged.jsonl")):
        if (r.get("metric") == "serve_paged_kernel"
                and "traffic" not in r  # traffic rows have their own stage
                and r.get("workload") in SERVE_PAGED_WORKLOADS
                and measured(r)
                and r.get("gather_free_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["workload"])
    return [w for w in SERVE_PAGED_WORKLOADS if w not in done]


def serve_paged_traffic_missing(d: str) -> list[str]:
    """Kernel-vs-einsum traffic rows still owed (the per-traffic
    ``serve_paged_kernel`` rows — ``traffic`` in prefill / verify /
    fused — the same ``--paged`` invocation emits after the gather-free
    row).  A pair closes only when the row measured a real kernel/einsum
    throughput ratio (``value`` > 0; CPU smoke rows never measure one —
    interpret mode times the interpreter, so tokens/sec is only taken on
    a TPU), the kernel at least matched the einsum fallback with all
    three engines bit-identical over fragmented tables (``kernel_ok``,
    which folds in ``parity_ok``), and the row is from the TPU.  Same
    file, same SERVE_PAGED resume contract — one rerun refills every
    row of the workload."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_paged.jsonl")):
        if (r.get("metric") == "serve_paged_kernel"
                and r.get("workload") in SERVE_PAGED_WORKLOADS
                and r.get("traffic") in SERVE_PAGED_TRAFFIC
                and measured(r)
                and r.get("kernel_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add((r["workload"], r["traffic"]))
    return [f"{w}:{t}" for w in SERVE_PAGED_WORKLOADS
            for t in SERVE_PAGED_TRAFFIC if (w, t) not in done]


def serve_fused_missing(d: str) -> list[int]:
    """Fused-decode window sizes still lacking a real TPU measurement.
    A row closes its N only when it measured something (tokens/sec >
    0), kept bit-exact parity with the single-step engine
    (``parity_ok``), and actually amortized the host dispatch
    (``dispatch_ok`` — host-dispatches-per-decoded-token <= 1/N x
    1.25).  CPU smoke and error rows never close an N (same rules as
    serve_missing).  Comma-ready for SERVE_DECODE_FUSE so a window
    resumes the sweep mid-way."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_fused.jsonl")):
        if (r.get("metric") == "serve_fused"
                and r.get("decode_fuse") in SERVE_FUSED_NS
                and measured(r)
                and r.get("parity_ok") is True
                and r.get("dispatch_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["decode_fuse"])
    return [n for n in SERVE_FUSED_NS if n not in done]


def serve_spec_fused_missing(d: str) -> list[str]:
    """On-device fused-speculation configs still lacking a real TPU
    measurement.  A row closes its config only when it measured
    something (tokens/sec > 0), held bit-exact parity against both
    referees (``parity_ok`` — greedy vs host-drafted spec AND plain
    fused; sampled vs host-drafted under the same PRNG chains), and
    passed the full gate (``spec_fused_ok`` — the fused window engaged
    and tokens/sec >= max of both baselines).  CPU smoke and error rows
    never close a config (same rules as serve_missing).  Comma-ready
    for SERVE_SPEC_FUSED so a window resumes the sweep mid-way."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_spec_fused.jsonl")):
        if (r.get("metric") == "serve_spec_fused"
                and r.get("config") in SERVE_SPEC_FUSED_CONFIGS
                and measured(r)
                and r.get("parity_ok") is True
                and r.get("spec_fused_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["config"])
    return [c for c in SERVE_SPEC_FUSED_CONFIGS if c not in done]


def stale_tpu_rows(d: str) -> list[str]:
    """Named ``stale-tpu-row`` gap: result files whose CURRENT artifact
    is a banked last-known-good re-emission rather than a fresh
    measurement.  A re-emitted row is honest (it carries ``source:
    last_known_good``, ``fresh: false`` and ``stale_since`` — the
    capture timestamp it was banked at) but it is still STALE evidence,
    and the watcher must keep treating the stage as owed instead of
    silently re-dating the old number.  Scans the files themselves (not
    the history twins — banked history is supposed to be old)."""
    stale = []
    for fname in ("bench.json", "bench_bf16.json"):
        path = os.path.join(d, fname)
        try:
            with open(path) as f:
                rows = [json.loads(ln) for ln in f if ln.strip()]
        except (OSError, json.JSONDecodeError):
            continue
        if any(r.get("source") == "last_known_good" for r in rows):
            stale.append(f"stale-tpu-row:{fname}")
    return stale


def serve_soak_missing(d: str) -> list[int]:
    """Soak seeds still lacking a PASSING real-TPU run.  A soak row
    closes its seed only when it measured something (``value`` =
    completed requests > 0), the surviving outputs matched generate()
    bit-exactly (``parity_ok``), the engine ended empty (``no_leak``),
    and the canary cadence ran clean — canaries actually fired and ZERO
    quarantines (``canary_ok``, the serving false-positive gate: a
    canary that condemns a healthy engine is as much a bug as one that
    misses corruption) — a soak that wedged, leaked a slot, or diverged
    is a FAILURE to retry, exactly like an error row.  CPU smoke rows
    never close a seed (same rules as serve_missing)."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_soak.jsonl")):
        if (r.get("metric") == "serve_soak"
                and r.get("seed") in SERVE_SOAK_SEEDS
                and measured(r)
                and r.get("parity_ok") is True
                and r.get("no_leak") is True
                and r.get("canary_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["seed"])
    return [s for s in SERVE_SOAK_SEEDS if s not in done]


def serve_disagg_missing(d: str) -> list[int]:
    """Disagg seeds still lacking a PASSING run.  A row closes its seed
    only when it measured something (``value`` = migration us/page > 0
    — pages actually moved), every request prefilled on rank 0 and
    decoded on rank 1 (``split_ok``), outputs matched the colocated
    engine bit-exactly (``parity_ok``), both processes ended empty and
    leak-free (``no_leak``), and the latency gates held
    (``ttft_ok``/``p99_ok``).  No device gate — see
    SERVE_DISAGG_SEEDS; error rows never close a seed."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_disagg.jsonl")):
        if (r.get("metric") == "serve_disagg"
                and r.get("seed") in SERVE_DISAGG_SEEDS
                and measured(r)
                and r.get("split_ok") is True
                and r.get("parity_ok") is True
                and r.get("no_leak") is True
                and r.get("ttft_ok") is True
                and r.get("p99_ok") is True):
            done.add(r["seed"])
    return [s for s in SERVE_DISAGG_SEEDS if s not in done]


def serve_tenancy_missing(d: str) -> list[int]:
    """Tenancy seeds still lacking a PASSING real-TPU run.  A row
    closes its seed only when it measured something (``value`` = the
    high tier's overload p99 TTFT > 0), the high tier's p99 held under
    overload (``p99_ok`` — the SLO the priority/preemption machinery
    exists to defend), every surviving output matched generate()
    bit-exactly (``parity_ok``), and the engine ended empty
    (``no_leak``).  CPU smoke and error rows never close a seed (same
    rules as serve_soak_missing)."""
    done = set()
    for r in rows_with_history(os.path.join(d, "serve_tenancy.jsonl")):
        if (r.get("metric") == "serve_tenancy"
                and r.get("seed") in SERVE_TENANCY_SEEDS
                and measured(r)
                and r.get("p99_ok") is True
                and r.get("parity_ok") is True
                and r.get("no_leak") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["seed"])
    return [s for s in SERVE_TENANCY_SEEDS if s not in done]


def train_soak_missing(d: str) -> list[int]:
    """Kill/resume soak seeds still lacking a PASSING real-TPU run.  A
    row closes its seed only when it measured something (``value`` =
    recoveries > 0 — a soak that recovered nothing proved nothing), the
    final params matched the uninterrupted run bit-exactly
    (``parity_ok``), and every injected fault/kill has a matching typed
    recovery event (``accounted``) — a soak that diverged or lost a
    recovery is a FAILURE to retry, exactly like an error row.  CPU
    smoke rows never close a seed (same rules as serve_soak_missing)."""
    done = set()
    for r in rows_with_history(os.path.join(d, "train_soak.jsonl")):
        if (r.get("metric") == "train_soak"
                and r.get("seed") in TRAIN_SOAK_SEEDS
                and measured(r)
                and r.get("parity_ok") is True
                and r.get("accounted") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["seed"])
    return [s for s in TRAIN_SOAK_SEEDS if s not in done]


def train_pipeline_missing(d: str) -> list[str]:
    """Pipeline-parallel geometries still lacking a PASSING real-TPU
    row.  A row closes its config only when it measured real throughput
    (``value`` > 0), the geometry's loss trajectory tracked the
    single-stage baseline within ~1 float32 ulp (``parity_ok``; the
    row also records its bit-exact leading prefix — see the
    pipeline_bench.py docstring for the scoping), and the injected
    stage fault was recovered through the
    voted rollback path with bit-exact params (``accounted``) — a fast
    row that diverged or lost its recovery is a FAILURE to retry,
    exactly like an error row.  CPU smoke rows never close a config
    (same rules as train_soak_missing)."""
    done = set()
    for r in rows_with_history(os.path.join(d, "train_pipeline.jsonl")):
        if (r.get("metric") == "train_pipeline"
                and r.get("config") in PIPELINE_CONFIGS
                and measured(r)
                and r.get("parity_ok") is True
                and r.get("accounted") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["config"])
    return [c for c in PIPELINE_CONFIGS if c not in done]


def train_soak_multihost_missing(d: str) -> list[int]:
    """Pod-scale soak seeds still lacking a PASSING run.  Same rules as
    train_soak_missing, plus the row must prove the ELASTIC step — the
    multi-host checkpoint actually restored at the reduced geometry
    (``elastic_resumes > 0``); a soak that only ever relaunched at the
    save-time host count proved nothing about shrinking.  No real-TPU
    device gate (see the registry comment): the pod workers run the CPU
    backend by construction, and the protocol the soak certifies is
    platform-independent."""
    done = set()
    for r in rows_with_history(os.path.join(d, "train_soak_multihost.jsonl")):
        if (r.get("metric") == "train_soak_multihost"
                and r.get("seed") in TRAIN_SOAK_MULTIHOST_SEEDS
                and measured(r)
                and r.get("parity_ok") is True
                and r.get("accounted") is True
                and r.get("elastic_resumes", 0) > 0):
            done.add(r["seed"])
    return [s for s in TRAIN_SOAK_MULTIHOST_SEEDS if s not in done]


def sdc_soak_missing(d: str) -> list[int]:
    """SDC soak seeds still lacking a PASSING real-TPU run.  A row
    closes its seed only when it measured something (``value`` =
    detections > 0 — a soak that detected nothing proved nothing),
    the clean fit raised zero detections (``clean_ok`` — the
    false-positive gate), the one-shot flip was detected, localized to
    the injected replica, and graded transient with the persistent
    flip quarantined (``accounted``/``quarantine_ok``), and the
    repaired params matched the clean run bit-exactly (``parity_ok``).
    CPU smoke rows never close a seed (same rules as
    train_soak_missing)."""
    done = set()
    for r in rows_with_history(os.path.join(d, "sdc_soak.jsonl")):
        if (r.get("metric") == "sdc_soak"
                and r.get("seed") in SDC_SOAK_SEEDS
                and measured(r)
                and r.get("clean_ok") is True
                and r.get("parity_ok") is True
                and r.get("accounted") is True
                and r.get("quarantine_ok") is True
                and "TPU" in str(r.get("device_kind", ""))):
            done.add(r["seed"])
    return [s for s in SDC_SOAK_SEEDS if s not in done]


def tier1_headroom_missing(d: str) -> list[str]:
    """``tier1-headroom`` when the LAST recorded tier-1 run burned past
    TIER1_WARN_S of the TIER1_BUDGET_S ceiling.  The record is
    ``<dir>/tier1.log`` — a tee of the tier-1 pytest run (ROADMAP.md's
    command) — parsed for pytest's final summary line (``... passed
    ... in 812.34s``); only the LAST summary counts (a log may hold
    several runs).  No log or no summary line is NOT a gap: headroom
    tracking is advisory until a run is recorded, and absence must not
    block TPU stages that never run the suite."""
    import re

    try:
        with open(os.path.join(d, "tier1.log"), errors="replace") as f:
            text = f.read()
    except OSError:
        return []
    took = None
    for m in re.finditer(r"\bpassed\b[^\n]*?\bin (\d+(?:\.\d+)?)s\b", text):
        took = float(m.group(1))
    if took is not None and took > TIER1_WARN_S:
        return ["tier1-headroom"]
    return []


def epoch_missing(d: str) -> bool:
    return not any(
        r.get("metric") == "vgg11_epoch_images_per_sec" and measured(r)
        for r in rows_with_history(os.path.join(d, "epoch.json")))


MFU_VARIANTS = ("full", "fwd_bwd", "fwd_only", "no_bn", "bf16_params")


def mfu_missing(d: str) -> list[str]:
    """Ablation variants that still lack a real TPU measurement (a
    CPU-smoke row must not satisfy the gate).  Returned as a list the
    watcher passes straight to ``MFU_VARIANTS`` so a window resumes the
    sweep mid-way instead of restarting it (round-5 micro battery:
    the first window runs only ``full,bf16_params``; the remaining
    ablations are exactly this gap).  bf16_params may legitimately fail
    (the bench emits an error row and continues), so for it an attempt of
    any outcome suffices."""
    rows = list(rows_with_history(os.path.join(d, "mfu.jsonl")))
    have = {r["variant"] for r in rows
            if r.get("variant") and measured(r)
            and "TPU" in str(r.get("device_kind", ""))}
    # "Attempted" also excludes smoke rows: a measured row carrying a
    # non-TPU device_kind must not satisfy the gate; error rows carry no
    # device_kind (the watcher only ever runs this stage on the TPU) and
    # count as attempts.
    attempted = {r["variant"] for r in rows
                 if r.get("variant")
                 and ("device_kind" not in r
                      or "TPU" in str(r.get("device_kind", "")))}
    return [v for v in MFU_VARIANTS
            if (v not in attempted if v == "bf16_params" else v not in have)]


def lever_missing(d: str) -> bool:
    """Is the bf16-params lever capture still owed?  (VERDICT r4 #2:
    "act on the MFU data in-round".)

    Owed exactly when the attribution sweep has PROVEN the lever wins on
    the real chip (a measured TPU ``bf16_params`` row with
    ``speedup_vs_full >= 1.03``) and no fresh TPU headline row with
    ``param_dtype == "bfloat16"`` exists yet.  A measured speedup below
    the threshold closes the stage with nothing to do — the ablation row
    itself is then the documented "why the headline stays fp32-params".
    """
    speedup_proven = any(
        r.get("variant") == "bf16_params" and measured(r)
        and "TPU" in str(r.get("device_kind", ""))
        and (r.get("speedup_vs_full") or 0) >= 1.03
        for r in rows_with_history(os.path.join(d, "mfu.jsonl")))
    if not speedup_proven:
        return False
    # bench.py banks every fresh headline into bench.history.jsonl
    # regardless of where stdout was redirected, so look in both the
    # lever stage's own file and the shared headline history.
    rows = list(rows_with_history(os.path.join(d, "bench_bf16.json")))
    rows += list(rows_with_history(os.path.join(d, "bench.json")))
    return not any(
        r.get("metric") == "vgg11_cifar10_images_per_sec_per_chip"
        and measured(r) and r.get("source") != "last_known_good"
        and "TPU" in str(r.get("device_kind", ""))
        and r.get("param_dtype") == "bfloat16"
        for r in rows)


def collective_missing(d: str) -> bool:
    """Ring-vs-psum head-to-head (VERDICT r3 #5: back the ring default
    with a number).  Complete once the three key schedules each hold a
    real multi-device TPU measurement (simulated CPU-mesh sweeps never
    satisfy the gate, same rule as mfu_missing) — or once collective_bench
    has recorded its labeled single-device skip row AND the most recent
    healthy probe still saw a 1-device slice (on 1 chip every collective
    compiles to a no-op; the HLO evidence in BASELINE.md is the backing
    instead).  A probe that sees a multi-chip slice re-opens the stage:
    the skip row must not mask the measurement it exists to schedule."""
    rows = list(rows_with_history(os.path.join(d, "collective.jsonl")))
    # 'ring' rows must carry the post-flip "uni" stamp (round-4 advisor:
    # a pre-flip row measured the bidirectional schedule — the hazard the
    # stage exists to disambiguate).  Same stdlib-only duplication of
    # sync.RING_DIRECTION["ring"] as matrix_missing; test-pinned.
    have = {r.get("strategy") for r in rows
            if measured(r) and r.get("devices", 0) > 1
            and "TPU" in str(r.get("device_kind", ""))
            and (r.get("strategy") != "ring"
                 or r.get("ring_direction") == "uni")}
    if {"allreduce", "ring", "ring_bidir"} <= have:
        return False
    try:
        with open(os.path.join(d, "probe.json")) as f:
            probed_devices = json.load(f).get("devices")
    except (OSError, json.JSONDecodeError):
        probed_devices = None
    if probed_devices is not None and probed_devices > 1:
        return True
    return not any(r.get("skipped") and r.get("devices") == 1 for r in rows)


def _load_analysis():
    """tpudp/analysis as a standalone package (no `tpudp` import, so no
    jax): spec_from_file_location with submodule_search_locations makes
    the package's own relative imports work."""
    if "_tpudp_analysis" in sys.modules:
        return sys.modules["_tpudp_analysis"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkgdir = os.path.join(root, "tpudp", "analysis")
    spec = importlib.util.spec_from_file_location(
        "_tpudp_analysis", os.path.join(pkgdir, "__init__.py"),
        submodule_search_locations=[pkgdir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_tpudp_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


ANALYSIS_LINT_PATHS = ("tpudp", "tools", "benchmarks")

#: Serve-bench result files whose rows must ship with the tpudp.obs
#: metrics sidecar (serve_bench_metrics.json — per-stage
#: Engine.metrics() snapshots: device counters, span rollups, stats).
OBS_SIDECAR_STAGES = ("serve.jsonl", "serve_spec.jsonl",
                      "serve_fused.jsonl", "serve_spec_fused.jsonl",
                      "serve_prefix.jsonl", "serve_paged.jsonl")
OBS_SIDECAR_NAME = "serve_bench_metrics.json"


def obs_missing(d: str) -> list[str]:
    """Is the serve bench's metrics sidecar still owed?  Owed exactly
    when some serve stage has banked MEASURED rows (telemetry must ship
    with the numbers it explains) but no ``serve_bench_metrics.json``
    exists in the results dir — a bench run that emitted rows without
    the sidecar regressed the obs exposition contract.  Nothing
    measured yet = nothing owed (the sidecar is written by the same
    process that writes the rows)."""
    has_rows = any(
        measured(r)
        for f in OBS_SIDECAR_STAGES
        for r in rows_with_history(os.path.join(d, f)))
    if not has_rows:
        return []
    return [] if os.path.exists(os.path.join(d, OBS_SIDECAR_NAME)) \
        else ["sidecar"]


def analysis_missing(root: str | None = None) -> list[str]:
    """Correctness gates still owed on the current TREE: ``lint`` when
    `python -m tpudp.analysis lint` would fail (unsuppressed findings),
    ``audit`` when tools/trace_lock.json no longer matches the pinned
    hot-path sources (an edit landed without `audit --update`; the full
    jaxpr re-trace is the tier-1 test's job — this is the cheap stdlib
    staleness proxy for the poll path), ``protocol`` when the
    cross-host protocol verifier has unsuppressed findings over the
    multihost modules (stdlib, same file-path load), and ``budget``
    when the lockfile lacks a resource ledger or capture geometry for
    any pinned program (the jaxpr re-derivation is the tier-1 test's
    job — this checks the committed artifact)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mod = _load_analysis()
    audit = importlib.import_module("_tpudp_analysis.audit")
    protocol = importlib.import_module("_tpudp_analysis.protocol")
    gaps = []
    # a configured path that vanished must NOT read as "clean" — the
    # CLI exits 2 on exactly this ('no such path'), and the poll gate
    # must agree with it
    missing = [p for p in ANALYSIS_LINT_PATHS
               if not os.path.exists(os.path.join(root, p))]
    findings, errors = mod.lint_paths(
        [p for p in ANALYSIS_LINT_PATHS if p not in missing], root)
    if findings or errors or missing:
        gaps.append("lint")
    if audit.sources_stale(os.path.join(root, "tools", "trace_lock.json"),
                           root):
        gaps.append("audit")
    pfindings, perrors = protocol.verify_paths(
        ["tpudp"] if os.path.exists(os.path.join(root, "tpudp")) else [],
        root)
    if pfindings or perrors or not os.path.exists(
            os.path.join(root, "tpudp")):
        gaps.append("protocol")
    budget = importlib.import_module("_tpudp_analysis.budget")
    try:
        with open(os.path.join(root, "tools", "trace_lock.json")) as f:
            lock = json.load(f)
        budget_ok = budget.lock_has_ledgers(lock)
    except (OSError, json.JSONDecodeError):
        budget_ok = False
    if not budget_ok:
        gaps.append("budget")
    return gaps


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("stage", choices=["matrix", "flash", "epoch", "mfu",
                                     "collective", "lever", "serve",
                                     "serve_spec", "serve_fused",
                                     "serve_spec_fused",
                                     "serve_soak", "serve_disagg",
                                     "serve_prefix",
                                     "serve_paged", "serve_paged_kernel",
                                     "serve_paged_traffic",
                                     "serve_tenancy",
                                     "train_soak",
                                     "train_soak_multihost",
                                     "sdc_soak", "tier1_headroom",
                                     "train_pipeline", "analysis",
                                     "obs", "stale"])
    p.add_argument("--dir", default="bench_results")
    args = p.parse_args()
    if args.stage == "matrix":
        print(",".join(matrix_missing(args.dir)), end="")
    elif args.stage == "epoch":
        print("epoch" if epoch_missing(args.dir) else "", end="")
    elif args.stage == "mfu":
        print(",".join(mfu_missing(args.dir)), end="")
    elif args.stage == "serve":
        print(",".join(str(c) for c in serve_missing(args.dir)), end="")
    elif args.stage == "serve_spec":
        print(",".join(str(k) for k in serve_spec_missing(args.dir)),
              end="")
    elif args.stage == "serve_fused":
        print(",".join(str(n) for n in serve_fused_missing(args.dir)),
              end="")
    elif args.stage == "serve_spec_fused":
        print(",".join(serve_spec_fused_missing(args.dir)), end="")
    elif args.stage == "stale":
        print(",".join(stale_tpu_rows(args.dir)), end="")
    elif args.stage == "serve_soak":
        print(",".join(str(s) for s in serve_soak_missing(args.dir)),
              end="")
    elif args.stage == "serve_tenancy":
        print(",".join(str(s) for s in serve_tenancy_missing(args.dir)),
              end="")
    elif args.stage == "serve_disagg":
        print(",".join(str(s) for s in serve_disagg_missing(args.dir)),
              end="")
    elif args.stage == "train_soak":
        print(",".join(str(s) for s in train_soak_missing(args.dir)),
              end="")
    elif args.stage == "train_soak_multihost":
        print(",".join(str(s)
                       for s in train_soak_multihost_missing(args.dir)),
              end="")
    elif args.stage == "sdc_soak":
        print(",".join(str(s) for s in sdc_soak_missing(args.dir)),
              end="")
    elif args.stage == "tier1_headroom":
        print(",".join(tier1_headroom_missing(args.dir)), end="")
    elif args.stage == "train_pipeline":
        print(",".join(train_pipeline_missing(args.dir)), end="")
    elif args.stage == "serve_prefix":
        print(",".join(serve_prefix_missing(args.dir)), end="")
    elif args.stage == "serve_paged":
        print(",".join(serve_paged_missing(args.dir)), end="")
    elif args.stage == "serve_paged_kernel":
        print(",".join(serve_paged_kernel_missing(args.dir)), end="")
    elif args.stage == "serve_paged_traffic":
        print(",".join(serve_paged_traffic_missing(args.dir)), end="")
    elif args.stage == "analysis":
        print(",".join(analysis_missing()), end="")
    elif args.stage == "obs":
        print(",".join(obs_missing(args.dir)), end="")
    elif args.stage == "collective":
        print("collective" if collective_missing(args.dir) else "", end="")
    elif args.stage == "lever":
        print("bf16_params" if lever_missing(args.dir) else "", end="")
    else:
        print(" ".join(str(t) for t in flash_missing(args.dir)), end="")


if __name__ == "__main__":
    main()
