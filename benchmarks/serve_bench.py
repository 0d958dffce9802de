"""Serving throughput/latency: continuous batching vs sequential decode,
and speculative decoding vs the plain engine.

The tpudp.serve engine multiplexes many generation requests through one
jitted fixed-shape decode step (slot KV arena + chunked prefill); this
bench quantifies what that buys over the one-request-at-a-time
``generate()`` baseline the repo previously offered.  Workload: N
requests with a shared small-GPT-2 config arriving as a POISSON process
(exponential inter-arrival times at an offered load of ``SERVE_LOAD``
times the sequential service rate per slot — saturating by default, so
the number measures the engine, not the arrival gaps), swept over
several concurrency levels (``num_slots``).

One JSON line per concurrency level (machine-readable) plus a final
summary line:

  value                 aggregate NEW tokens/sec, first submit -> last token
  p50/p99_token_latency_ms   per-token latency (submit->first token, then
                        inter-token gaps — the streaming user experience)
  ttft_p50/p99_ms       time to FIRST token per request (submit -> first
                        emission: queueing + prefill + first sample)
  mean_slot_occupancy   active slots / num_slots per decode step
  speedup_vs_sequential value / the sequential generate() baseline

With ``--speculate-k K1,K2`` (or SERVE_SPECULATE_K) the bench instead
emits one ``serve_spec_tokens_per_sec`` row per k: the speculative
engine (n-gram prompt-lookup drafting, ``tpudp.serve.speculate``) vs a
non-speculative engine on the IDENTICAL repetitive greedy workload, at
``SERVE_SPEC_CONCURRENCY`` (default 1 — speculation is the LOW-occupancy
latency lever; at high occupancy the batch already amortizes the weight
read).  The workload is the deterministic speculation ceiling (see
``run_spec``): same forwards, same weight streaming, acceptance ~1; the
measured acceptance_rate column is what scales the row to real
workloads.

Greedy decode, so every emitted token is bit-identical to what the
sequential baseline produces for the same request (pinned by
tests/test_serve.py and tests/test_speculate.py) — all columns measure
the SAME work.

With ``--decode-fuse N1,N2`` (or SERVE_DECODE_FUSE) the bench instead
emits one ``serve_fused`` row per window size N: the SAME greedy
pure-decode workload through an ``Engine(decode_fuse=N)`` — whose
scheduler dispatches ONE ``lax.while_loop`` program running up to N
decode steps on device per host round trip — and through the
single-step engine.  Each row reports host-dispatches-per-decoded-token
for both (the fused engine's must land within ``1/N x (1 + eps)`` —
``dispatch_ok``, the gate the resume machinery keys on), tokens/sec
for both with the headline ``value`` = fused tokens/sec, and the
in-bench ``parity_ok`` (fused outputs bit-identical to single-step).
``N=1`` is the single-step control row.  The workload defaults to one
in-flight request (SERVE_FUSED_CONCURRENCY) — dispatch overhead per
token is largest at the smallest batch, the regime the fused loop
exists for (ROADMAP "On-device decode loop").

With ``--queue-limit N`` (or SERVE_QUEUE_LIMIT) the sweep also exercises
the robustness layer's bounded admission: submits past the limit are
shed with a typed ``QueueFull`` (counted per row in ``shed``) instead of
growing the host queue, and the throughput/latency columns then measure
only the ADMITTED work — the overload story is "p99 TTFT of survivors
stays bounded while sheds absorb the burst".  Every row also carries
``shed``/``deadline_expired`` counters (0 when the knobs are off);
SERVE_DEADLINE_S / SERVE_TTFT_DEADLINE_S attach per-request budgets.

With ``--prefix-cache W1,W2`` (or SERVE_PREFIX) the bench instead emits
one ``serve_prefix`` row per workload, measuring what the block-pool +
radix-tree prefix cache (``Engine(prefix_cache_blocks=N)``,
``tpudp.serve.prefix_cache``) buys on the traffic it exists for:
``shared_prefix`` (every request carries the same long system prompt
plus a short unique tail — the "millions of users behind one system
prompt" shape) and ``multiturn`` (conversations that re-send their whole
history each turn).  Each row runs the IDENTICAL greedy workload through
a cache-off and a cache-on engine (greedy outputs are bit-identical
either way — ``parity_ok`` records the bench's own check) and reports
TTFT p50/p99 for both, the hit-token counts
(``stats["prefix_hit_tokens"]``/``["prefix_lookups"]``), and the
headline ``value`` = uncached/cached TTFT p50 ratio.

With ``--paged shared_prefix`` (or SERVE_PAGED) the bench instead emits
one ``serve_paged`` row per workload: the TRUE paged engine
(``Engine(kv_pages=N)`` — per-slot block tables into one shared page
pool, cache hits as table writes with copy-on-write at the divergence
block) vs the dense copy-cache engine at the SAME KV byte budget.
The row reports the peak co-resident contexts each engine sustained
(headline ``value`` = their ratio, gated >= 1.5x with zero
page-pressure vacates — ``capacity_ok``), TTFT p50/p99 for both, the
paged engine's table-hit accounting, and the in-bench greedy
``parity_ok``.  The same invocation ALSO emits one
``serve_paged_kernel`` row per workload: decode tokens/sec through the
dense engine, the gather-based paged engine
(``Engine(paged_attn='gather')`` — PR 13's gather→dense→scatter
baseline), and the gather-free default (K/V read through the block
table inside the attention contraction, single-token page commits) at
the SAME pool bytes, gated on ``gather_free_ok`` — gather-free
tokens/sec >= gather-paged AND all three engines' greedy outputs
bit-identical (``parity_ok``).  ``SERVE_PAGED_KERNEL_TPS=1``
additionally times the Pallas-kernel engine
(``Engine(paged_attn='kernel')``; off by default — interpret mode on a
CPU host is not a meaningful number, and the gate never depends on
it).

With ``--soak SEED1,SEED2`` (or SERVE_SOAK) the bench instead runs the
fault-injection SOAK harness (one ``serve_soak`` row per seed): a
deterministic per-seed mix of random cancels, impossible and tight
deadlines, queue-limit sheds, a drafter that dies mid-run, injected
device-step faults, and a PREEMPTION STORM — scheduled high-priority
bursts (``tpudp.serve.faults.PreemptionStorm``) that evict low-tier
in-flight slots through the tenancy layer's carry-over path — against a
small tenant-aware engine.  A seed PASSES only if the run never wedges
(bounded step count), the engine ends empty (``no_leak`` — no slot or
queue entry stranded), and every surviving completed request's greedy
output is bit-identical to ``generate()`` (``parity_ok``).

With ``--tenants SEED1,SEED2`` (or SERVE_TENANCY) the bench instead
runs the MULTI-TENANT mixed workload (one ``serve_tenancy`` row per
seed): a small engine with a high-priority tier over two equal-priority
weighted tiers (3:1).  Phase A measures the high tier's TTFT p99 with
no other load; phase B saturates the low tiers well past capacity
(their per-class queue_limits shed the excess) while the same high-tier
arrival pattern rides on top, preempting low slots.  The row records
per-tier TTFT and token-latency percentiles, measured fairness shares
vs the configured weights, shed/preemption counts, and three gates the
resume machinery keys on: ``p99_ok`` (high-tier overload TTFT p99 <=
baseline p99 x TENANCY_P99_BOUND — the SLO priority scheduling exists
to defend), ``parity_ok`` (every completed request, preempted or not,
bit-identical to ``generate()``), and ``no_leak``.

With ``--disagg SEED1,SEED2`` (or SERVE_DISAGG) the bench instead runs
the DISAGGREGATED serving stage (one ``serve_disagg`` row per seed):
two OS processes — rank 0 the prefill host, rank 1 the decode host —
rendezvous over ``jax.distributed`` and drive the real
:class:`tpudp.serve.disagg.DisaggHost` four-phase handshake, while the
SAME deterministic per-seed workload (Poisson arrivals in the
``default`` tenant class plus a same-instant ``urgent`` burst that
preempts) also runs through one colocated engine for the baseline.
Every request must prefill on rank 0 and decode on rank 1
(``split_ok``), with outputs bit-identical to the colocated run —
greedy and sampled (``parity_ok``), both processes ending empty with
leak-free pools (``no_leak``), TTFT p99 and decode-gap p99 within
DISAGG_TTFT_BOUND / DISAGG_P99_BOUND x the colocated percentiles
(``ttft_ok`` / ``p99_ok``), and the headline ``value`` = the migration
cost, transfer-span microseconds per adopted page.  Like the
train_soak_multihost stage there is no real-TPU device gate: the two
ranks are co-located CPU processes by construction (two processes
cannot share one host's libtpu), and what the row certifies — the
handoff protocol and its cost — is platform-independent.  The soak
stage (``--soak``) additionally replays each seed's workload through a
3-host in-process ``DisaggCluster`` under the four WIRE fault
injectors (dropped / corrupt / slow / sender-killed-mid-offer): no
wedge, no page leak, bit-exact survivor parity, folded into the soak
row's gates.

Runs on whatever device is attached; SERVE_PLATFORM=cpu pins the CPU
smoke mode (tier-1 runs it at a trimmed geometry).  Knobs: SERVE_CONCURRENCY
(comma-separated levels; default the sweep below), SERVE_SPECULATE_K
(same, for the spec rows),
SERVE_SOAK (same, for the soak rows),
SERVE_DECODE_FUSE (same, for the fused-decode rows),
SERVE_FUSED_CONCURRENCY,
SERVE_PREFIX (same, for the prefix rows), SERVE_SPEC_CONCURRENCY,
SERVE_REQUESTS, SERVE_PROMPT_LEN, SERVE_MAX_NEW, SERVE_LAYERS,
SERVE_DMODEL, SERVE_VOCAB, SERVE_CHUNK, SERVE_LOAD, SERVE_SEED,
SERVE_QUEUE_LIMIT, SERVE_DEADLINE_S, SERVE_TTFT_DEADLINE_S,
SERVE_PREFIX_BLOCKS, SERVE_PREFIX_LEN, SERVE_PREFIX_CONCURRENCY,
SERVE_PREFIX_USERS, SERVE_PREFIX_TURNS,
SOAK_REQUESTS, SOAK_LAYERS, SOAK_DMODEL, SOAK_VOCAB,
SERVE_TENANCY (seed subset), TENANCY_STEPS, TENANCY_HIGH, TENANCY_QL,
TENANCY_P99_BOUND, TENANCY_LAYERS, TENANCY_DMODEL, TENANCY_VOCAB,
SERVE_DISAGG (seed subset), DISAGG_REQUESTS, DISAGG_BURST,
DISAGG_MAX_NEW, DISAGG_MEAN_GAP_S, DISAGG_LAYERS, DISAGG_DMODEL,
DISAGG_VOCAB, DISAGG_TTFT_BOUND, DISAGG_P99_BOUND,
SERVE_STRICT_LEVELS=1 (reject levels/seeds outside the default sweeps).
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The default sweep of each mode.  Workload and config NAMES outside
# their tuple are always refused (a typo); numeric levels and seeds
# outside theirs only under SERVE_STRICT_LEVELS=1.
SERVE_CONCURRENCIES = (1, 4, 8)          # num_slots of the baseline sweep
SERVE_SPEC_KS = (2, 4, 8)                # --speculate-k
SERVE_PREFIX_WORKLOADS = ("shared_prefix", "multiturn")   # --prefix-cache
SERVE_PAGED_WORKLOADS = ("shared_prefix",)                # --paged
# --paged's kernel-vs-einsum rows, one per traffic kind: chunked prompt
# ingestion, k=2 host speculation (the verify window), 4-token fused
# decode windows.
SERVE_PAGED_TRAFFIC = ("prefill", "verify", "fused")
SERVE_FUSED_NS = (1, 4, 8)               # --decode-fuse; N=1 is the control
SERVE_SPEC_FUSED_CONFIGS = ("k2n4", "k4n8")   # --spec-fused
SPEC_FUSED_NAME = re.compile(r"k(\d+)n(\d+)")   # what a config name must be
SERVE_SOAK_SEEDS = (0, 1, 2)             # --soak
SERVE_TENANCY_SEEDS = (0, 1, 2)          # --tenants
SERVE_DISAGG_SEEDS = (0, 1, 2)           # --disagg

METRIC = "serve_tokens_per_sec"
DISAGG_METRIC = "serve_disagg"
SPEC_METRIC = "serve_spec_tokens_per_sec"
SOAK_METRIC = "serve_soak"
PREFIX_METRIC = "serve_prefix"
PAGED_METRIC = "serve_paged"
PAGED_KERNEL_METRIC = "serve_paged_kernel"
TENANCY_METRIC = "serve_tenancy"
FUSED_METRIC = "serve_fused"
SPEC_FUSED_METRIC = "serve_spec_fused"

#: The serve_paged capacity gate: the paged engine must sustain at
#: least this many times the dense engine's co-resident contexts at
#: the same KV byte budget (with zero page-pressure vacates) for the
#: row to count (ISSUE 13 acceptance bar).
PAGED_CAPACITY_BOUND = 1.5

#: Slack on the fused dispatch gate: staggered prefill completions pay
#: a few single-step decodes before the first window, so the measured
#: host-dispatches-per-decoded-token sits slightly above the ideal 1/N.
FUSED_DISPATCH_EPS = 0.25


def _percentile(xs, q):
    xs = sorted(xs)
    if not xs:
        return None
    i = min(int(round(q / 100.0 * (len(xs) - 1))), len(xs) - 1)
    return xs[i]


def _parse_levels(value):
    return [int(x) for x in value.split(",") if x]


def _disagg_workload(seed: int) -> list[dict]:
    """Deterministic per-seed arrival plan shared by the colocated
    baseline worker and the two disagg ranks (all three reconstruct it
    from the seed, so no workload bytes cross the process boundary):
    Poisson inter-arrivals in the ``default`` tenant class, alternating
    greedy and sampled, plus a same-instant ``urgent`` BURST landing at
    the median arrival — the burst preempts default slots through the
    tenancy layer, so the handoff path is exercised under admission
    churn, not a quiet queue."""
    import numpy as np

    n = int(os.environ.get("DISAGG_REQUESTS", 6))
    burst = int(os.environ.get("DISAGG_BURST", 3))
    max_new = int(os.environ.get("DISAGG_MAX_NEW", 8))
    vocab = int(os.environ.get("DISAGG_VOCAB", 128))
    mean_gap = float(os.environ.get("DISAGG_MEAN_GAP_S", 0.02))
    rng = np.random.default_rng(77_000 + seed)
    gaps = rng.exponential(mean_gap, size=n)
    offsets = np.cumsum(gaps) - gaps[0]
    jobs = []
    for i in range(n):
        kw = {} if i % 2 == 0 else dict(temperature=0.8, top_k=7,
                                        seed=100 + seed + i)
        jobs.append(dict(
            offset=float(offsets[i]), tenant="default",
            prompt=rng.integers(0, vocab, size=8 + 2 * (i % 3))
            .astype(np.int32),
            max_new=max_new - (i % 3), kw=kw))
    burst_at = float(offsets[n // 2])
    for _ in range(burst):
        jobs.append(dict(
            offset=burst_at, tenant="urgent",
            prompt=rng.integers(0, vocab, size=8).astype(np.int32),
            max_new=max_new, kw={}))
    jobs.sort(key=lambda j: j["offset"])
    return jobs


def _disagg_build(seed: int):
    """(model, params, engine) at the disagg smoke geometry — tiny like
    the soak's (the stage measures the HANDOFF, not FLOPs), tenant-aware
    (the burst needs a priority tier to preempt through), paged (the
    transfer ships pages)."""
    import jax
    import jax.numpy as jnp

    from tpudp.models.gpt2 import GPT2, GPT2Config
    from tpudp.serve import Engine, TenantClass

    cfg = GPT2Config(
        vocab_size=int(os.environ.get("DISAGG_VOCAB", 128)),
        max_seq_len=64,
        num_layers=int(os.environ.get("DISAGG_LAYERS", 2)),
        num_heads=2,
        d_model=int(os.environ.get("DISAGG_DMODEL", 64)))
    model = GPT2(cfg)
    # Same seed, same platform -> bit-identical params on every rank
    # and in the colocated baseline, no weight broadcast needed.
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = Engine(model, params, num_slots=4, max_len=32,
                 prefill_chunk=8, kv_pages=24,
                 tenants={"default": TenantClass(priority=0),
                          "urgent": TenantClass(priority=1)})
    return model, params, eng


def _disagg_worker_main(spec: str) -> None:
    """Subprocess body for the serve_disagg stage (not a bench row
    emitter itself — it writes one JSON result file the parent joins).
    ``spec`` is ``mode:nproc:port:out_path:seed`` where mode is ``c``
    (colocated baseline, no distributed init) or a rank digit.  Always
    CPU: two processes cannot share one host's libtpu, and the protocol
    the stage certifies is platform-independent."""
    mode, nproc, port, out_path, seed = spec.split(":", 4)
    seed = int(seed)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jobs = _disagg_workload(seed)
    result: dict = {"mode": mode, "seed": seed}

    def _submit_due(eng, handles, nxt, start):
        now = time.perf_counter() - start
        while nxt < len(jobs) and now >= jobs[nxt]["offset"]:
            j = jobs[nxt]
            handles[nxt] = eng.submit(j["prompt"], j["max_new"],
                                      tenant=j["tenant"], **j["kw"])
            nxt += 1
        return nxt

    import numpy as np

    warm_prompt = np.zeros(8, np.int32)
    if mode == "c":
        _model, _params, eng = _disagg_build(seed)
        # Warmup off the clock: compile prefill/decode/sample before the
        # timed arrivals (the disagg ranks warm up symmetrically, so the
        # latency ratio the parent gates on compares compiled-vs-
        # compiled, not compile luck).
        wh = eng.submit(warm_prompt, 6, tenant="default")
        while not wh.done:
            eng.step()
        handles: list = [None] * len(jobs)
        nxt = 0
        start = time.perf_counter()
        while nxt < len(jobs) or eng.slots_in_use or eng.queue_depth:
            nxt = _submit_due(eng, handles, nxt, start)
            eng.step()
        eng.check_paged()
        result.update(
            tokens={str(i): list(h.tokens)
                    for i, h in enumerate(handles)},
            ttfts=[h.token_times[0] - h.submit_time for h in handles
                   if h.token_times],
            gaps=[b - a for h in handles
                  for a, b in zip(h.token_times, h.token_times[1:])],
            no_leak=(eng.slots_in_use == 0 and eng.queue_depth == 0),
            stats={k: int(v) for k, v in eng.stats.items()})
    else:
        rank = int(mode)
        from tpudp.mesh import initialize_distributed

        initialize_distributed("127.0.0.1", int(nproc), rank,
                               port=int(port))
        from tpudp.serve.disagg import DisaggHost

        _model, _params, eng = _disagg_build(seed)
        host = DisaggHost(eng, rank=rank, n_hosts=int(nproc),
                          role=("prefill" if rank == 0 else "decode"),
                          retries=2)
        admitted: list = []   # (sender rid, tokens carried at admit, req)
        host.on_admit = lambda src, t, r: admitted.append(
            (t.rid, len(r.tokens), r))
        # Warmup off the clock: one dummy request travels the WHOLE
        # handoff (prefill on rank 0, pages over the wire, decode on
        # rank 1), compiling both engines' programs AND the handshake
        # collectives at a representative blob width before the timed
        # workload.  Its stats/spans are snapshotted out below.
        wwh = (eng.submit(warm_prompt, 6, tenant="default")
               if rank == 0 else None)
        wstaged = False
        for _ in range(200):
            eng.step()
            if (rank == 0 and not wstaged and wwh.tokens
                    and not wwh.done and wwh._nfill == wwh._fill.size
                    and wwh._slot is not None):
                host.stage(1, wwh)
                wstaged = True
            w_done = (eng.slots_in_use == 0 and eng.queue_depth == 0
                      and host.pending == 0
                      and (rank != 0 or wstaged))
            if os.environ.get("DISAGG_DEBUG"):
                print(f"[warm r{rank}] slots={eng.slots_in_use} "
                      f"q={eng.queue_depth} pend={host.pending} "
                      f"staged={wstaged} done={w_done} "
                      f"toks={wwh.tokens if wwh else None} "
                      f"wdone={wwh.done if wwh else None}",
                      file=sys.stderr, flush=True)
            if host.round(done=w_done):
                break
        else:
            raise RuntimeError("disagg warmup never completed")
        base_stats = dict(eng.stats)
        base_spans = {k: dict(v)
                      for k, v in eng.metrics()["spans"].items()}
        admitted.clear()
        handles = [None] * len(jobs)
        staged: set = set()
        nxt = 0
        # Handshake cadence: a full round costs a handful of host-wide
        # collectives, so running one EVERY engine step taxes each
        # decode token with round latency.  Both ranks key the cadence
        # off the same iteration counter (their loops advance in
        # lockstep between rounds), so the collective sequence stays
        # host-uniform — the property the protocol verifier proves.
        round_every = int(os.environ.get("DISAGG_ROUND_EVERY", 4))
        start = time.perf_counter()
        for it in range(5000):
            if rank == 0:
                nxt = _submit_due(eng, handles, nxt, start)
            eng.step()
            if rank == 0:
                for h in handles:
                    if (h is not None and h.id not in staged
                            and h.tokens and not h.done
                            and h._nfill == h._fill.size
                            and h._slot is not None):
                        host.stage(1, h)
                        staged.add(h.id)
            if (it + 1) % round_every:
                continue
            my_done = (eng.slots_in_use == 0 and eng.queue_depth == 0
                       and host.pending == 0
                       and (rank != 0 or (nxt == len(jobs)
                                          and len(staged) == len(jobs))))
            if host.round(done=my_done):
                break
        else:
            raise RuntimeError("disagg round loop never reached "
                               "joint done")
        eng.check_paged()
        # Report the timed workload's deltas, not the warmup's: the
        # headline us/page divides the transfer span by migrated pages,
        # and the warmup transfer carries the one-off compile cost.
        spans = {}
        for k, v in eng.metrics()["spans"].items():
            b = base_spans.get(k, {})
            spans[k] = {
                "count": int(v["count"]) - int(b.get("count", 0)),
                "total_s": float(v["total_s"])
                - float(b.get("total_s", 0.0))}
        result.update(
            no_leak=(eng.slots_in_use == 0 and eng.queue_depth == 0
                     and host.pending == 0),
            stats={k: int(v) - int(base_stats.get(k, 0))
                   for k, v in eng.stats.items()},
            spans=spans)
        if rank == 0:
            result.update(
                ttfts=[h.token_times[0] - h.submit_time for h in handles
                       if h is not None and h.token_times],
                rid_map={str(i): h.id for i, h in enumerate(handles)
                         if h is not None},
                staged=len(staged), n_jobs=len(jobs))
        else:
            toks, gaps = {}, []
            for rid, carried, r in admitted:
                toks[str(rid)] = list(r.tokens)
                tt = r.token_times[carried:]
                gaps.extend(b - a for a, b in zip(tt, tt[1:]))
            result.update(tokens_by_rid=toks, gaps=gaps)
    with open(out_path, "w") as f:
        json.dump(result, f, default=str)
    if mode != "c":
        jax.distributed.shutdown()


#: Rows emitted with an "error" field this run (the exit code's input).
_FAILED_ROWS: list = []


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--speculate-k", default=None,
                    help="comma-separated speculation depths; emits "
                         "speculative-vs-baseline rows instead of the "
                         "concurrency sweep (env: SERVE_SPECULATE_K)")
    ap.add_argument("--decode-fuse", default=None,
                    help="comma-separated fused decode window sizes; "
                         "emits host-dispatches-per-token + tokens/sec "
                         "rows for the on-device lax.while_loop decode "
                         "program vs the single-step engine "
                         "(env: SERVE_DECODE_FUSE)")
    ap.add_argument("--spec-fused", default=None,
                    help="comma-separated on-device fused-speculation "
                         "configs (k{K}n{N}, e.g. k2n4); emits rows "
                         "comparing Engine(speculate_k=K, decode_fuse=N, "
                         "drafter=DraftModelDrafter) against the "
                         "host-drafted speculative engine AND the plain "
                         "fused engine at identical geometry "
                         "(env: SERVE_SPEC_FUSED)")
    ap.add_argument("--soak", default=None,
                    help="comma-separated soak seeds; runs the "
                         "fault-injection soak harness instead of the "
                         "concurrency sweep (env: SERVE_SOAK)")
    ap.add_argument("--prefix-cache", default=None,
                    help="comma-separated prefix-caching workloads "
                         "(shared_prefix, multiturn); emits TTFT "
                         "cache-on/off rows instead of the concurrency "
                         "sweep (env: SERVE_PREFIX)")
    ap.add_argument("--paged", default=None,
                    help="comma-separated paged-attention workloads "
                         "(shared_prefix); emits the paged-vs-copy "
                         "capacity + TTFT row — Engine(kv_pages=N) vs "
                         "the dense copy-cache engine at the same KV "
                         "byte budget (env: SERVE_PAGED)")
    ap.add_argument("--disagg", default=None,
                    help="comma-separated disagg seeds; runs the "
                         "two-process prefill/decode split (rank 0 "
                         "prefills and ships pages, rank 1 adopts and "
                         "decodes) against a colocated baseline on the "
                         "same Poisson+burst mixed-tenant workload "
                         "(env: SERVE_DISAGG)")
    ap.add_argument("--disagg-worker", default=None,
                    help="internal: subprocess body for the --disagg "
                         "stage (mode:nproc:port:out_path:seed)")
    ap.add_argument("--tenants", default=None,
                    help="comma-separated multi-tenant seeds; runs the "
                         "mixed-priority tenancy workload (per-tier "
                         "p50/p99, fairness shares, sheds, preemptions) "
                         "instead of the concurrency sweep "
                         "(env: SERVE_TENANCY)")
    ap.add_argument("--queue-limit", default=None,
                    help="bound the engine queue in the concurrency "
                         "sweep; overload sheds with QueueFull and rows "
                         "record the shed count (env: SERVE_QUEUE_LIMIT)")
    ap.add_argument("--obs-check", action="store_true",
                    help="measure the tpudp.obs overhead: the identical "
                         "greedy workload through spans+counters-enabled "
                         "vs disabled engines, one serve_obs_overhead "
                         "row (the acceptance bar is within 3%% on the "
                         "CPU smoke host; env: SERVE_OBS_CHECK=1)")
    args = ap.parse_args()

    if args.disagg_worker:
        # Before the jax import: the worker pins its own platform/env.
        _disagg_worker_main(args.disagg_worker)
        return

    import jax

    if os.environ.get("SERVE_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["SERVE_PLATFORM"])
    from tpudp.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    import jax.numpy as jnp
    import numpy as np

    from tpudp.models.generate import generate
    from tpudp.models.gpt2 import GPT2, GPT2Config
    from tpudp.serve import (DraftModelDrafter, Engine, NgramDrafter,
                             QueueFull, TenantClass)

    spec_env = args.speculate_k or os.environ.get("SERVE_SPECULATE_K")
    spec_ks = _parse_levels(spec_env) if spec_env else []
    fused_env = args.decode_fuse or os.environ.get("SERVE_DECODE_FUSE")
    fused_ns = _parse_levels(fused_env) if fused_env else []
    sf_env = args.spec_fused or os.environ.get("SERVE_SPEC_FUSED")
    sf_names = [c for c in sf_env.split(",") if c] if sf_env else []
    # Config names validate like workload names (always strict — an
    # unknown "k{K}n{N}" is a typo, not an unregistered sweep point).
    sf_pairs = []  # (name, k, n)
    for name in sf_names:
        m = SPEC_FUSED_NAME.fullmatch(name)
        if not m or name not in SERVE_SPEC_FUSED_CONFIGS:
            raise SystemExit(
                f"error: unknown spec-fused config {name!r} "
                f"(registry: {list(SERVE_SPEC_FUSED_CONFIGS)})")
        sf_pairs.append((name, int(m.group(1)), int(m.group(2))))
    soak_env = args.soak or os.environ.get("SERVE_SOAK")
    soak_seeds = _parse_levels(soak_env) if soak_env else []
    tenancy_env = args.tenants or os.environ.get("SERVE_TENANCY")
    tenancy_seeds = _parse_levels(tenancy_env) if tenancy_env else []
    disagg_env = args.disagg or os.environ.get("SERVE_DISAGG")
    disagg_seeds = _parse_levels(disagg_env) if disagg_env else []
    prefix_env = args.prefix_cache or os.environ.get("SERVE_PREFIX")
    prefix_workloads = ([w for w in prefix_env.split(",") if w]
                        if prefix_env else [])
    bad_w = [w for w in prefix_workloads
             if w not in SERVE_PREFIX_WORKLOADS]
    if bad_w:
        # Always strict for names (unlike numeric levels, an unknown
        # workload name is a typo, not an unregistered sweep point).
        raise SystemExit(f"error: unknown prefix workloads {bad_w} "
                         f"(registry: {list(SERVE_PREFIX_WORKLOADS)})")
    paged_env = args.paged or os.environ.get("SERVE_PAGED")
    paged_workloads = ([w for w in paged_env.split(",") if w]
                       if paged_env else [])
    bad_p = [w for w in paged_workloads if w not in SERVE_PAGED_WORKLOADS]
    if bad_p:
        raise SystemExit(f"error: unknown paged workloads {bad_p} "
                         f"(registry: {list(SERVE_PAGED_WORKLOADS)})")
    levels_env = os.environ.get("SERVE_CONCURRENCY")
    levels = (_parse_levels(levels_env)
              if levels_env else list(SERVE_CONCURRENCIES))
    if os.environ.get("SERVE_STRICT_LEVELS") == "1":
        bad = [c for c in levels if c not in SERVE_CONCURRENCIES]
        if (not spec_ks and not soak_seeds and not prefix_workloads
                and not paged_workloads and not tenancy_seeds
                and not disagg_seeds
                and not fused_ns and not sf_pairs and bad):
            raise SystemExit(f"error: unregistered concurrency levels {bad} "
                             f"(registry: {list(SERVE_CONCURRENCIES)})")
        bad_k = [k for k in spec_ks if k not in SERVE_SPEC_KS]
        if bad_k:
            raise SystemExit(f"error: unregistered speculate_k values "
                             f"{bad_k} (registry: {list(SERVE_SPEC_KS)})")
        bad_n = [n for n in fused_ns if n not in SERVE_FUSED_NS]
        if bad_n:
            raise SystemExit(f"error: unregistered decode_fuse sizes "
                             f"{bad_n} (registry: {list(SERVE_FUSED_NS)})")
        bad_s = [s for s in soak_seeds if s not in SERVE_SOAK_SEEDS]
        if bad_s:
            raise SystemExit(f"error: unregistered soak seeds {bad_s} "
                             f"(registry: {list(SERVE_SOAK_SEEDS)})")
        bad_t = [s for s in tenancy_seeds
                 if s not in SERVE_TENANCY_SEEDS]
        if bad_t:
            raise SystemExit(f"error: unregistered tenancy seeds {bad_t} "
                             f"(registry: {list(SERVE_TENANCY_SEEDS)})")
        bad_d = [s for s in disagg_seeds if s not in SERVE_DISAGG_SEEDS]
        if bad_d:
            raise SystemExit(f"error: unregistered disagg seeds {bad_d} "
                             f"(registry: {list(SERVE_DISAGG_SEEDS)})")
    n_requests = int(os.environ.get("SERVE_REQUESTS", 24))
    prompt_len = int(os.environ.get("SERVE_PROMPT_LEN", 16))
    max_new = int(os.environ.get("SERVE_MAX_NEW", 32))
    chunk = int(os.environ.get("SERVE_CHUNK", 16))
    load = float(os.environ.get("SERVE_LOAD", 8.0))
    seed = int(os.environ.get("SERVE_SEED", 0))
    # Speculation's home regime is LOW occupancy: at high concurrency the
    # batch already amortizes the weight read (the two levers compete),
    # so the spec rows default to one in-flight request — the latency
    # story — and to longer generations, where the repetitive phase an
    # untrained greedy LM collapses into dominates the run.
    spec_conc = int(os.environ.get("SERVE_SPEC_CONCURRENCY", 1))
    spec_max_new = int(os.environ.get("SERVE_SPEC_MAX_NEW", 64))
    # The fused decode loop's home regime is the same LOW-occupancy one:
    # dispatch overhead per token is largest when the batch is smallest
    # (ROADMAP "On-device decode loop"), so the fused rows default to
    # one in-flight request and measure dispatch mechanics.
    fused_conc = int(os.environ.get("SERVE_FUSED_CONCURRENCY", 1))
    # Robustness axes for the concurrency sweep: a bounded queue (sheds
    # counted per row) and optional per-request deadline budgets.
    ql_env = args.queue_limit or os.environ.get("SERVE_QUEUE_LIMIT")
    queue_limit = int(ql_env) if ql_env else None
    deadline_s = (float(os.environ["SERVE_DEADLINE_S"])
                  if os.environ.get("SERVE_DEADLINE_S") else None)
    ttft_deadline_s = (float(os.environ["SERVE_TTFT_DEADLINE_S"])
                       if os.environ.get("SERVE_TTFT_DEADLINE_S") else None)

    # Default geometry: small GPT-2 family but with the weights (~93 MB
    # fp32) well past any cache, so the decode step is weight-STREAM
    # bound — the regime continuous batching exists for (a config whose
    # weights fit in cache is FLOP-bound at decode and batching buys
    # little; measured on the 2-core host: 17M params -> 2.8x batch-8
    # scan gain, 4M params -> 2.0x).
    dm = int(os.environ.get("SERVE_DMODEL", 512))
    # Prefix-cache axes: pool budget, the shared system prompt's length,
    # the cached engines' slot count, and the multiturn conversation
    # shape (users x turns, each turn re-sending the whole history).
    prefix_blocks = int(os.environ.get("SERVE_PREFIX_BLOCKS", 64))
    prefix_len = int(os.environ.get("SERVE_PREFIX_LEN", 4 * chunk))
    prefix_conc = int(os.environ.get("SERVE_PREFIX_CONCURRENCY", 4))
    prefix_users = int(os.environ.get("SERVE_PREFIX_USERS", 4))
    prefix_turns = int(os.environ.get("SERVE_PREFIX_TURNS", 3))
    prefix_tail = max(chunk // 2, 1)
    # Speculative windows need k scratch beyond the generation budget —
    # both the host-drafted sweep's and the fused-spec sweep's.
    slack = max([*spec_ks, *(k for _, k, _n in sf_pairs)], default=0)
    if prefix_workloads or paged_workloads:
        # The deepest multiturn prompt is the whole prior conversation:
        # shared prefix + `turns` user tails + (turns-1) responses, plus
        # this turn's generation.  (The paged rows only need one turn's
        # worth; sharing the geometry keeps the two stages comparable.)
        need = (prefix_len + prefix_turns * prefix_tail
                + prefix_turns * max_new)
    else:
        need = prompt_len + (max(max_new, spec_max_new) + slack
                             if spec_ks or sf_pairs else max_new)
    cfg = GPT2Config(
        vocab_size=int(os.environ.get("SERVE_VOCAB", 8192)),
        max_seq_len=((need + chunk - 1) // chunk) * chunk,
        num_layers=int(os.environ.get("SERVE_LAYERS", 6)),
        num_heads=max(dm // 64, 1),
        d_model=dm,
    )
    model = GPT2(cfg)
    # Soak, tenancy, and disagg modes build their own tiny models (they
    # measure scheduling/handoff under faults/priorities, not FLOPs) —
    # don't pay the ~93 MB default init for them.
    params = (None if soak_seeds or tenancy_seeds or disagg_seeds else
              model.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 8), jnp.int32))["params"])
    kind = jax.devices()[0].device_kind

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len)
               .astype(np.int32) for _ in range(n_requests)]

    def drive(engine, offsets, reqs, new_tokens):
        """Submit ``reqs`` at ``offsets`` (seconds from start), step the
        engine to completion; return aggregate timing.  A submit refused
        by the bounded queue (QueueFull) is counted shed and dropped —
        the overload contract is refusal, so the bench must absorb it
        rather than retry-loop the burst back in."""
        n = len(reqs)
        start = time.perf_counter()
        handles = []
        nxt = 0
        shed = 0
        latencies = []
        consumed = {}  # request id -> tokens already accounted
        last_emit = start
        while nxt < n or engine.slots_in_use or engine.queue_depth:
            now = time.perf_counter()
            while nxt < n and now - start >= offsets[nxt]:
                try:
                    handles.append(engine.submit(
                        reqs[nxt], new_tokens, seed=seed + nxt,
                        deadline_s=deadline_s,
                        ttft_deadline_s=ttft_deadline_s))
                except QueueFull:
                    shed += 1
                nxt += 1
                now = time.perf_counter()
            if engine.slots_in_use or engine.queue_depth:
                for req, _tok in engine.step():
                    # Index per request, not [-1]/[-2]: a speculative
                    # window lands several tokens at once, and each
                    # pair must charge ITS token's gap (first token of
                    # a window carries the inter-window forward time,
                    # the rest of the burst ~0 — the client-visible
                    # streaming distribution).
                    j = consumed.get(req.id, 0)
                    consumed[req.id] = j + 1
                    t = req.token_times[j]
                    prev = (req.token_times[j - 1] if j
                            else req.submit_time)
                    latencies.append(t - prev)
                    last_emit = max(last_emit, t)
            elif nxt < n:
                time.sleep(min(0.001, max(offsets[nxt] - (now - start), 0)))
        elapsed = last_emit - start
        ttfts = [h.token_times[0] - h.submit_time for h in handles
                 if h.token_times]
        return elapsed, latencies, ttfts, handles, shed

    def latency_fields(latencies, ttfts):
        return {
            "p50_token_latency_ms": round(
                _percentile(latencies, 50) * 1e3, 3),
            "p99_token_latency_ms": round(
                _percentile(latencies, 99) * 1e3, 3),
            "ttft_p50_ms": round(_percentile(ttfts, 50) * 1e3, 3),
            "ttft_p99_ms": round(_percentile(ttfts, 99) * 1e3, 3),
        }

    results = []

    def emit(row):
        # Unified serve-row schema: EVERY row (including error rows)
        # carries accept_rate — null when speculation is off or the row
        # never measured one — so downstream consumers read acceptance
        # accounting from one key across all stages instead of probing
        # per-stage column names (test_bench_smoke pins this).
        row.setdefault("accept_rate", None)
        results.append(row)
        if "error" in row:
            _FAILED_ROWS.append(row)
        print(json.dumps(row), flush=True)

    # Per-stage metric sidecar (tpudp.obs exposition): every stage banks
    # the Engine.metrics() snapshots of the engines it measured —
    # device counters, span rollups, stats — into ONE JSON file next to
    # the row stream, so a bench row always ships with the structured
    # telemetry that explains it.
    sidecar: dict = {"kind": "serve_bench_metrics", "stages": {}}

    def bank_metrics(stage: str, key, metrics: dict) -> None:
        sidecar["stages"].setdefault(stage, {})[str(key)] = metrics

    def write_sidecar() -> None:
        path = os.environ.get("SERVE_METRICS_SIDECAR") or os.path.join(
            "bench_results", "serve_bench_metrics.json")
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            sidecar["device_kind"] = kind
            with open(path, "w") as f:
                json.dump(sidecar, f, indent=1, sort_keys=True,
                          default=str)
                f.write("\n")
            print(f"[serve_bench] metrics sidecar -> {path}",
                  file=sys.stderr)
        except OSError as exc:
            print(f"[serve_bench] metrics sidecar write failed: {exc}",
                  file=sys.stderr)

    obs_check = bool(args.obs_check
                     or os.environ.get("SERVE_OBS_CHECK") == "1")
    if obs_check:
        # Spans+counters on vs off, identical greedy workload — the
        # telemetry acceptance bar: enabled within 3% of disabled on
        # the CPU smoke host.  Best-of-N both sides (the smoke host has
        # documented double-digit variance; a single pair would gate on
        # scheduler luck).  Parity is also asserted: obs must never
        # perturb outputs.
        oc_conc = int(os.environ.get("SERVE_OBS_CONCURRENCY", 4))
        oc_tries = int(os.environ.get("SERVE_OBS_TRIES", 3))
        offsets = np.zeros(n_requests)

        def measure(obs_on):
            eng = Engine(model, params, num_slots=oc_conc,
                         max_len=cfg.max_seq_len, prefill_chunk=chunk,
                         obs=obs_on)
            eng.generate_many(prompts[:2], 2)  # compile off the clock
            best, outs = 0.0, None
            for _ in range(oc_tries):
                elapsed, _lat, _ttft, handles, _shed = drive(
                    eng, offsets, prompts, max_new)
                toks = sum(len(h.tokens) for h in handles)
                tps = toks / elapsed if elapsed > 0 else 0.0
                if tps >= best:
                    best, outs = tps, [h.tokens for h in handles]
            return best, outs, eng

        on_tps, on_out, on_eng = measure(True)
        off_tps, off_out, _off_eng = measure(False)
        ratio = on_tps / off_tps if off_tps else None
        emit({
            "metric": "serve_obs_overhead",
            "value": round(ratio, 4) if ratio is not None else None,
            "unit": "enabled/disabled tokens/sec ratio",
            "tokens_per_sec_obs_on": round(on_tps, 1),
            "tokens_per_sec_obs_off": round(off_tps, 1),
            "within_3pct": ratio is not None and ratio >= 0.97,
            "parity_ok": on_out == off_out,
            "concurrency": oc_conc,
            "tries": oc_tries,
            "requests": n_requests,
            "max_new_tokens": max_new,
            "device_kind": kind,
        })
        bank_metrics("obs_check", "on", on_eng.metrics())
        write_sidecar()
        print(json.dumps({"serve_obs": results}))
        return

    # ---- sequential generate() baseline (one request at a time) --------
    # Warmup compiles the prefill+decode program; every request shares the
    # (prompt_len, max_new) geometry, so the timed loop never recompiles.
    # Skipped in spec mode: its rows compare against a PLAIN ENGINE at
    # the same concurrency instead (the honest baseline for speculation).
    # Skipped in soak mode too: the soak referees robustness invariants
    # against per-request generate() references, not throughput.
    seq_tps = per_req_s = None
    seq_latencies = []
    if (not spec_ks and not soak_seeds and not prefix_workloads
            and not paged_workloads and not tenancy_seeds
            and not disagg_seeds and not fused_ns and not sf_pairs):
        np.asarray(generate(model, params, jnp.asarray(prompts[0][None]),
                            max_new))
        t0 = time.perf_counter()
        for p in prompts:
            r0 = time.perf_counter()
            np.asarray(generate(model, params, jnp.asarray(p[None]),
                                max_new))
            seq_latencies.append(time.perf_counter() - r0)
        seq_elapsed = time.perf_counter() - t0
        seq_tps = n_requests * max_new / seq_elapsed
        per_req_s = seq_elapsed / n_requests

    def run_level(c: int) -> None:
        engine = Engine(model, params, num_slots=c,
                        max_len=cfg.max_seq_len, prefill_chunk=chunk)
        # Warmup: compile prefill/decode/sample for THIS geometry off the
        # clock (the persistent cache makes relaunches cheap on TPU).
        # The queue bound is applied AFTER warmup — a --queue-limit
        # below the warmup batch size must shed the measured burst, not
        # the warmup's own submits.
        engine.generate_many(prompts[:2], 2)
        engine.queue_limit = queue_limit
        base_stats = dict(engine.stats)

        # Poisson arrivals: offered load = `load` x the sequential service
        # rate per slot -> saturating for load >= 1.
        lam = load * c / per_req_s  # requests/sec
        arrival_rng = np.random.default_rng(seed + 1)
        gaps = arrival_rng.exponential(1.0 / lam, size=n_requests)
        offsets = np.cumsum(gaps) - gaps[0]  # first request at t=0

        elapsed, latencies, ttfts, handles, shed = drive(
            engine, offsets, prompts, max_new)
        # Count what was actually EMITTED: with a bounded queue or
        # deadlines some requests shed or retire early, and charging the
        # full n*max_new would overstate throughput.
        emitted_tokens = sum(len(h.tokens) for h in handles)
        tps = emitted_tokens / elapsed if elapsed > 0 else 0.0
        dec = engine.stats["decode_steps"] - base_stats.get("decode_steps", 0)
        act = (engine.stats["active_slot_steps"]
               - base_stats.get("active_slot_steps", 0))
        occupancy = act / (dec * c) if dec else None
        emit({
            "metric": METRIC,
            "concurrency": c,
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "queue_limit": queue_limit,
            "shed": shed,
            "deadline_expired": int(engine.stats["deadline_expired"]),
            "sequential_tokens_per_sec": round(seq_tps, 1),
            "speedup_vs_sequential": round(tps / seq_tps, 2) if seq_tps
            else None,
            **latency_fields(latencies, ttfts),
            "seq_p50_request_latency_ms": round(
                _percentile(seq_latencies, 50) * 1e3, 1),
            "mean_slot_occupancy": (round(occupancy, 3)
                                    if occupancy is not None else None),
            "requests": n_requests,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new,
            "prefill_chunk": chunk,
            "offered_load": load,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve", c, engine.metrics())

    def run_spec(k: int) -> None:
        """Speculative vs plain engine, identical repetitive greedy
        workload (all requests at t=0; the column measures decode
        mechanics, not arrival luck).

        The workload is the speculation CEILING, made deterministic:
        both engines decode the same zero-scaled weight tree, whose
        greedy output is provably constant — every forward streams the
        same 93 MB of weights through the same gemms (cost identical to
        real weights; only the VALUES are zero), and the n-gram drafter
        locks on after two tokens, so acceptance ~1 and the speedup is
        the engine's mechanical best case, not prompt luck.  A real
        workload interpolates between the baseline and this row by its
        own acceptance rate — which is why acceptance_rate is a
        first-class column.  (Random-init weights loop too, but WHICH
        loop each prompt falls into swings acceptance 0.3-0.7 between
        seeds — a regression gate can't sit on that.)"""
        spec_rng = np.random.default_rng(seed + 2)
        spec_prompts = [
            np.tile(spec_rng.integers(0, cfg.vocab_size, size=4),
                    (prompt_len + 3) // 4)[:prompt_len].astype(np.int32)
            for _ in range(n_requests)]
        offsets = np.zeros(n_requests)
        warm = np.tile(spec_rng.integers(0, cfg.vocab_size, size=2),
                       chunk // 2 + 1)[:chunk].astype(np.int32)

        plain = Engine(model, zero_params, num_slots=spec_conc,
                       max_len=cfg.max_seq_len, prefill_chunk=chunk)
        plain.generate_many([warm], 2)  # warmup: prefill+decode programs
        base_elapsed, _base_lat, base_ttft, _h, _s = drive(
            plain, offsets, spec_prompts, spec_max_new)
        base_tps = (n_requests * spec_max_new / base_elapsed
                    if base_elapsed > 0 else 0.0)

        # min_ngram=2: a single-token match is mostly noise, and every
        # wrong proposal costs a full-width verify forward.
        engine = Engine(model, zero_params, num_slots=spec_conc,
                        max_len=cfg.max_seq_len, prefill_chunk=chunk,
                        speculate_k=k,
                        drafter=NgramDrafter(max_ngram=3, min_ngram=2))
        # Repetitive warmup prompt: guarantees drafted steps, so the
        # VERIFY program compiles off the clock too.
        engine.generate_many([warm], 8)
        elapsed, latencies, ttfts, _h, _s = drive(
            engine, offsets, spec_prompts, spec_max_new)
        tps = (n_requests * spec_max_new / elapsed if elapsed > 0 else 0.0)
        emit({
            "metric": SPEC_METRIC,
            "speculate_k": k,
            "concurrency": spec_conc,
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "drafter": "ngram(max=3,min=2)",
            # acceptance_rate is this row's historical column name;
            # accept_rate is the unified cross-stage schema key.
            "acceptance_rate": (round(engine.acceptance_rate, 3)
                                if engine.acceptance_rate is not None
                                else None),
            "accept_rate": (round(engine.acceptance_rate, 3)
                            if engine.acceptance_rate is not None
                            else None),
            "verify_steps": engine.stats["verify_steps"],
            "draft_tokens": engine.stats["draft_tokens"],
            "baseline_tokens_per_sec": round(base_tps, 1),
            "speedup_vs_baseline": (round(tps / base_tps, 2)
                                    if base_tps else None),
            "baseline_ttft_p50_ms": round(
                _percentile(base_ttft, 50) * 1e3, 3),
            **latency_fields(latencies, ttfts),
            "workload": "repetitive-ceiling",
            "requests": n_requests,
            "prompt_len": prompt_len,
            "max_new_tokens": spec_max_new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve_spec", k, engine.metrics())

    # The fused sweep's single-step baseline, measured lazily once and
    # shared by every run_fused row (see its docstring).
    fused_shared: dict = {}

    def run_fused(n: int) -> None:
        """Fused-decode-window row: the IDENTICAL greedy pure-decode
        workload through a ``decode_fuse=n`` engine and a single-step
        engine (bit-identical outputs — ``parity_ok`` is the row's own
        check), reporting host-dispatches-per-decoded-token and
        tokens/sec for both.  Requests run ``fused_conc`` at a time
        with the queue kept empty, so once prefill drains every
        scheduler iteration is a pure-decode step — the regime where
        the single-step engine pays one host round trip per token and
        the fused engine pays one per up-to-n-token window.  The
        ``dispatch_ok`` gate (<= 1/n x (1 + eps)) is what the resume
        machinery keys on: a fused run that still dispatched per token
        proved the loop never engaged.  ``n=1`` is the single-step
        control row (the fused program is never built).

        The single-step baseline is measured ONCE per sweep and shared
        across rows (the workload is a pure function of the seed, so
        every row compares against the identical run) — re-measuring
        the same engine per N would only burn chip time, the
        same sharing rationale as run_spec's shared zero tree."""
        frng = np.random.default_rng(seed + 4)
        f_prompts = [frng.integers(0, cfg.vocab_size, size=prompt_len)
                     .astype(np.int32) for _ in range(n_requests)]

        def run(engine):
            # Warmup compiles prefill/sample/decode — and, for n > 1,
            # the fused window program — off the clock.
            engine.generate_many([f_prompts[0]], 2)
            base_stats = dict(engine.stats)
            outputs = []
            t0 = time.perf_counter()
            for i in range(0, n_requests, fused_conc):
                batch = f_prompts[i:i + fused_conc]
                handles = [engine.submit(p, max_new, seed=seed + i + j)
                           for j, p in enumerate(batch)]
                engine.run_until_complete()
                outputs.extend(h.tokens for h in handles)
            elapsed = time.perf_counter() - t0
            st = engine.stats
            decoded = (st["tokens"] - base_stats.get("tokens", 0)
                       - n_requests)  # first tokens ride the prefill sample
            dispatches = (st["decode_steps"]
                          - base_stats.get("decode_steps", 0)
                          + st["fused_windows"]
                          - base_stats.get("fused_windows", 0))
            tokens = st["tokens"] - base_stats.get("tokens", 0)
            return dict(
                elapsed=elapsed, outputs=outputs, tokens=tokens,
                decoded=decoded, dispatches=dispatches,
                fused_windows=(st["fused_windows"]
                               - base_stats.get("fused_windows", 0)),
                fused_steps=(st["fused_steps"]
                             - base_stats.get("fused_steps", 0)),
                metrics=engine.metrics())

        if "base" not in fused_shared:
            fused_shared["base"] = run(
                Engine(model, params, num_slots=fused_conc,
                       max_len=cfg.max_seq_len, prefill_chunk=chunk))
        base = fused_shared["base"]
        fused = run(Engine(model, params, num_slots=fused_conc,
                           max_len=cfg.max_seq_len, prefill_chunk=chunk,
                           decode_fuse=n))
        dpt = (fused["dispatches"] / fused["decoded"]
               if fused["decoded"] else None)
        bound = (1.0 / n) * (1.0 + FUSED_DISPATCH_EPS)
        tps = (fused["tokens"] / fused["elapsed"]
               if fused["elapsed"] > 0 else 0.0)
        base_tps = (base["tokens"] / base["elapsed"]
                    if base["elapsed"] > 0 else 0.0)
        emit({
            "metric": FUSED_METRIC,
            "decode_fuse": n,
            "concurrency": fused_conc,
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "host_dispatches_per_token": (round(dpt, 4)
                                          if dpt is not None else None),
            "dispatch_bound": round(bound, 4),
            "dispatch_ok": dpt is not None and dpt <= bound,
            "fused_windows": fused["fused_windows"],
            "fused_steps": fused["fused_steps"],
            "single_step_tokens_per_sec": round(base_tps, 1),
            "single_step_dispatches_per_token": (
                round(base["dispatches"] / base["decoded"], 4)
                if base["decoded"] else None),
            "speedup_vs_single_step": (round(tps / base_tps, 3)
                                       if base_tps else None),
            "parity_ok": fused["outputs"] == base["outputs"],
            "requests": n_requests,
            "prompt_len": prompt_len,
            "max_new_tokens": max_new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve_fused", n, fused["metrics"])

    def run_spec_fused(config: str, k: int, n: int, draft_model,
                       zero_params, zero_draft_params) -> None:
        """On-device fused speculation vs BOTH of its ancestors, same
        repetitive-ceiling greedy workload (run_spec's zero-scaled
        weight tree — every forward streams real-sized weights, greedy
        output is provably constant, so acceptance ~1 and the row is
        the mechanical best case, not prompt luck):

        * the host-drafted speculative engine (speculate_k=k, draft
          model bucketed to the same max_len-wide context) — isolates
          what moving draft->verify->accept on device buys;
        * the plain fused engine (decode_fuse=n, no speculation) —
          isolates what the draft model buys on top of dispatch
          amortization.

        The gate (``spec_fused_ok``) is the ISSUE acceptance bar: the
        fused-spec window actually engaged (fused_spec_windows > 0),
        greedy outputs bit-identical across all three engines, sampled
        outputs bit-identical vs the host-drafted engine under the same
        per-slot PRNG chains (both advance one key per verify window),
        and tokens/sec >= max(both baselines).  Interleaved best-of-
        ``tries`` per engine, like run_paged_kernel — the smoke host
        has documented double-digit timing variance and a one-shot
        >=max(...) gate would sit on scheduler luck."""
        sf_rng = np.random.default_rng(seed + 5)
        sf_prompts = [
            np.tile(sf_rng.integers(0, cfg.vocab_size, size=4),
                    (prompt_len + 3) // 4)[:prompt_len].astype(np.int32)
            for _ in range(n_requests)]
        offsets = np.zeros(n_requests)
        warm = np.tile(sf_rng.integers(0, cfg.vocab_size, size=2),
                       chunk // 2 + 1)[:chunk].astype(np.int32)
        tries = int(os.environ.get("SERVE_SPEC_FUSED_TRIES", 2))

        engines = {
            "fused_spec": Engine(
                model, zero_params, num_slots=spec_conc,
                max_len=cfg.max_seq_len, prefill_chunk=chunk,
                speculate_k=k, decode_fuse=n,
                drafter=DraftModelDrafter(draft_model, zero_draft_params)),
            "host_spec": Engine(
                model, zero_params, num_slots=spec_conc,
                max_len=cfg.max_seq_len, prefill_chunk=chunk,
                speculate_k=k,
                drafter=DraftModelDrafter(draft_model, zero_draft_params,
                                          bucket=cfg.max_seq_len)),
            "plain_fused": Engine(
                model, zero_params, num_slots=spec_conc,
                max_len=cfg.max_seq_len, prefill_chunk=chunk,
                decode_fuse=n),
        }
        for eng in engines.values():
            eng.generate_many([warm], 8)  # all programs off the clock

        best = dict.fromkeys(engines, 0.0)
        outs: dict = {}
        lat_best: dict = {}
        for _ in range(tries):
            for name, eng in engines.items():
                elapsed, lats, ttfts, handles, _s = drive(
                    eng, offsets, sf_prompts, spec_max_new)
                tps_i = (n_requests * spec_max_new / elapsed
                         if elapsed > 0 else 0.0)
                if tps_i >= best[name]:
                    best[name] = tps_i
                    lat_best[name] = (lats, ttfts)
                outs[name] = [list(h.tokens) for h in handles]
        sf_eng = engines["fused_spec"]
        stats = dict(sf_eng.stats)
        accept = sf_eng.acceptance_rate
        host_accept = engines["host_spec"].acceptance_rate
        engaged = stats.get("fused_spec_windows", 0) > 0

        # Sampled parity vs the host-drafted referee (identical PRNG
        # chains: both speculative engines advance the per-slot key once
        # per verify window) — short, off the throughput clock.
        sampled = {}
        for name in ("fused_spec", "host_spec"):
            hs = [engines[name].submit(p, 12, temperature=0.9, top_k=12,
                                       seed=seed + 77 + i)
                  for i, p in enumerate(sf_prompts[:2])]
            engines[name].run_until_complete()
            sampled[name] = [list(h.tokens) for h in hs]
        sampled_parity = sampled["fused_spec"] == sampled["host_spec"]

        tps = best["fused_spec"]
        host_tps = best["host_spec"]
        fused_tps = best["plain_fused"]
        parity_ok = (outs["fused_spec"] == outs["host_spec"]
                     == outs["plain_fused"] and sampled_parity)
        spec_fused_ok = (tps > 0 and parity_ok and engaged
                         and tps >= host_tps and tps >= fused_tps)
        lats, ttfts = lat_best["fused_spec"]
        emit({
            "metric": SPEC_FUSED_METRIC,
            "config": config,
            "speculate_k": k,
            "decode_fuse": n,
            "concurrency": spec_conc,
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "drafter": (f"draft_model(L{draft_model.config.num_layers},"
                        f"d{draft_model.config.d_model})"),
            "accept_rate": round(accept, 3) if accept is not None else None,
            "draft_tokens": stats.get("draft_tokens", 0),
            "draft_accepted": stats.get("draft_accepted", 0),
            "fused_spec_windows": stats.get("fused_spec_windows", 0),
            "fused_spec_steps": stats.get("fused_spec_steps", 0),
            "host_spec_tokens_per_sec": round(host_tps, 1),
            "host_spec_accept_rate": (round(host_accept, 3)
                                      if host_accept is not None else None),
            "plain_fused_tokens_per_sec": round(fused_tps, 1),
            "speedup_vs_host_spec": (round(tps / host_tps, 3)
                                     if host_tps else None),
            "speedup_vs_plain_fused": (round(tps / fused_tps, 3)
                                       if fused_tps else None),
            "sampled_parity_ok": sampled_parity,
            "parity_ok": parity_ok,
            "spec_fused_ok": spec_fused_ok,
            "tries": tries,
            "workload": "repetitive-ceiling",
            **latency_fields(lats, ttfts),
            "requests": n_requests,
            "prompt_len": prompt_len,
            "max_new_tokens": spec_max_new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve_spec_fused", config, sf_eng.metrics())

    def run_soak(soak_seed: int) -> None:
        """Fault-injection soak against the robustness layer, fully
        deterministic per seed: a small tenant-aware engine (tiny
        config — the soak exercises SCHEDULING under faults, not FLOPs)
        serves a workload mixing free-running requests, impossible TTFT
        deadlines, tight total deadlines, mid-stream client cancels,
        and queue-limit sheds, while a drafter dies mid-run
        (quarantine), two device steps are injected to fail
        (requeue-once containment), and a PREEMPTION STORM of scheduled
        high-priority bursts evicts low-tier slots through the tenancy
        carry-over path — all with the SDC canary cadence ON
        (``canary_every_s``), so pinned-reference replays interleave
        with the chaos.  The row passes only if nothing wedged (bounded
        step count), the engine ended empty, every surviving COMPLETE
        request's greedy output — storm and preempted requests included
        — is bit-identical to generate(), and the canaries ran CLEAN
        (``canary_ok``: >=1 comparison, zero quarantines — the serving
        false-positive gate; faults, preemptions, and requeues must
        never read as corruption)."""
        from tpudp.serve import FinishReason
        from tpudp.serve.faults import (FailingDrafter, FaultySteps,
                                        PreemptionStorm)

        srng = np.random.default_rng(10_000 + soak_seed)
        s_cfg = GPT2Config(
            vocab_size=int(os.environ.get("SOAK_VOCAB", 128)),
            max_seq_len=64,
            num_layers=int(os.environ.get("SOAK_LAYERS", 2)),
            num_heads=2,
            d_model=int(os.environ.get("SOAK_DMODEL", 64)),
        )
        s_model = GPT2(s_cfg)
        s_params = s_model.init(jax.random.PRNGKey(soak_seed),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        n = int(os.environ.get("SOAK_REQUESTS", 16))
        p_len, s_new = 8, 8
        s_prompts = [srng.integers(0, s_cfg.vocab_size, size=p_len)
                     .astype(np.int32) for _ in range(n)]
        hook = FaultySteps(
            fail_at=set(int(x) for x in srng.integers(5, 60, size=2)))
        # The main workload rides the bounded "default" class; the storm
        # submits into the unbounded high-priority "urgent" class, so
        # every storm burst that lands while the slots are busy forces a
        # preemption (bit-exact carry-over is part of the pass bar).
        eng = Engine(
            s_model, s_params, num_slots=4, max_len=32, prefill_chunk=8,
            speculate_k=2,
            drafter=FailingDrafter(inner=NgramDrafter(),
                                   ok_proposals=int(srng.integers(1, 8))),
            drafter_timeout_s=30.0, step_fault_hook=hook,
            canary_every_s=0.02, canary_new_tokens=4,
            tenants={"default": TenantClass(priority=0, queue_limit=6),
                     "urgent": TenantClass(priority=1)})
        # Request mix by kind: 0 -> impossible TTFT deadline (expires
        # while queued), 1 -> tight total deadline (expires wherever the
        # clock catches it), 2 -> cancelled mid-stream, else free-run.
        kinds = srng.integers(0, 8, size=n)
        cancel_at = {i: int(srng.integers(1, s_new))
                     for i in range(n) if kinds[i] == 2}
        storm_new = 4
        storm = PreemptionStorm(
            "urgent",
            [srng.integers(0, s_cfg.vocab_size, size=p_len)
             .astype(np.int32) for _ in range(3)],
            at_steps=sorted(int(x) for x in srng.integers(4, 40, size=3)),
            max_new=storm_new, seed=1_000 + soak_seed)
        handles: list = []
        submitted = 0
        steps = 0
        max_steps = 100 + 40 * n  # wedge guard: way past any honest run
        while ((submitted < n or eng.slots_in_use or eng.queue_depth
                or not storm.done)
               and steps < max_steps):
            for _ in range(3):  # submit in waves: queue + admission churn
                if submitted >= n:
                    break
                i = submitted
                kw = {}
                if kinds[i] == 0:
                    kw["ttft_deadline_s"] = 1e-7
                elif kinds[i] == 1:
                    kw["deadline_s"] = 0.02
                try:
                    handles.append(eng.submit(s_prompts[i], s_new,
                                              seed=soak_seed + i, **kw))
                except QueueFull:
                    handles.append(None)
                submitted += 1
            eng.step()
            steps += 1
            storm.tick(eng, steps)
            if submitted >= n and storm.done:
                # Workload fully in: stop LAUNCHING canaries (else the
                # cadence keeps a slot busy and the drain never ends)
                # but keep the comparison path live for the in-flight
                # one — a huge interval, not None, so its completion is
                # still checked against the pinned reference.
                eng.canary_every_s = 1e9
            for i, h in enumerate(handles):
                if (h is not None and not h.done and i in cancel_at
                        and len(h.tokens) >= cancel_at[i]):
                    h.cancel()
        wedged = steps >= max_steps
        no_leak = eng.slots_in_use == 0 and eng.queue_depth == 0
        parity_ok = True
        completed = 0
        for i, h in enumerate(handles):
            if h is None or h.finish_reason is not FinishReason.COMPLETE:
                continue
            completed += 1
            ref = np.asarray(generate(s_model, s_params,
                                      jnp.asarray(s_prompts[i][None]),
                                      s_new))[0, p_len:]
            if h.tokens != ref.tolist():
                parity_ok = False
        for h in storm.handles:
            if h is None or h.finish_reason is not FinishReason.COMPLETE:
                continue
            completed += 1
            ref = np.asarray(generate(s_model, s_params,
                                      jnp.asarray(h.prompt[None]),
                                      storm_new))[0, p_len:]
            if h.tokens != ref.tolist():
                parity_ok = False
        # Disaggregated transfer-fault sub-phase: the same seed replays
        # a small mixed greedy/sampled job set through a 3-host
        # in-process DisaggCluster once per WIRE injector — dropped
        # transfers (retries exhaust -> typed local fallback), corrupt
        # payloads (receiver quarantine + clean retry), a slow link,
        # and a sender SIGKILL'd mid-offer (survivor failover).  The
        # bar folds into the row's gates: no wedge (bounded ticks), no
        # page leak on any surviving host, survivors bit-identical to
        # one colocated engine.
        from tpudp.serve import DisaggCluster
        from tpudp.serve.faults import (CorruptPagePayload,
                                        DroppedTransfer,
                                        SenderKilledMidOffer, SlowLink)

        d_rng = np.random.default_rng(20_000 + soak_seed)
        d_jobs = []
        for i in range(4):
            kw = {} if i % 2 == 0 else dict(
                temperature=0.8, top_k=7, seed=300 + soak_seed + i)
            d_jobs.append((d_rng.integers(0, s_cfg.vocab_size,
                                          size=8 + 2 * (i % 2))
                           .astype(np.int32), 5 + i % 3, kw))

        def _d_engine():
            return Engine(s_model, s_params, num_slots=4, max_len=32,
                          prefill_chunk=8, kv_pages=24)

        d_ref = _d_engine()
        d_handles = [d_ref.submit(p, m, **kw) for p, m, kw in d_jobs]
        d_ref.run_until_complete()
        d_ref.check_paged()
        d_want = [list(h.tokens) for h in d_handles]
        transfer_parity = True
        transfer_no_leak = True
        transfer_wedged = False
        transfer_quarantined = 0
        transfer_retries = 0
        transfer_failovers = 0
        d_faults = (
            DroppedTransfer(rank=0, at_seqs=range(0, 40)),
            CorruptPagePayload(rank=0,
                               at_seqs=range(0, 2 + soak_seed % 2)),
            SlowLink(delay_s=0.001, rank=0),
            # at_seq=4: late enough that a handoff has landed on rank 2
            # by the kill, so the death orphans a journaled request and
            # the failover vote actually redistributes it (at seq 2 the
            # host still owns nothing and failover is a no-op).
            SenderKilledMidOffer(rank=2, at_seq=4),
        )
        for d_fault in d_faults:
            cl = DisaggCluster([_d_engine() for _ in range(3)],
                               prefill=0, retries=1, faults=(d_fault,))
            d_creqs = [cl.submit(p, m, **kw) for p, m, kw in d_jobs]
            try:
                cl.run_until_complete(max_ticks=3000)
            except RuntimeError:
                transfer_wedged = True
                continue
            if [c.tokens for c in d_creqs] != d_want:
                transfer_parity = False
            try:
                cl.check()
            except Exception:  # noqa: BLE001
                transfer_no_leak = False
            st = cl.stats()
            transfer_quarantined += sum(
                s.get("quarantined_transfers", 0) for s in st.values())
            transfer_retries += sum(
                s.get("migration_retries", 0) for s in st.values())
            transfer_failovers += sum(
                1 for e in cl.events if e["kind"] == "failover")
        parity_ok = parity_ok and transfer_parity
        no_leak = no_leak and transfer_no_leak
        wedged = wedged or transfer_wedged
        canary_runs = int(eng.stats["canary_runs"])
        canary_quarantines = int(eng.stats["canary_mismatch"])
        canary_ok = (canary_runs >= 1 and canary_quarantines == 0
                     and not eng.quarantined)
        emit({
            "metric": SOAK_METRIC,
            "seed": soak_seed,
            "value": completed,
            "unit": "completed_requests",
            "requests": n,
            "storm_requests": storm.submitted,
            "steps": steps,
            "wedged": wedged,
            "no_leak": no_leak,
            "parity_ok": parity_ok,
            "shed": int(eng.stats["shed"]),
            "deadline_expired": int(eng.stats["deadline_expired"]),
            "cancelled": int(eng.stats["cancelled"]),
            "errors": int(eng.stats["errors"]),
            "requeued": int(eng.stats["requeued"]),
            "preempted": int(eng.stats["preempted"]),
            "step_failures": int(eng.stats["step_failures"]),
            "drafter_quarantined": int(eng.stats["drafter_quarantined"]),
            "canary_runs": canary_runs,
            "canary_quarantines": canary_quarantines,
            "canary_ok": canary_ok,
            "transfer_faults": len(d_faults),
            "transfer_quarantined": int(transfer_quarantined),
            "transfer_retries": int(transfer_retries),
            "transfer_failovers": int(transfer_failovers),
            "num_layers": s_cfg.num_layers,
            "d_model": s_cfg.d_model,
            "vocab_size": s_cfg.vocab_size,
            "device_kind": kind,
        })

    def run_tenancy(t_seed: int) -> None:
        """Multi-tenant mixed workload: one high-priority tier over two
        equal-priority weighted tiers (3:1), tiny model (the row
        measures SCHEDULING — priorities, preemption, fair shares —
        not FLOPs).

        Phase A (baseline): the high tier alone, one request at a time,
        records the no-load TTFT distribution.  Phase B (overload): the
        low tiers are burst-submitted past their per-class queue_limits
        every step (the excess sheds — that IS the overload evidence)
        while the same high-tier arrivals ride on top, preempting
        low-tier slots whenever none is free.  The row's gates:
        ``p99_ok`` — high-tier TTFT p99 under overload held within
        TENANCY_P99_BOUND x the phase-A p99; ``parity_ok`` — every
        completed request (preempted, resumed, high or low) greedy-
        bit-identical to generate(); ``no_leak`` — the engine ended
        empty.  Fairness: admitted shares of the two low tiers vs their
        configured 3:1 weights, within 10%."""
        from tpudp.serve import FinishReason

        trng = np.random.default_rng(20_000 + t_seed)
        t_cfg = GPT2Config(
            vocab_size=int(os.environ.get("TENANCY_VOCAB", 128)),
            max_seq_len=64,
            num_layers=int(os.environ.get("TENANCY_LAYERS", 2)),
            num_heads=2,
            d_model=int(os.environ.get("TENANCY_DMODEL", 64)),
        )
        t_model = GPT2(t_cfg)
        t_params = t_model.init(jax.random.PRNGKey(t_seed),
                                jnp.zeros((1, 8), jnp.int32))["params"]
        p_len, t_new = 8, 8
        n_high = int(os.environ.get("TENANCY_HIGH", 12))
        phase_steps = int(os.environ.get("TENANCY_STEPS", 240))
        ql = int(os.environ.get("TENANCY_QL", 4))
        bound = float(os.environ.get("TENANCY_P99_BOUND", 5.0))
        w_a, w_b = 3.0, 1.0

        def make_engine():
            return Engine(
                t_model, t_params, num_slots=4, max_len=32,
                prefill_chunk=8,
                tenants={"high": TenantClass(priority=1),
                         "lo_a": TenantClass(priority=0, weight=w_a,
                                             queue_limit=ql),
                         "lo_b": TenantClass(priority=0, weight=w_b,
                                             queue_limit=ql)})

        high_prompts = [trng.integers(0, t_cfg.vocab_size, size=p_len)
                        .astype(np.int32) for _ in range(n_high)]
        # Low traffic cycles a small prompt pool: scheduling doesn't
        # care about prompt diversity, and the pool keeps the parity
        # referee to a handful of generate() references (memoized).
        low_pool = [trng.integers(0, t_cfg.vocab_size, size=p_len)
                    .astype(np.int32) for _ in range(8)]
        refs: dict = {}

        def check(h) -> bool:
            key = (h.prompt.tobytes(), h.max_new_tokens)
            if key not in refs:
                refs[key] = np.asarray(generate(
                    t_model, t_params, jnp.asarray(h.prompt[None]),
                    h.max_new_tokens))[0, h.prompt.size:].tolist()
            return h.tokens == refs[key]

        parity_ok = True

        # Phase A: no-load baseline for the high tier's TTFT (one
        # unmeasured warmup request first — compile time is not an SLO).
        eng_a = make_engine()
        warm = eng_a.submit(low_pool[0], t_new, tenant="high")
        eng_a.run_until_complete()
        parity_ok = check(warm) and parity_ok
        base_ttfts = []
        for i, p in enumerate(high_prompts):
            h = eng_a.submit(p, t_new, seed=t_seed + i, tenant="high")
            eng_a.run_until_complete()
            base_ttfts.append(h.token_times[0] - h.submit_time)
            parity_ok = check(h) and parity_ok
        base_p99 = _percentile(base_ttfts, 99)

        # Phase B: overload.  Fresh engine, same (cfg, params) tree —
        # the step programs are already warm through the shared LRU.
        eng = make_engine()
        high_handles: list = []
        low_handles: list = []
        shed = 0
        hi_sub = 0
        low_seed = 0
        high_every = max(phase_steps // n_high, 1)
        steps = 0
        max_steps = 4 * phase_steps + 200  # wedge guard
        while ((steps < phase_steps or hi_sub < n_high
                or eng.slots_in_use or eng.queue_depth)
               and steps < max_steps):
            if steps < phase_steps:
                for name in ("lo_a", "lo_b"):
                    for _ in range(2):  # burst past the bound -> sheds
                        try:
                            low_handles.append(eng.submit(
                                low_pool[low_seed % len(low_pool)],
                                t_new, seed=5_000 + low_seed,
                                tenant=name))
                        except QueueFull:
                            shed += 1
                        low_seed += 1
            if hi_sub < n_high and steps % high_every == 0:
                high_handles.append(eng.submit(
                    high_prompts[hi_sub], t_new, seed=t_seed + hi_sub,
                    tenant="high"))
                hi_sub += 1
            eng.step()
            steps += 1
        wedged = steps >= max_steps
        no_leak = eng.slots_in_use == 0 and eng.queue_depth == 0

        def tier_latency(handles):
            ttfts, gaps = [], []
            for h in handles:
                if not h.token_times:
                    continue
                ttfts.append(h.token_times[0] - h.submit_time)
                prev = h.submit_time
                for t in h.token_times:
                    gaps.append(t - prev)
                    prev = t
            return ttfts, gaps

        hi_ttfts, hi_gaps = tier_latency(high_handles)
        lo_ttfts, lo_gaps = tier_latency(low_handles)
        completed_high = sum(
            h.finish_reason is FinishReason.COMPLETE for h in high_handles)
        completed_low = sum(
            h.finish_reason is FinishReason.COMPLETE for h in low_handles)
        for h in high_handles + low_handles:
            if h.finish_reason is FinishReason.COMPLETE:
                parity_ok = check(h) and parity_ok
        hi_p99 = _percentile(hi_ttfts, 99)
        p99_ok = (completed_high == n_high and hi_p99 is not None
                  and base_p99 is not None and hi_p99 <= base_p99 * bound)
        adm_a = int(eng.tenant_stats["lo_a"]["admitted"])
        adm_b = int(eng.tenant_stats["lo_b"]["admitted"])
        share = adm_a / (adm_a + adm_b) if adm_a + adm_b else None
        share_cfg = w_a / (w_a + w_b)
        fairness_ok = (share is not None
                       and abs(share - share_cfg) <= 0.10)
        emit({
            "metric": TENANCY_METRIC,
            "seed": t_seed,
            "value": round(hi_p99 * 1e3, 3) if hi_p99 else 0.0,
            "unit": "high_tier_overload_ttft_p99_ms",
            "p99_ok": p99_ok,
            "p99_bound": bound,
            "ttft_p99_baseline_ms": (round(base_p99 * 1e3, 3)
                                     if base_p99 else None),
            "ttft_p50_ms_high": round(
                _percentile(hi_ttfts, 50) * 1e3, 3) if hi_ttfts else None,
            "ttft_p50_ms_low": round(
                _percentile(lo_ttfts, 50) * 1e3, 3) if lo_ttfts else None,
            "ttft_p99_ms_low": round(
                _percentile(lo_ttfts, 99) * 1e3, 3) if lo_ttfts else None,
            "p50_token_latency_ms_high": round(
                _percentile(hi_gaps, 50) * 1e3, 3) if hi_gaps else None,
            "p99_token_latency_ms_high": round(
                _percentile(hi_gaps, 99) * 1e3, 3) if hi_gaps else None,
            "p50_token_latency_ms_low": round(
                _percentile(lo_gaps, 50) * 1e3, 3) if lo_gaps else None,
            "p99_token_latency_ms_low": round(
                _percentile(lo_gaps, 99) * 1e3, 3) if lo_gaps else None,
            "fairness_share_measured": (round(share, 3)
                                        if share is not None else None),
            "fairness_share_configured": share_cfg,
            "fairness_ok": fairness_ok,
            "low_admitted_a": adm_a,
            "low_admitted_b": adm_b,
            "shed": shed,
            "preempted": int(eng.stats["preempted"]),
            "deadline_expired": int(eng.stats["deadline_expired"]),
            "high_requests": n_high,
            "completed_high": int(completed_high),
            "completed_low": int(completed_low),
            "steps": steps,
            "wedged": wedged,
            "no_leak": no_leak,
            "parity_ok": parity_ok,
            "queue_limit_low": ql,
            "num_layers": t_cfg.num_layers,
            "d_model": t_cfg.d_model,
            "vocab_size": t_cfg.vocab_size,
            "device_kind": kind,
        })

    def _prefix_engine(cache_blocks: int):
        """Engine for the prefix rows, warmed OFF the clock: two
        sequential identical generations compile prefill/decode/sample
        — and, on the cached engine, the publish program (first
        retirement) and the block-copy-in program (second admission's
        hit).  The warm cache entries and counters are then dropped so
        the measured run starts cold and every hit it records came
        from the measured workload itself."""
        e = Engine(model, params, num_slots=prefix_conc,
                   max_len=cfg.max_seq_len, prefill_chunk=chunk,
                   prefix_cache_blocks=cache_blocks)
        warm = np.arange(2 * chunk, dtype=np.int32) % cfg.vocab_size
        e.generate_many([warm], 2)
        e.generate_many([warm], 2)
        if e.prefix_cache is not None:
            e.prefix_cache.flush()
            for key in ("prefix_lookups", "prefix_hit_tokens",
                        "prefix_published_blocks"):
                e.stats[key] = 0
        return e

    def run_prefix(workload: str) -> None:
        """One prefix-caching row: the IDENTICAL greedy workload through
        a cache-off and a cache-on engine (greedy outputs bit-identical
        either way — the row's own parity_ok double-checks the tests'
        contract), TTFT percentiles for both, and the cache-on engine's
        hit accounting.  ``shared_prefix``: all requests = one long
        system prompt + a short unique tail, submitted as a burst.
        ``multiturn``: ``prefix_users`` conversations of
        ``prefix_turns`` turns; every turn re-sends the whole history
        plus a new user tail, so from turn 2 on the history is a cache
        hit."""
        prng = np.random.default_rng(seed + 3)
        shared = prng.integers(0, cfg.vocab_size,
                               size=prefix_len).astype(np.int32)

        if workload == "shared_prefix":
            reqs = [np.concatenate([shared, prng.integers(
                0, cfg.vocab_size, size=prefix_tail).astype(np.int32)])
                for _ in range(n_requests)]

            def run(e):
                offsets = np.zeros(len(reqs))
                elapsed, _lat, ttfts, handles, _shed = drive(
                    e, offsets, reqs, max_new)
                tokens = sum(len(h.tokens) for h in handles)
                return elapsed, ttfts, tokens, [h.tokens for h in handles]
        else:  # multiturn
            opening = [np.concatenate([shared, prng.integers(
                0, cfg.vocab_size, size=prefix_tail).astype(np.int32)])
                for _ in range(prefix_users)]
            extras = [[prng.integers(0, cfg.vocab_size, size=prefix_tail)
                       .astype(np.int32) for _ in range(prefix_turns - 1)]
                      for _ in range(prefix_users)]

            def run(e):
                ttfts, outputs = [], []
                tokens = 0
                hist = list(opening)
                t0 = time.perf_counter()
                for t in range(prefix_turns):
                    handles = [e.submit(hist[u], max_new, seed=seed + u)
                               for u in range(prefix_users)]
                    e.run_until_complete()
                    for u, h in enumerate(handles):
                        ttfts.append(h.token_times[0] - h.submit_time)
                        tokens += len(h.tokens)
                        outputs.append(h.tokens)
                        if t + 1 < prefix_turns:
                            hist[u] = np.concatenate(
                                [h.result(), extras[u][t]])
                return time.perf_counter() - t0, ttfts, tokens, outputs

        off = _prefix_engine(0)
        off_elapsed, off_ttfts, off_tokens, off_out = run(off)
        on = _prefix_engine(prefix_blocks)
        on_elapsed, on_ttfts, on_tokens, on_out = run(on)
        on_p50 = _percentile(on_ttfts, 50)
        off_p50 = _percentile(off_ttfts, 50)
        emit({
            "metric": PREFIX_METRIC,
            "workload": workload,
            "value": (round(off_p50 / on_p50, 3)
                      if on_p50 and off_p50 else None),
            "unit": "ttft_p50_speedup",
            "ttft_p50_ms": round(on_p50 * 1e3, 3),
            "ttft_p99_ms": round(_percentile(on_ttfts, 99) * 1e3, 3),
            "ttft_p50_off_ms": round(off_p50 * 1e3, 3),
            "ttft_p99_off_ms": round(
                _percentile(off_ttfts, 99) * 1e3, 3),
            "tokens_per_sec": round(on_tokens / on_elapsed, 1)
            if on_elapsed > 0 else None,
            "tokens_per_sec_off": round(off_tokens / off_elapsed, 1)
            if off_elapsed > 0 else None,
            "prefix_hit_tokens": int(on.stats["prefix_hit_tokens"]),
            "prefix_lookups": int(on.stats["prefix_lookups"]),
            "prefix_published_blocks": int(
                on.stats["prefix_published_blocks"]),
            "parity_ok": on_out == off_out,
            "cache_blocks": prefix_blocks,
            "concurrency": prefix_conc,
            "requests": (n_requests if workload == "shared_prefix"
                         else prefix_users * prefix_turns),
            "prefix_len": prefix_len,
            "max_new_tokens": max_new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })

    def run_paged(workload: str) -> None:
        """One paged-vs-copy row: the TRUE paged engine
        (``Engine(kv_pages=N)`` — per-slot block tables into one shared
        page pool, cache hits as table writes, copy-on-write at the
        divergence block) against the dense copy-cache engine
        (``prefix_cache_blocks=N``) at the SAME KV byte budget, on the
        shared-system-prompt workload paging exists for.

        The byte budget is the dense engine's arena: ``dense_slots x
        max_len`` tokens, i.e. ``kv_pages = dense_slots x max_len /
        chunk`` pages (the copy engine additionally keeps its own
        block pool on top — a handicap AGAINST the paged row).  Both
        engines are warmed with one shared-prefix request (compiles
        off the clock AND publishes the prefix), then serve the
        identical burst.  Columns: ``contexts_paged`` /
        ``contexts_dense`` — the peak co-resident in-flight contexts
        each engine sustained (the paged engine runs ``2 x
        dense_slots`` slots and must hold them with ZERO page-pressure
        vacates for ``capacity_ok``); headline ``value`` = their
        ratio, gated at >= PAGED_CAPACITY_BOUND; TTFT p50/p99 for
        both; and the in-bench greedy ``parity_ok`` (paged outputs
        bit-identical to the copy engine's)."""
        prng = np.random.default_rng(seed + 5)
        shared = prng.integers(0, cfg.vocab_size,
                               size=prefix_len).astype(np.int32)
        dense_slots = prefix_conc
        paged_slots = 2 * dense_slots
        n_burst = max(n_requests, 2 * paged_slots)
        reqs = [np.concatenate([shared, prng.integers(
            0, cfg.vocab_size, size=prefix_tail).astype(np.int32)])
            for _ in range(n_burst)]
        pages_per_slot = cfg.max_seq_len // chunk
        kv_pages = dense_slots * pages_per_slot

        def run(e):
            # Warm: compile programs off the clock AND publish the
            # shared prefix, so the measured burst's hits are the
            # steady-state traffic shape (the warm handle's output
            # also rides the parity check).
            warm = e.submit(reqs[0], max_new, seed=seed)
            e.run_until_complete()
            outputs = [warm.tokens]
            handles = [e.submit(p, max_new, seed=seed + 1 + i)
                       for i, p in enumerate(reqs[1:])]
            peak = 0
            while e.slots_in_use or e.queue_depth:
                e.step()
                peak = max(peak, e.slots_in_use)
            outputs += [h.tokens for h in handles]
            ttfts = [h.token_times[0] - h.submit_time for h in handles
                     if h.token_times]
            return outputs, peak, ttfts

        dense = Engine(model, params, num_slots=dense_slots,
                       max_len=cfg.max_seq_len, prefill_chunk=chunk,
                       prefix_cache_blocks=prefix_blocks)
        dense_out, dense_peak, dense_ttfts = run(dense)
        paged = Engine(model, params, num_slots=paged_slots,
                       max_len=cfg.max_seq_len, prefill_chunk=chunk,
                       kv_pages=kv_pages)
        paged_out, paged_peak, paged_ttfts = run(paged)
        paged.check_paged()
        vacates = int(paged.stats["page_pressure_vacates"])
        ratio = paged_peak / dense_peak if dense_peak else None
        capacity_ok = (ratio is not None and vacates == 0
                       and ratio >= PAGED_CAPACITY_BOUND)
        pool = paged.page_pool
        emit({
            "metric": PAGED_METRIC,
            "workload": workload,
            "value": round(ratio, 3) if ratio is not None else None,
            "unit": "co_resident_contexts_vs_dense_at_fixed_pool_bytes",
            "capacity_ok": capacity_ok,
            "capacity_bound": PAGED_CAPACITY_BOUND,
            "contexts_paged": paged_peak,
            "contexts_dense": dense_peak,
            "page_pressure_vacates": vacates,
            "kv_pages": kv_pages,
            "page_tokens": chunk,
            "pool_bytes": kv_pages * pool.page_bytes(),
            "pages_used_end": int(pool.used_pages),
            "prefix_hit_tokens": int(paged.stats["prefix_hit_tokens"]),
            "prefix_lookups": int(paged.stats["prefix_lookups"]),
            "prefix_published_blocks": int(
                paged.stats["prefix_published_blocks"]),
            "ttft_p50_ms": round(_percentile(paged_ttfts, 50) * 1e3, 3),
            "ttft_p99_ms": round(_percentile(paged_ttfts, 99) * 1e3, 3),
            "ttft_p50_copy_ms": round(
                _percentile(dense_ttfts, 50) * 1e3, 3),
            "ttft_p99_copy_ms": round(
                _percentile(dense_ttfts, 99) * 1e3, 3),
            "parity_ok": paged_out == dense_out,
            "dense_slots": dense_slots,
            "paged_slots": paged_slots,
            "requests": n_burst,
            "prefix_len": prefix_len,
            "max_new_tokens": max_new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve_paged", workload, paged.metrics())

    def run_paged_kernel(workload: str) -> None:
        """One gather-free-vs-gather throughput row
        (``serve_paged_kernel``): decode tokens/sec through THREE
        engines over the identical shared-prefix burst at the same KV
        byte budget — dense (no paging), gather-paged
        (``paged_attn='gather'``: PR 13's per-step full-view
        gather→dense-math→scatter), and the gather-free default
        (attention reads K/V through the block table inside the
        contraction, each committed token writes one row of one page).
        Gate ``gather_free_ok`` = gather-free tokens/sec >=
        gather-paged AND ``parity_ok`` (all three engines' greedy
        outputs bit-identical — the perf rework moved bytes, never
        values).  ``SERVE_PAGED_KERNEL_TPS=1`` adds the Pallas-kernel
        engine's tokens/sec as an extra column (opt-in: interpret mode
        on a CPU host measures the interpreter, not the kernel; the
        gate never reads it)."""
        prng = np.random.default_rng(seed + 6)
        shared = prng.integers(0, cfg.vocab_size,
                               size=prefix_len).astype(np.int32)
        # The gather-free advantage is PROPORTIONAL to live context (it
        # removes the stream-every-live-page tax), so this row wants
        # enough co-resident depth to measure it; the override lets the
        # tier-1 smoke run the capacity row small and this row at
        # measurement scale.
        slots = int(os.environ.get("SERVE_PAGED_KERNEL_SLOTS",
                                   prefix_conc))
        kv_pages = slots * (cfg.max_seq_len // chunk)  # = one dense arena
        reqs = [np.concatenate([shared, prng.integers(
            0, cfg.vocab_size, size=prefix_tail).astype(np.int32)])
            for _ in range(2 * slots + 1)]

        # Best-of-N per engine, with the engines' reps INTERLEAVED
        # (rep 0 of all three, then rep 1 of all three, ...): the smoke
        # host has documented double-digit scheduler variance, and
        # back-to-back per-engine blocks would let one load spike sink
        # every rep of whichever engine it landed on — interleaving
        # gives each engine a shot at each quiet window, and best-of-N
        # then measures the engines, not the noise (same rationale as
        # the obs-check row's best-of-N).  The first rep is a DISCARDED
        # warmup (allocator/frequency ramp lands on it, not on either
        # engine's best).  Outputs are asserted identical across reps —
        # reruns through a warm tree are the same math.
        reps = max(1, int(os.environ.get("SERVE_PAGED_REPS", "4")))

        def warm_up(e):
            warm = e.submit(reqs[0], max_new, seed=seed)
            e.run_until_complete()  # compiles + publishes off the clock
            return warm

        def measure_once(e):
            t0 = time.perf_counter()
            handles = [e.submit(p, max_new, seed=seed + 1 + i)
                       for i, p in enumerate(reqs[1:])]
            e.run_until_complete()
            elapsed = time.perf_counter() - t0
            tokens = sum(len(h.tokens) for h in handles)
            tps = tokens / elapsed if elapsed > 0 else None
            return [h.tokens for h in handles], tps

        def engine(**kw):
            return Engine(model, params, num_slots=slots,
                          max_len=cfg.max_seq_len, prefill_chunk=chunk,
                          **kw)

        engines = [engine(),                   # dense baseline
                   engine(kv_pages=kv_pages, paged_attn="gather"),
                   engine(kv_pages=kv_pages)]  # the gather-free default
        warms = [warm_up(e) for e in engines]
        best = [None] * len(engines)
        outs = [None] * len(engines)
        for rep in range(reps + 1):
            for i, e in enumerate(engines):
                rep_outs, tps = measure_once(e)
                rep_outs = [warms[i].tokens] + rep_outs
                assert outs[i] is None or outs[i] == rep_outs
                outs[i] = rep_outs
                if rep == 0:
                    continue  # warmup rep: run, verify outputs, discard
                if tps is not None and (best[i] is None or tps > best[i]):
                    best[i] = tps
        (dense_out, gather_out, free_out) = outs
        (tps_dense, tps_gather, tps_free) = best
        free_eng = engines[2]
        free_eng.check_paged()
        tps_kernel = None
        if os.environ.get("SERVE_PAGED_KERNEL_TPS") == "1":
            k_eng = engine(kv_pages=kv_pages, paged_attn="kernel")
            warm_up(k_eng)  # compile off the clock, like the others
            for rep in range(reps + 1):
                _, tps = measure_once(k_eng)
                if (rep and tps is not None
                        and (tps_kernel is None or tps > tps_kernel)):
                    tps_kernel = tps
        parity_ok = dense_out == gather_out == free_out
        gather_free_ok = (parity_ok and tps_free is not None
                          and tps_gather is not None
                          and tps_free >= tps_gather)
        emit({
            "metric": PAGED_KERNEL_METRIC,
            "workload": workload,
            "value": (round(tps_free / tps_gather, 3)
                      if tps_free and tps_gather else None),
            "unit": "gather_free_tokens_per_sec_vs_gather_paged",
            "gather_free_ok": gather_free_ok,
            "parity_ok": parity_ok,
            "tokens_per_sec_dense": (round(tps_dense, 1)
                                     if tps_dense else None),
            "tokens_per_sec_gather": (round(tps_gather, 1)
                                      if tps_gather else None),
            "tokens_per_sec_gather_free": (round(tps_free, 1)
                                           if tps_free else None),
            "tokens_per_sec_kernel": (round(tps_kernel, 1)
                                      if tps_kernel else None),
            "kv_pages": kv_pages,
            "pool_bytes": kv_pages * free_eng.page_pool.page_bytes(),
            "prefix_hit_tokens": int(
                free_eng.stats["prefix_hit_tokens"]),
            "num_slots": slots,
            "requests": len(reqs),
            "prefix_len": prefix_len,
            "max_new_tokens": max_new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve_paged_kernel", workload, free_eng.metrics())

    def run_paged_kernel_traffic(workload: str) -> None:
        """Kernel-vs-einsum throughput rows per traffic kind (the same
        ``serve_paged_kernel`` metric, distinguished by a ``traffic``
        field): **prefill** (chunked prompt ingestion, one new token —
        the flash-prefill kernel's path), **verify** (k=2 host
        speculation through the multi-token verify-window kernel), and
        **fused** (4-token in-loop decode windows dispatching the
        decode kernel inside the while body).  Each kind runs THREE
        engines over the same over-subscribed shared-prefix burst at
        the same page budget: ``paged_attn='einsum'`` (the bit-exact
        fallback the kernel must beat), ``paged_attn='gather'``
        (PR 13's materialize-then-dense oracle), and
        ``paged_attn='kernel'``.  Over-subscription (2x slots + 1
        requests) retires and re-admits mid-burst, so later admissions
        inherit recycled non-contiguous pages — the parity gate
        (``parity_ok``: all three engines' greedy tokens identical)
        runs over genuinely FRAGMENTED tables.  ``kernel_ok`` folds
        parity with the throughput bar — kernel tokens/sec >= einsum —
        whenever tokens/sec was measured; on a CPU host the kernel
        lowers in interpret mode (timing the interpreter, not the
        kernel), so tokens/sec is only taken on a TPU or under
        ``SERVE_PAGED_KERNEL_TPS=1`` and the CPU smoke gate reads
        parity alone (``value`` stays null: an interpreter's rate is
        never written as a speed)."""
        deep_new = min(max_new, int(
            os.environ.get("SERVE_PAGED_TRAFFIC_NEW", "12")))
        kinds = {
            "prefill": (dict(), 1),
            "verify": (dict(speculate_k=2), deep_new),
            "fused": (dict(decode_fuse=4), deep_new),
        }
        assert set(kinds) == set(SERVE_PAGED_TRAFFIC)
        for traffic in SERVE_PAGED_TRAFFIC:
            # Same isolation contract as the stage dispatch loop: one
            # traffic kind crashing must not cost the remaining kinds.
            try:
                _run_traffic_kind(workload, traffic, *kinds[traffic])
            except Exception as exc:  # noqa: BLE001
                emit({"metric": PAGED_KERNEL_METRIC, "workload": workload,
                      "traffic": traffic,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})

    def _run_traffic_kind(workload, traffic, ekw, new) -> None:
        prng = np.random.default_rng(seed + 7)
        shared = prng.integers(0, cfg.vocab_size,
                               size=prefix_len).astype(np.int32)
        slots = int(os.environ.get("SERVE_PAGED_TRAFFIC_SLOTS",
                                   prefix_conc))
        kv_pages = slots * (cfg.max_seq_len // chunk)  # = one dense arena
        reqs = [np.concatenate([shared, prng.integers(
            0, cfg.vocab_size, size=prefix_tail).astype(np.int32)])
            for _ in range(2 * slots + 1)]
        reps = max(1, int(os.environ.get("SERVE_PAGED_REPS", "4")))
        want_tps = ("TPU" in kind
                    or os.environ.get("SERVE_PAGED_KERNEL_TPS") == "1")

        def engine(impl):
            return Engine(model, params, num_slots=slots,
                          max_len=cfg.max_seq_len,
                          prefill_chunk=chunk, kv_pages=kv_pages,
                          paged_attn=impl, **ekw)

        def warm_up(e):
            warm = e.submit(reqs[0], new, seed=seed)
            e.run_until_complete()  # compiles + publishes off the clock
            return warm

        def measure_once(e):
            t0 = time.perf_counter()
            handles = [e.submit(p, new, seed=seed + 1 + i)
                       for i, p in enumerate(reqs[1:])]
            e.run_until_complete()
            elapsed = time.perf_counter() - t0
            tokens = sum(len(h.tokens) for h in handles)
            tps = tokens / elapsed if elapsed > 0 else None
            return [h.tokens for h in handles], tps

        engines = [engine("einsum"), engine("gather"),
                   engine("kernel")]
        warms = [warm_up(e) for e in engines]
        # The gather oracle runs ONCE — it is a parity referee, not
        # a measured contender.  When tokens/sec is off (CPU smoke)
        # the einsum and kernel engines also run once, for outputs
        # only; when it is on they interleave best-of-N with a
        # discarded warmup rep, same as the gather-free row above.
        timed = [want_tps, False, want_tps]
        outs = [None] * len(engines)
        best = [None] * len(engines)
        for rep in range(reps + 1):
            for i, e in enumerate(engines):
                if rep > 0 and not timed[i]:
                    continue
                rep_outs, tps = measure_once(e)
                rep_outs = [warms[i].tokens] + rep_outs
                assert outs[i] is None or outs[i] == rep_outs
                outs[i] = rep_outs
                if rep == 0:
                    continue  # warmup rep: run, verify, discard
                if tps is not None and (best[i] is None
                                        or tps > best[i]):
                    best[i] = tps
        einsum_out, gather_out, kernel_out = outs
        tps_einsum, _, tps_kernel = best
        parity_ok = einsum_out == gather_out == kernel_out
        kernel_ok = parity_ok and (
            tps_kernel is None
            or (tps_einsum is not None and tps_kernel >= tps_einsum))
        pa = engines[2].metrics().get("paged_attn", {})
        emit({
            "metric": PAGED_KERNEL_METRIC,
            "workload": workload,
            "traffic": traffic,
            "value": (round(tps_kernel / tps_einsum, 3)
                      if tps_kernel and tps_einsum else None),
            "unit": "kernel_tokens_per_sec_vs_einsum_paged",
            "kernel_ok": kernel_ok,
            "parity_ok": parity_ok,
            "tokens_per_sec_einsum": (round(tps_einsum, 1)
                                      if tps_einsum else None),
            "tokens_per_sec_kernel": (round(tps_kernel, 1)
                                      if tps_kernel else None),
            "dispatch": pa.get("dispatch"),
            "fallbacks": pa.get("fallbacks"),
            # the burst's later admissions hit the shared prefix as
            # table writes with COW at the divergence block, so the
            # parity gate covered shared pages, not just private ones
            "prefix_hit_tokens": int(
                engines[2].stats["prefix_hit_tokens"]),
            "speculate_k": ekw.get("speculate_k", 0),
            "decode_fuse": ekw.get("decode_fuse", 1),
            "kv_pages": kv_pages,
            "num_slots": slots,
            "requests": len(reqs),
            "prefix_len": prefix_len,
            "max_new_tokens": new,
            "prefill_chunk": chunk,
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size,
            "device_kind": kind,
        })
        bank_metrics("serve_paged_kernel", f"{workload}:{traffic}",
                     engines[2].metrics())

    def run_disagg(d_seed: int) -> None:
        """Two-process prefill/decode split vs the colocated engine on
        the identical Poisson+burst mixed-tenant workload.  All three
        measurement bodies run as SUBPROCESSES (``--disagg-worker``)
        pinned to CPU, so the baseline and the split are always the
        same platform regardless of what this parent attached — the
        latency ratio the row gates on compares like with like."""
        import socket
        import subprocess
        import tempfile

        # Both latency bounds are generous on purpose.  At the CPU smoke
        # geometry a colocated decode step costs ~2ms and colocated TTFT
        # p99 ~9ms, so every disagg number is dominated by
        # collective-dispatch latency: TTFT pays a full handoff (offer
        # round + page transfer + adopt + first decode, ~100ms of
        # collectives) and every rank-1 token that lands next to a
        # handshake round absorbs tens of ms, putting the ratios around
        # 10-17x (TTFT) and 30-60x (decode gap) no matter how small the
        # model is.  The gates exist to catch order-of-magnitude
        # regressions — a handoff that blocks decode outright, a retry
        # storm stretching gaps to seconds — not to price round latency,
        # which amortizes away at real decode-step costs.
        bound_ttft = float(os.environ.get("DISAGG_TTFT_BOUND", 30.0))
        bound_p99 = float(os.environ.get("DISAGG_P99_BOUND", 100.0))
        script = os.path.abspath(__file__)
        wenv = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}

        def spawn(mode, nproc, port, out):
            return subprocess.Popen(
                [sys.executable, script, "--disagg-worker",
                 f"{mode}:{nproc}:{port}:{out}:{d_seed}"],
                env=wenv, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)

        with tempfile.TemporaryDirectory() as td:
            co_out = os.path.join(td, "colocated.json")
            p = spawn("c", 1, 0, co_out)
            text, _ = p.communicate(timeout=600)
            if p.returncode != 0:
                raise RuntimeError(f"colocated worker rc="
                                   f"{p.returncode}:\n{text[-1500:]}")
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            outs = [os.path.join(td, f"rank{r}.json") for r in range(2)]
            procs = [spawn(str(r), 2, port, outs[r]) for r in range(2)]
            texts = [pr.communicate(timeout=600)[0] for pr in procs]
            for pr, t in zip(procs, texts):
                if pr.returncode != 0:
                    raise RuntimeError(f"disagg worker rc="
                                       f"{pr.returncode}:\n{t[-1500:]}")
            with open(co_out) as f:
                co = json.load(f)
            with open(outs[0]) as f:
                r0 = json.load(f)
            with open(outs[1]) as f:
                r1 = json.load(f)
        # Join on the sender's request id: rank 0 maps workload index ->
        # rid, rank 1 keys adopted outputs by the ticket's rid.
        parity_ok = True
        for i, want in co["tokens"].items():
            rid = r0["rid_map"].get(i)
            if r1["tokens_by_rid"].get(str(rid)) != want:
                parity_ok = False
        split_ok = (r0["staged"] == r0["n_jobs"]
                    and len(r1["tokens_by_rid"]) == r0["n_jobs"])
        no_leak = bool(co["no_leak"] and r0["no_leak"] and r1["no_leak"])
        c_ttft_p99 = _percentile(co["ttfts"], 99)
        d_ttft_p99 = _percentile(r0["ttfts"], 99)
        c_gap_p99 = _percentile(co["gaps"], 99)
        d_gap_p99 = _percentile(r1["gaps"], 99)
        ttft_ok = bool(c_ttft_p99 and d_ttft_p99 is not None
                       and d_ttft_p99 <= bound_ttft * c_ttft_p99)
        p99_ok = bool(c_gap_p99 and d_gap_p99 is not None
                      and d_gap_p99 <= bound_p99 * c_gap_p99)
        pages = int(r1["stats"].get("migrated_in_pages", 0))
        xfer_s = float(r0["spans"].get("migrate_transfer", {})
                       .get("total_s", 0.0))
        emit({
            "metric": DISAGG_METRIC,
            "seed": d_seed,
            "value": (round(xfer_s * 1e6 / pages, 1) if pages else None),
            "unit": "migration_us_per_page",
            "parity_ok": parity_ok,
            "no_leak": no_leak,
            "split_ok": split_ok,
            "ttft_ok": ttft_ok,
            "p99_ok": p99_ok,
            "migrated": int(r1["stats"].get("migrated_in", 0)),
            "migrated_pages": pages,
            "migration_retries": int(
                r0["stats"].get("migration_retries", 0)),
            "quarantined": int(
                r1["stats"].get("quarantined_transfers", 0)),
            "preempted": int(r0["stats"].get("preempted", 0)),
            "ttft_p50_ms": round(
                (_percentile(r0["ttfts"], 50) or 0) * 1e3, 3),
            "ttft_p99_ms": round((d_ttft_p99 or 0) * 1e3, 3),
            "colocated_ttft_p50_ms": round(
                (_percentile(co["ttfts"], 50) or 0) * 1e3, 3),
            "colocated_ttft_p99_ms": round((c_ttft_p99 or 0) * 1e3, 3),
            "decode_gap_p99_ms": round((d_gap_p99 or 0) * 1e3, 3),
            "colocated_decode_gap_p99_ms": round(
                (c_gap_p99 or 0) * 1e3, 3),
            "ttft_bound": bound_ttft,
            "p99_bound": bound_p99,
            "requests": int(os.environ.get("DISAGG_REQUESTS", 6)),
            "burst": int(os.environ.get("DISAGG_BURST", 3)),
            "device_kind": kind,
        })
        bank_metrics("serve_disagg", d_seed, {
            "rank0": {"stats": r0["stats"], "spans": r0["spans"]},
            "rank1": {"stats": r1["stats"], "spans": r1["spans"]}})

    # One level crashing (OOM, transient backend fault) must not cost
    # the remaining rows: every level still prints its row, then the
    # run exits non-zero.
    if disagg_seeds:
        for s in disagg_seeds:
            try:
                run_disagg(s)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": DISAGG_METRIC, "seed": s,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_disagg": results}))
        return
    if tenancy_seeds:
        for s in tenancy_seeds:
            try:
                run_tenancy(s)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": TENANCY_METRIC, "seed": s,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_tenancy": results}))
        return
    if soak_seeds:
        for s in soak_seeds:
            try:
                run_soak(s)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": SOAK_METRIC, "seed": s,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_soak": results}))
        return
    if prefix_workloads:
        for w in prefix_workloads:
            try:
                run_prefix(w)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": PREFIX_METRIC, "workload": w,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_prefix": results}))
        return
    if paged_workloads:
        # SERVE_PAGED_TRAFFIC_ROWS gates the per-traffic kernel rows:
        # "1" (default) emits them after the capacity + gather-free
        # rows, "0" skips them, "only" skips the capacity + gather-free
        # rows instead — the tier-1 smoke runs the two halves at
        # different geometries (the gather-free >= gather margin needs
        # depth; the traffic parity gate holds at any size) without
        # paying for both twice.  A TPU capture leaves it at the
        # default, so one --paged rerun still refills every row.
        traffic_rows = os.environ.get("SERVE_PAGED_TRAFFIC_ROWS", "1")
        for w in paged_workloads:
            if traffic_rows != "only":
                try:
                    run_paged(w)
                except Exception as exc:  # noqa: BLE001
                    emit({"metric": PAGED_METRIC, "workload": w,
                          "error": f"{type(exc).__name__}: {exc}"[:500]})
                try:
                    run_paged_kernel(w)
                except Exception as exc:  # noqa: BLE001
                    emit({"metric": PAGED_KERNEL_METRIC, "workload": w,
                          "error": f"{type(exc).__name__}: {exc}"[:500]})
            if traffic_rows != "0":
                try:
                    run_paged_kernel_traffic(w)
                except Exception as exc:  # noqa: BLE001
                    emit({"metric": PAGED_KERNEL_METRIC, "workload": w,
                          "traffic": "?",
                          "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_paged": results}))
        return
    if sf_pairs:
        # One zero target tree + one zero draft tree for the whole
        # sweep (same program-cache rationale as the spec branch).  The
        # draft is a genuinely smaller model — fewer layers, narrower —
        # sharing the target's vocab, with enough position budget for
        # the fused program's max_len + k scratch eligibility floor.
        zero_params = jax.tree_util.tree_map(lambda x: x * 0, params)
        d_dm = max(dm // 4, 32)
        draft_cfg = GPT2Config(
            vocab_size=cfg.vocab_size,
            max_seq_len=cfg.max_seq_len
            + max(k for _, k, _n in sf_pairs),
            num_layers=max(cfg.num_layers // 3, 1),
            num_heads=max(d_dm // 64, 1),
            d_model=d_dm)
        draft_model_sf = GPT2(draft_cfg)
        zero_draft_params = jax.tree_util.tree_map(
            lambda x: x * 0,
            draft_model_sf.init(jax.random.PRNGKey(seed + 1),
                                jnp.zeros((1, 8), jnp.int32))["params"])
        for name, k, n in sf_pairs:
            try:
                run_spec_fused(name, k, n, draft_model_sf,
                               zero_params, zero_draft_params)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": SPEC_FUSED_METRIC, "config": name,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_spec_fused": results}))
        return
    if fused_ns:
        for n in fused_ns:
            try:
                run_fused(n)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": FUSED_METRIC, "decode_fuse": n,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_fused": results}))
        return
    if spec_ks:
        # One zero tree for the whole sweep: a fresh tree per k would
        # miss the engine's (cfg, params-identity) program cache and
        # re-freeze/re-compile identical decode/prefill programs.
        zero_params = jax.tree_util.tree_map(lambda x: x * 0, params)
        for k in spec_ks:
            try:
                run_spec(k)
            except Exception as exc:  # noqa: BLE001
                emit({"metric": SPEC_METRIC, "speculate_k": k,
                      "error": f"{type(exc).__name__}: {exc}"[:500]})
        write_sidecar()
        print(json.dumps({"serve_spec": results}))
        return
    for c in levels:
        try:
            run_level(c)
        except Exception as exc:  # noqa: BLE001
            emit({"metric": METRIC, "concurrency": c,
                  "error": f"{type(exc).__name__}: {exc}"[:500]})
    write_sidecar()
    print(json.dumps({"serve": results}))


if __name__ == "__main__":
    main()
    # One level crashing never costs the remaining rows, but a run with
    # an error row did not succeed.
    if _FAILED_ROWS:
        raise SystemExit(f"error: {len(_FAILED_ROWS)} serve_bench row(s) "
                         "failed (see the rows carrying \"error\")")
