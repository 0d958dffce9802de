"""tpudp.obs — structured telemetry: recorder ring semantics, overhead
budget, Perfetto export round-trip, Prometheus exposition, zero-sync
device counters, flight-recorder dumps on serve step faults / watchdog
timeouts / training rollbacks, and the lint cleanliness of the obs
layer itself (the telemetry must pass the repo's own static analysis —
the design constraint the whole subsystem is shaped around)."""

import glob
import json
import os
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.small_model import SmallConv
from tpudp import obs
from tpudp.data.cifar10 import _synthetic
from tpudp.data.loader import DataLoader
from tpudp.models.generate import generate
from tpudp.models.gpt2 import GPT2, GPT2Config
from tpudp.serve import Engine
from tpudp.serve.engine import OBS_DEVICE_COUNTERS
from tpudp.serve.faults import FaultySteps
from tpudp.serve.tenancy import TenantClass
from tpudp.train import Trainer, init_state, make_optimizer
from tpudp.utils.watchdog import StepHangError, Watchdog

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- recorder core ----------------------------------------------------


def test_ring_is_bounded_and_drops_oldest():
    rec = obs.Recorder(name="t", capacity=4)
    for i in range(10):
        tok = rec.begin(f"s{i}")
        rec.end(tok)
    snap = rec.snapshot()
    assert len(snap) == 4
    assert [r["name"] for r in snap] == ["s6", "s7", "s8", "s9"]
    # a token the ring lapped is silently dropped, never an error
    rec.end(0)


def test_disabled_recorder_is_noop():
    rec = obs.Recorder(enabled=False)
    tok = rec.begin("x")
    assert tok == obs.NO_SPAN
    rec.end(tok)
    rec.event("e", a=1)
    rec.count("c")
    with rec.span("s"):
        pass
    assert rec.snapshot() == [] and not rec.counters


def test_span_event_counter_semantics():
    rec = obs.Recorder(capacity=16)
    with rec.span("outer", tag="v"):
        rec.event("point", a=1)
        rec.count("tokens", 3)
        rec.count("tokens", 2)
    snap = rec.snapshot()
    kinds = {(r["name"], r["kind"]) for r in snap}
    assert ("outer", "span") in kinds and ("point", "event") in kinds
    outer = next(r for r in snap if r["name"] == "outer")
    assert outer["dur"] is not None and outer["dur"] >= 0.0
    assert outer["fields"] == {"tag": "v"}
    assert rec.counters["tokens"] == 5
    assert rec.summary()["outer"]["count"] == 1
    # last completed record is the span (it closed after the event)
    assert rec.last_span()["name"] == "outer"


def test_open_span_snapshot_and_nesting():
    rec = obs.Recorder(capacity=8)
    a = rec.begin("a")
    b = rec.begin("b")
    rec.end(b)
    snap = {r["name"]: r for r in rec.snapshot()}
    assert snap["a"]["dur"] is None          # still open
    assert snap["b"]["dur"] is not None
    rec.end(a)
    assert {r["name"]: r for r in rec.snapshot()}["a"]["dur"] is not None


def test_overhead_budget_for_hot_path_api():
    """The allocation-free begin/end pair must cost microseconds — the
    budget that makes leaving spans ON in production (and inside the
    tier-1 engines) a non-decision.  Generous bound: 50us/pair mean
    over 20k pairs on an arbitrarily-loaded CI host (measured ~1-2us)."""
    rec = obs.Recorder(capacity=1024)
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        rec.end(rec.begin("hot"))
    per_pair = (time.perf_counter() - t0) / n
    assert per_pair < 50e-6, f"begin/end pair cost {per_pair * 1e6:.1f}us"


def test_totals_survive_a_lapped_ring_and_are_monotone():
    """``summary()`` is a set of counters: every closed span counts,
    lapped out of the ring or not, so a later snapshot is never below
    an earlier one (a window delta of it means something); ``clear()``
    is the one reset."""
    rec = obs.Recorder(name="t", capacity=4)
    seen = []
    for i in range(25):
        tok = rec.begin("a" if i % 5 else "b")
        rec.end(tok)
        seen.append(rec.summary())
    assert len(rec.snapshot()) == 4  # the ring lapped six times over
    assert seen[-1]["a"]["count"] == 20 and seen[-1]["b"]["count"] == 5
    for earlier, later in zip(seen, seen[1:]):
        for name, slot in earlier.items():
            assert later[name]["count"] >= slot["count"]
            assert later[name]["total_s"] >= slot["total_s"]
    # the ring's survivors are a part of the total, never more than it
    ring_a = sum(r["dur"] for r in rec.snapshot() if r["name"] == "a")
    assert 0.0 <= ring_a <= seen[-1]["a"]["total_s"]
    # an open span, a lapped token and an event add nothing
    stale = rec.begin("open")
    for _ in range(4):
        rec.end(rec.begin("a"))
    rec.end(stale)
    rec.event("point")
    assert set(rec.summary()) == {"a", "b"}
    rec.clear()
    assert rec.summary() == {}


def test_obs_never_imports_jax_at_module_level():
    """The recorder finds the profiler through an already-imported jax
    (``sys.modules``) and no module of the package imports it at its
    top level: stdlib-only tooling can load ``tpudp/obs`` files."""
    import ast

    for path in glob.glob(os.path.join(ROOT, "tpudp", "obs", "*.py")):
        for node in ast.parse(open(path).read()).body:
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            assert not any(n == "jax" or n.startswith("jax.")
                           for n in names), (path, names)


def test_spans_reach_the_profilers_host_plane(tmp_path):
    """While a JAX profiler session captures, a begin/end span is also
    a ``tpudp.<recorder>.<span>`` TraceAnnotation on the trace's clock,
    children inside their parent; outside a session nothing is
    annotated."""
    from jax.profiler import ProfileData

    rec = obs.Recorder(name="serve")
    rec.end(rec.begin("before"))          # no session: ring only
    with jax.profiler.trace(str(tmp_path)):
        outer = rec.begin("step")
        inner = rec.begin("fetch")
        time.sleep(0.002)
        rec.end(inner)
        rec.end(outer)
    rec.end(rec.begin("after"))
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    events = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("tpudp."):
                        events[ev.name] = (ev.start_ns,
                                           ev.start_ns + ev.duration_ns)
    assert set(events) == {"tpudp.serve.step", "tpudp.serve.fetch"}
    (s0, s1), (f0, f1) = events["tpudp.serve.step"], events["tpudp.serve.fetch"]
    assert s0 <= f0 and f1 <= s1 and f1 - f0 >= 1e6  # ns: the 2 ms sleep
    # the recorder's own view is unchanged by the session
    assert {"before", "step", "fetch", "after"} == set(rec.summary())


# -- exports -----------------------------------------------------------


def test_chrome_trace_schema_round_trip():
    """to_chrome_trace -> json -> spans_from_chrome_trace is the
    identity on (name, kind, t0, dur, fields) — the Perfetto schema
    can't drift from what the parser (and the UI) reads."""
    rec = obs.Recorder(name="rt", capacity=8)
    with rec.span("win", idx=3):
        rec.event("commit", token=7)
    rec.count("tokens", 11)
    open_tok = rec.begin("open")  # still-open span survives the trip
    trace = json.loads(json.dumps(obs.to_chrome_trace(rec, pid=2)))
    back = obs.spans_from_chrome_trace(trace)
    orig = rec.snapshot()
    assert len(back) == len(orig)
    for o, b in zip(orig, back):
        assert b["name"] == o["name"] and b["kind"] == o["kind"]
        assert b["t0"] == pytest.approx(o["t0"], abs=1e-9)
        if o["kind"] == "span":
            if o["dur"] is None:
                assert b["dur"] is None
            else:
                assert b["dur"] == pytest.approx(o["dur"], abs=1e-9)
        assert b.get("fields") == o.get("fields")
    assert [(ev["name"], ev["args"]["value"])
            for ev in trace["traceEvents"] if ev["ph"] == "C"] == [
        ("tokens", 11)]
    # every event is well-formed trace_event JSON
    for ev in trace["traceEvents"]:
        assert ev["ph"] in ("X", "i", "C") and "ts" in ev
    rec.end(open_tok)


def test_prometheus_text_flattens_numeric_leaves():
    text = obs.prometheus_text(
        {"stats": {"tokens": 42, "ok": True},
         "nested": {"deep": {"v": 1.5}},
         "big": 123456789,  # counters keep full precision (no %g)
         "skipped": "a string", "also_skipped": None})
    assert "tpudp_big 123456789\n" in text
    assert "tpudp_stats_tokens 42\n" in text
    assert "tpudp_stats_ok 1\n" in text
    assert "tpudp_nested_deep_v 1.5\n" in text
    assert "# TYPE tpudp_stats_tokens gauge" in text
    assert "skipped" not in text


def test_metrics_server_serves_live_snapshot():
    state = {"v": 1}
    srv = obs.MetricsServer(0, lambda: {"counter": state["v"]})
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "tpudp_counter 1" in body
        state["v"] = 2  # supplier is called per request — live values
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert "tpudp_counter 2" in body
    finally:
        srv.close()


# -- reference-parity window formatter --------------------------------


def test_reference_window_lines_are_byte_exact():
    """The span-backed formatter must print the reference's strings
    byte-for-byte (src/Part 2a/main.py:100-112 cadence) — the window
    print refactor is parity-neutral by construction."""
    assert obs.reference_window_lines(
        40, 1.25, 4.0, 20, first_window=False) == [
        "Training loss after 40 iterations is 1.25",
        "Average Pass time in iter 40 is 0.2",
    ]
    assert obs.reference_window_lines(
        20, 2.5, 4.0, 20, first_window=True) == [
        "Training loss after 20 iterations is 2.5",
    ]
    assert obs.reference_window_lines(
        40, 1.0, 4.0, 20, fwd_t=2.0, bwd_t=6.0, first_window=False) == [
        "Training loss after 40 iterations is 1.0",
        "Forward Pass time in iter 40 is 0.1",
        "Backward Pass time in iter 40 is 0.3",
        "Average Pass time in iter 40 is 0.2",
    ]


def test_one_timing_api_reexports():
    """The profiler capture keeps its old import path, resolving to the
    SAME object as the obs package's; the helpers nothing read (PR 26)
    are gone from both."""
    import tpudp.utils
    import tpudp.utils.profiler as profiler

    assert profiler.trace is obs.trace
    for gone in ("StepTimer", "step_annotation", "snapshot_json",
                 "counters_from_chrome_trace", "list_dumps"):
        assert not hasattr(obs, gone) and gone not in obs.__all__
        assert not hasattr(profiler, gone) and not hasattr(tpudp.utils, gone)
    with pytest.raises(ImportError):
        import tpudp.utils.timing  # noqa: F401


# -- flight recorder ---------------------------------------------------


def test_flight_dump_and_merge(tmp_path):
    rec = obs.Recorder(name="f")
    with rec.span("region"):
        rec.event("ev", k=1)
    fl = obs.FlightRecorder(rec, str(tmp_path), component="t")
    p1 = fl.dump("first", extra={"why": "test"})
    p2 = fl.dump("second")
    assert p1 and p2 and fl.dumps == 2
    doc = json.load(open(p1))
    assert doc["reason"] == "first" and doc["extra"] == {"why": "test"}
    assert any(s["name"] == "region" for s in doc["spans"])
    assert doc["last_span"] is not None
    merged = obs.merge_dumps(str(tmp_path))
    mdoc = json.load(open(merged))
    assert mdoc["merged"] == 2
    assert [r["reason"] for r in mdoc["records"]] == ["first", "second"]
    # single-process coordinated merge degenerates to the local merge
    assert obs.coordinated_merge(str(tmp_path)) == merged


def test_flight_disabled_without_directory(monkeypatch):
    monkeypatch.delenv(obs.FLIGHT_DIR_ENV, raising=False)
    fl = obs.FlightRecorder(obs.Recorder(), None)
    assert not fl.enabled and fl.dump("x") is None
    monkeypatch.setenv(obs.FLIGHT_DIR_ENV, "/tmp/some-dir")
    assert obs.resolve_flight_dir(None) == "/tmp/some-dir"
    assert obs.resolve_flight_dir("/explicit") == "/explicit"


# -- serve engine integration -----------------------------------------


@pytest.fixture(scope="module")
def lm():
    cfg = GPT2Config(vocab_size=64, max_seq_len=64, num_layers=2,
                     num_heads=2, d_model=32)
    model = GPT2(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return model, params


PROMPTS = [np.arange(1, 9, dtype=np.int32),
           np.arange(3, 11, dtype=np.int32)]


def test_engine_device_counters_match_host_stats(lm):
    """The zero-sync device counters must agree with the host-side
    accounting they mirror: on a pure greedy decode run, the device
    'tokens' counter is exactly stats['tokens'] minus the first tokens
    (those ride the prefill's sample_row, which the device counters
    deliberately exclude), and slot_steps matches active_slot_steps."""
    model, params = lm
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    eng.generate_many(PROMPTS, 8)
    m = eng.metrics()
    dev = m["device_counters"]
    assert set(dev) == set(OBS_DEVICE_COUNTERS)
    assert dev["tokens"] == m["stats"]["tokens"] - len(PROMPTS)
    assert dev["slot_steps"] == m["stats"]["active_slot_steps"]
    assert dev["steps"] == m["stats"]["decode_steps"]
    assert dev["eos_exits"] == 0.0
    # spans cover the whole device-call taxonomy of this run
    assert {"prefill", "sample", "decode"} <= set(m["spans"])
    # lifecycle events landed (admit + finish per request)
    names = [r["name"] for r in eng.obs.snapshot() if r["kind"] == "event"]
    assert names.count("admit") == 2 and names.count("finish") == 2


def _children_inside(snapshot, parent="step"):
    """Every span that is not ``parent`` lies inside one ``parent``."""
    spans = [r for r in snapshot if r["kind"] == "span" and r["dur"] is not None]
    outer = [(r["t0"], r["t0"] + r["dur"]) for r in spans if r["name"] == parent]
    return all(any(a <= r["t0"] and r["t0"] + r["dur"] <= b for a, b in outer)
               for r in spans if r["name"] != parent)


def test_engine_step_phase_spans_and_seconds_counters(lm):
    """One Engine.step leaves the phase spans, children enclosed by
    ``step``; the stats' seconds counters are the spans' totals, and the
    blocked time is a part of the step."""
    model, params = lm
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    h = eng.submit(PROMPTS[0], 4)
    eng.step()   # admit + the whole 8-token prompt + its first token + decode
    snap = eng.obs.snapshot()
    names = [r["name"] for r in snap if r["kind"] == "span"]
    assert names[0] == "step" and names.count("step") == 1
    assert {"admit", "prefill", "sample", "first_token_wait", "decode",
            "fetch", "commit"} <= set(names)
    assert names.count("commit") == 2  # the first token's, the decode's
    assert _children_inside(snap)
    assert len(h.tokens) == 2
    eng.run_until_complete()
    m = eng.metrics()
    st, spans = m["stats"], m["spans"]
    assert spans["step"]["count"] == st["steps"]
    assert st["step_s"] == spans["step"]["total_s"] > 0.0
    assert st["fetch_wait_s"] == spans["fetch"]["total_s"]
    assert st["dispatch_s"] == pytest.approx(sum(
        spans[n]["total_s"] for n in ("prefill", "sample", "decode")))
    assert st["step_s"] >= st["fetch_wait_s"] + st["first_token_wait_s"]
    assert st["step_s"] >= (st["admit_s"] + st["dispatch_s"] + st["commit_s"]
                            + st["fetch_wait_s"] + st["first_token_wait_s"]
                            + st["pages_s"])
    assert st["pages_s"] == 0.0  # an unpaged engine backs no pages
    paged = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                   kv_pages=8)
    paged.generate_many(PROMPTS, 4)
    assert paged.metrics()["spans"]["pages"]["count"] >= 1
    assert _children_inside(paged.obs.snapshot())


def test_engine_span_totals_are_monotone_past_the_ring(lm):
    """A run several times longer than the ring: every total in
    ``metrics()["spans"]`` and every seconds counter only grows."""
    model, params = lm
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    eng.obs = obs.Recorder(name="serve", capacity=16)
    handles = [eng.submit(p, 12) for p in PROMPTS * 2]
    prev, laps = None, 0
    while not all(h.done for h in handles):
        eng.step()
        m = eng.metrics()
        if prev is not None:
            for name, slot in prev["spans"].items():
                assert m["spans"][name]["count"] >= slot["count"]
                assert m["spans"][name]["total_s"] >= slot["total_s"]
            for key in ("step_s", "fetch_wait_s", "dispatch_s", "commit_s"):
                assert m["stats"][key] >= prev["stats"][key]
        prev = m
    assert eng.obs._seq > 5 * eng.obs.capacity  # it did lap
    assert prev["spans"]["step"]["count"] == prev["stats"]["steps"]
    assert prev["spans"]["fetch"]["count"] == prev["stats"]["decode_steps"]


def test_ttft_is_split_at_the_request_stamps(lm):
    """A request that holds a slot while another prompt prefills: its
    time to first token is queue + prefill wait + own prefill, stamped
    on the clock submit_time and token_times use; the engine's counters
    add the same numbers up and the finish event carries them."""
    model, params = lm
    eng = Engine(model, params, num_slots=2, max_len=64, prefill_chunk=8)
    long_prompt = np.arange(1, 25, dtype=np.int32)     # three chunks
    first = eng.submit(long_prompt, 3)
    second = eng.submit(PROMPTS[0], 3)                 # waits behind it
    eng.run_until_complete()
    st = eng.metrics()["stats"]
    assert st["first_tokens"] == 2
    total = queued = waited = 0.0
    for r in (first, second):
        ttft, q, w = r.ttft_split()
        assert r.submit_time <= r.admit_time <= r.first_chunk_time \
            <= r.token_times[0]
        own = r.token_times[0] - r.first_chunk_time
        assert ttft == pytest.approx(q + w + own, abs=1e-9)
        total, queued, waited = total + ttft, queued + q, waited + w
    assert st["ttft_s"] == pytest.approx(total)
    assert st["ttft_queue_s"] == pytest.approx(queued)
    assert st["ttft_prefill_wait_s"] == pytest.approx(waited)
    # both were admitted in the same step; the second then held its slot
    # for the first's three chunks
    assert second.ttft_split()[2] > first.ttft_split()[2]
    assert second.first_chunk_time > first.token_times[0]
    done = {r["fields"]["rid"]: r["fields"] for r in eng.obs.snapshot()
            if r["name"] == "finish"}
    for r in (first, second):
        ttft, q, w = r.ttft_split()
        assert (done[r.id]["ttft_s"], done[r.id]["ttft_queue_s"],
                done[r.id]["ttft_prefill_wait_s"]) == (ttft, q, w)
    # a third request queues for a slot: its queue share is not zero
    eng2 = Engine(model, params, num_slots=1, max_len=64, prefill_chunk=8)
    a = eng2.submit(PROMPTS[0], 6)
    b = eng2.submit(PROMPTS[1], 2)
    eng2.run_until_complete()
    assert b.ttft_split()[1] > a.ttft_split()[0] > a.ttft_split()[1]


def test_a_preempted_request_counts_one_first_token(lm):
    """A low-tier request evicted after its first token and resumed
    (bit-exact re-prefill of prompt + tokens) commits no second "first"
    token: ``first_tokens`` counts requests, not prefills."""
    model, params = lm
    eng = Engine(model, params, num_slots=1, max_len=64, prefill_chunk=8,
                 tenants={"high": TenantClass(priority=1),
                          "low": TenantClass(priority=0)})
    low = eng.submit(PROMPTS[0], 8, tenant="low")
    for _ in range(3):
        eng.step()
    assert len(low.tokens) >= 2 and not low.done
    stamps = (low.admit_time, low.first_chunk_time)
    high = eng.submit(PROMPTS[1], 3, tenant="high")
    eng.run_until_complete()
    assert low.preemptions == 1 and low.ok and high.ok
    st = eng.metrics()["stats"]
    assert st["first_tokens"] == 2 and st["admitted"] == 3
    assert (low.admit_time, low.first_chunk_time) == stamps  # the first grant


def test_engine_obs_off_is_inert_and_parity_neutral(lm):
    model, params = lm
    ref = [np.asarray(generate(model, params, jnp.asarray(p[None]), 8))[0]
           for p in PROMPTS]
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 obs=False)
    outs = eng.generate_many(PROMPTS, 8)
    for o, r in zip(outs, ref):
        assert np.array_equal(o, r)
    on = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8)
    for o, r in zip(on.generate_many(PROMPTS, 8), outs):
        assert np.array_equal(o, r)  # bit-identical with the recorder on
    assert eng.obs.snapshot() == []
    # the seconds counters and the TTFT split come from the recorder:
    # off, the keys are absent (a reader then returns nothing)
    from tpudp.serve.engine import OBS_PHASE_SECONDS
    new = {*OBS_PHASE_SECONDS, "first_tokens", "ttft_s", "ttft_queue_s",
           "ttft_prefill_wait_s"}
    assert new <= set(on.metrics()["stats"])
    assert not new & set(eng.metrics()["stats"])
    assert eng.metrics()["spans"] == {}
    # device counters still accumulate (they ride the programs, not the
    # host recorder) — metrics() stays truthful either way
    assert eng.metrics()["device_counters"]["tokens"] > 0


def test_fused_window_counts_eos_exit_on_device(lm):
    """Only the fused loop knows per-slot eos ids on device — its
    eos_exits counter must record an in-window EOS exit."""
    model, params = lm
    probe = Engine(model, params, num_slots=1, max_len=32,
                   prefill_chunk=8)
    toks = probe.generate_many([PROMPTS[0]], 6)[0][PROMPTS[0].size:]
    # A token first produced by DECODE (never earlier, and not the
    # prefill sample), so the exit happens inside a window.  Chosen by
    # that property, not by position: which tokens a random tiny model
    # emits moves with the installed XLA (under jax 0.9.0 toks[2] repeats
    # the prefill sample and EOS fired before any window ran).
    eos = next(int(t) for i, t in enumerate(toks)
               if i >= 1 and t not in toks[:i])
    eng = Engine(model, params, num_slots=1, max_len=32, prefill_chunk=8,
                 decode_fuse=4)
    h = eng.submit(PROMPTS[0], 6, eos_id=eos)
    eng.run_until_complete()
    assert h.finish_reason.value == "eos"
    assert eng.metrics()["device_counters"]["eos_exits"] == 1.0


def test_engine_step_fault_dumps_flight_record(tmp_path, lm):
    """An injected device-step fault (tpudp.serve.faults) must leave a
    black box: containment dumps the ring, and the dump's span timeline
    names the failing device call."""
    model, params = lm
    hook = FaultySteps(fail_at={5}, kind="decode")
    eng = Engine(model, params, num_slots=2, max_len=32, prefill_chunk=8,
                 step_fault_hook=hook, flight_dir=str(tmp_path))
    outs = eng.generate_many(PROMPTS, 8)
    assert hook.fired and eng.stats["step_failures"] == 1
    # requeue-once containment: outputs still bit-exact
    ref = [np.asarray(generate(model, params, jnp.asarray(p[None]), 8))[0]
           for p in PROMPTS]
    for o, r in zip(outs, ref):
        assert np.array_equal(o, r)
    dumps = glob.glob(os.path.join(str(tmp_path), "flightrec-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "step_failure"
    assert "InjectedFault" in doc["extra"]["error"]
    # the failing region is the LAST span in the timeline (the decode
    # call the fault landed in), and the containment event follows
    span_names = [s["name"] for s in doc["spans"]]
    assert "decode" in span_names
    assert span_names[-1] == "containment"
    assert eng.metrics()["flight_dumps"] == 1


def test_serve_watchdog_timeout_names_region_and_dumps(tmp_path, lm):
    """The serve-step-timeout acceptance path: a wedged decode call is
    killed by the watchdog, the StepHangError names the armed region
    ('decode') + arm timing, and the flight record lands BEFORE the
    engine's containment handles the hang."""
    from tpudp.serve.faults import SlowSteps

    model, params = lm
    # Warm the step programs first (shared through the per-(cfg,
    # params) ProgramCache): a cold compile inside the tight scoped
    # budget would read as a hang — the real deployments arm the
    # watchdog around warm engines.
    Engine(model, params, num_slots=1, max_len=32,
           prefill_chunk=8).generate_many(PROMPTS[:1], 2)
    wd = Watchdog(timeout_s=0.2, kill=False, poll_s=0.02).start()
    try:
        eng = Engine(model, params, num_slots=1, max_len=32,
                     prefill_chunk=8, watchdog=wd, step_timeout_s=0.2,
                     step_fault_hook=SlowSteps({4}, 0.8, kind="decode"),
                     flight_dir=str(tmp_path))
        assert wd.flight is eng.flight  # engine claimed the watchdog
        eng.generate_many(PROMPTS[:1], 8)
        # the hang was contained (requeued); the black box must exist
        assert eng.stats["step_failures"] >= 1
        dumps = sorted(glob.glob(
            os.path.join(str(tmp_path), "flightrec-*.json")))
        reasons = [json.load(open(p))["reason"] for p in dumps]
        assert any(r.startswith("watchdog_timeout") for r in reasons)
        wd_doc = json.load(open(dumps[reasons.index(next(
            r for r in reasons if r.startswith("watchdog_timeout")))]))
        assert wd_doc["extra"]["region"] == "decode"
        assert wd_doc["extra"]["armed_for_s"] is not None
        assert wd.last_hang["region"] == "decode"
    finally:
        wd.stop()


def test_watchdog_hang_error_carries_region_and_last_span():
    rec = obs.Recorder(name="w")
    fl = obs.FlightRecorder(rec, None)  # disabled: message still works
    wd = Watchdog(timeout_s=0.1, kill=False, poll_s=0.02,
                  flight=fl).start()
    try:
        done = rec.begin("healthy_step")
        rec.end(done)
        with wd.step(name="wedged_collective"):
            time.sleep(0.4)
        with pytest.raises(StepHangError) as ei:
            with wd.step(name="next"):
                pass
        msg = str(ei.value)
        assert "wedged_collective" in msg
        assert "healthy_step" in msg  # last completed span
        assert ei.value.hang["region"] == "wedged_collective"
    finally:
        wd.stop()


# -- trainer integration ----------------------------------------------


def _tiny_loader():
    return DataLoader(_synthetic(64, seed=3), 16, train=True, seed=2,
                      backend="numpy")


def test_trainer_metrics_and_grad_norm():
    tr = Trainer(SmallConv(), None, "none", spmd_mode="single",
                 log_every=2, log_fn=lambda s: None,
                 track_grad_norm=True)
    tr.train_epoch(_tiny_loader(), 0)
    m = tr.metrics()
    assert m["step"] == 4
    assert m["grad_norm_mean"] > 0 and m["grad_norm_rms"] > 0
    assert m["last_window_loss"] is not None
    assert {"train.window", "train.dispatch", "train.data",
            "train.window_barrier"} <= set(m["spans"])
    assert m["counters"]["train.windows"] == 2
    assert m["counters"]["train.samples"] == 64


def test_track_grad_norm_off_adds_no_pytree_leaf():
    """The default TrainState layout is byte-for-byte pre-obs: the
    obs_norms field contributes NO leaf unless explicitly enabled —
    checkpoints, shardings, and fingerprints are unchanged."""
    tx = make_optimizer()
    st = init_state(SmallConv(), tx)
    st_on = init_state(SmallConv(), tx, track_grad_norm=True)
    assert st.obs_norms is None
    assert len(jax.tree.leaves(st_on)) == len(jax.tree.leaves(st)) + 1


def test_training_rollback_dumps_flight_record(tmp_path):
    """The training-rollback acceptance path: a NaN window rolls back
    under the supervisor and the flight record lands, its ring carrying
    the window timeline plus the typed resilience event."""
    from tpudp.data.cifar10 import _synthetic as _syn
    from tpudp.resilience import ResiliencePolicy
    from tpudp.training_faults import CorruptingLoader

    flight = tmp_path / "flight"
    ckpt = tmp_path / "ckpt"
    tr = Trainer(SmallConv(), None, "none", spmd_mode="single",
                 log_every=2, log_fn=lambda s: None,
                 flight_dir=str(flight))
    loader = CorruptingLoader(
        DataLoader(_syn(64, seed=3), 16, train=True, seed=2,
                   backend="numpy"), nan_at={5})
    tr.fit(loader, epochs=2,
           resilience=ResiliencePolicy(checkpoint_dir=str(ckpt)))
    assert tr.stats["rollbacks"] == 1
    dumps = glob.glob(os.path.join(str(flight), "flightrec-*.json"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "rollback"
    assert "FloatingPointError" in doc["extra"]["error"]
    names = [s["name"] for s in doc["spans"]]
    assert "train.window" in names
    # the recovery event stream is mirrored into the same ring
    post = [r["name"] for r in tr.obs.snapshot()]
    assert "resilience.rollback" in post


def test_coordinated_rollback_dumps_and_merges(tmp_path, monkeypatch):
    """The VOTED recovery path banks a black box on every live host and
    rank 0 merges — exercised through the Supervisor's coordinated seam
    with the cross-host protocol monkeypatched to its single-host
    identities (the same seam-testing pattern as the PR 7 walk tests);
    the real gather ride-along is covered by the slow pod suite."""
    from tpudp.resilience import (OUTCOME_DIVERGENCE, ResiliencePolicy,
                                  Supervisor)
    from tpudp.utils.checkpoint import save_checkpoint

    flight = tmp_path / "flight"
    ckpt = tmp_path / "ckpt"
    tr = Trainer(SmallConv(), None, "none", spmd_mode="single",
                 log_every=2, log_fn=lambda s: None,
                 flight_dir=str(flight))
    save_checkpoint(os.path.join(str(ckpt), "step_0"), tr.state)
    sup = Supervisor(tr, ResiliencePolicy(checkpoint_dir=str(ckpt)))
    sup._per_epoch = 4
    sup._multihost = True  # exercise the coordinated arm single-process
    monkeypatch.setattr(sup, "_assert_replicas_agree", lambda: None)
    epoch, skip = sup._coordinated_recover(
        OUTCOME_DIVERGENCE, FloatingPointError("nan window"))
    assert (epoch, skip) == (0, 0)
    assert tr.stats["rollbacks"] == 1
    dumps = glob.glob(os.path.join(
        str(flight), "flightrec-*coordinated_divergence*"))
    assert len(dumps) == 1
    doc = json.load(open(dumps[0]))
    assert doc["extra"]["worst"] == "divergence"
    assert "FloatingPointError" in doc["extra"]["error"]
    # rank 0 merged the per-host dumps after the recovery
    merged = os.path.join(str(flight), "flightrec-merged.json")
    assert os.path.exists(merged)
    assert json.load(open(merged))["merged"] == 1


# -- the telemetry layer passes its own static analysis ---------------


def test_obs_package_lints_clean():
    """The satellite pin: tpudp.obs adds ZERO findings — the telemetry
    passes the same hazard rules (host-sync on hot paths included) it
    was designed around."""
    from tpudp.analysis import lint_paths

    findings, errors = lint_paths(["tpudp/obs"], ROOT)
    assert errors == []
    assert findings == [], "\n".join(f.render() for f in findings)


