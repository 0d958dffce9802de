"""The readers of the engine's exposed clock and of ``step``'s unspanned
time: ``engine_exposed_ms``, ``engine_exposed_dispatch_ms`` and
``engine_unspanned_ms``, from ``Engine.metrics()["stats"]``'s
``exposed_s``, ``exposed_dispatch_s`` and ``step_self_s``.  Each reader on
a hand-made run whose answer can be checked by eye, each returning nothing
where the program gives it nothing to read (the parent of the PR that
brought the counters, ``Engine(obs=False)``), their entries in
``BENCHMARK.json`` on the tree and on a grown copy, and the rehearsal's
line of ``gpt2m.serve_closed``."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.dirname(os.path.abspath(__file__))]

import benchmark_contracts as contracts  # noqa: E402
from perf.harness.cells import load_module  # noqa: E402

SERVE = "gpt2m.serve_closed"
# 20 engine steps of a window: 0.3 s in ``step``, the engine knew the
# device drained for 0.09 s of them (0.015 s of it while handing a
# program over), and 0.002 s of ``step`` lay in none of its phases.
STATS = {"steps": 20, "decode_steps": 20, "step_s": 0.3,
         "fetch_wait_s": 0.15, "first_token_wait_s": 0.01,
         "exposed_s": 0.09, "exposed_dispatch_s": 0.015,
         "step_self_s": 0.002}
# (metric, its counter, what it moves, the reading on STATS)
READERS = [
    ("engine_exposed_ms", "exposed_s", "serve_tokens_per_s", 4.5),
    ("engine_exposed_dispatch_ms", "exposed_dispatch_s",
     "serve_tokens_per_s", 0.75),
    ("engine_unspanned_ms", "step_self_s", "itl_p95_ms", 0.1),
]


def _run(stats):
    return SimpleNamespace(cell=SimpleNamespace(name=SERVE), rehearse=False,
                           window={} if stats is None
                           else {"engine_stats": stats, "num_slots": 64},
                           traced=None, trace_form=None, trace=None)


def _reader(name):
    return load_module("metrics", name).read


@pytest.mark.parametrize("name, _key, _moves, want", READERS)
def test_a_reader_on_a_hand_made_run(name, _key, _moves, want):
    assert _reader(name)(_run(STATS)) == pytest.approx(want)


@pytest.mark.parametrize("name, key, _moves, _want", READERS)
def test_a_reader_returns_nothing_where_the_program_gives_nothing(
        name, key, _moves, _want):
    """The parent's engine (its stats have no such counter), a window
    with no engine stats, a window without a step."""
    read = _reader(name)
    assert read(_run({k: v for k, v in STATS.items() if k != key})) is None
    assert read(_run(None)) is None
    assert read(_run(dict(STATS, steps=0))) is None


def test_the_exposed_parts_stay_inside_the_step():
    """On one run the dispatch share is a part of the exposed time, and
    the unspanned time a part of what ``engine_host_ms`` reads."""
    run = _run(STATS)
    exposed, dispatch, unspanned = (_reader(n)(run) for n, *_ in READERS)
    assert 0 <= dispatch <= exposed
    assert 0 <= unspanned <= _reader("engine_host_ms")(run)


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_three_metrics_are_entries_with_a_file_each(kind, tmp_path):
    """Found by name, appended in this order among themselves, each with
    its unit, direction, source, layer, the metric it moves and its one
    cell among its ``workloads``; whatever else the lists hold is other
    PRs' business (benchmark_contracts.py)."""
    root = contracts.checkout(kind, tmp_path)
    per_layer = {m["name"]: m for m in contracts.load(root)["per_layer"]}
    assert contracts.subsequence([name for name, *_ in READERS], per_layer)
    for name, _key, moves, _want in READERS:
        m = per_layer[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("ms", "lower", "program_span",
                                "Engine scheduler", moves)
        assert SERVE in m["workloads"]
        assert os.path.isfile(os.path.join(root, "perf", "metrics",
                                           name + ".py"))


@pytest.mark.parametrize("kind", contracts.CHECKOUTS)
def test_the_rehearsal_prints_the_three_readings(kind, tmp_path):
    """``--rehearse --trace 1`` of ``gpt2m.serve_closed``: the engine's
    clock runs on any platform, so the three metrics print, the dispatch
    share within the exposed time and the unspanned time within the
    host's share of a step."""
    root = contracts.checkout(kind, tmp_path)
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        [sys.executable, os.path.join(root, "perf", "run.py"), "--workload",
         SERVE, "--seed", "3900000017", "--seconds", "2", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, env=env,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in line["metrics"].items()}
    exposed, dispatch, unspanned = (m[name] for name, *_ in READERS)
    assert 0 < exposed <= m["engine_step_ms"]
    assert 0 <= dispatch <= exposed
    assert 0 <= unspanned < m["engine_host_ms"]
