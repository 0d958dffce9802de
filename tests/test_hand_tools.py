"""The hand tools and referees stand on their own.

The micro-benchmarks (one kernel or collective at a time, rows on stdout) and
the soak referees used to take their sweeps from a gate file in ``tools/``;
that file is gone (PR 31) and each script now owns its defaults.  None of
these seven had a tier-1 test, and an ``ImportError`` is the one fault losing
the gate file could give them, so each is loaded by path, without running
``main``, on the CPU.  The defaults are then held to their script's own
parser, and ``bench_results/`` to the one capture it tracks.
"""

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every script here loads with what the container has; one that needed an
# optional package would be left out, never skipped.
TOOLS = [
    "benchmarks/collective_bench.py",
    "benchmarks/flash_attention_bench.py",
    "benchmarks/grouped_matmul_bench.py",
    "benchmarks/pipeline_bench.py",
    "benchmarks/resilience_bench.py",
    "benchmarks/torch_reference_bench.py",
    "tools/ring_hlo_evidence.py",
]
# flash_attention_bench chooses a platform and turns the compile cache on as
# it is imported, so its case runs in a process of its own.
ISOLATED = {"benchmarks/flash_attention_bench.py"}


def load_by_path(script: str):
    """Execute ``script`` as a module of its own (``main`` does not run:
    the name is not ``__main__``) and check that the retired gate file is
    neither importable nor imported.  ``sys.path`` and ``sys.modules`` are
    left as they were."""
    name = "_hand_tool_" + os.path.basename(script)[:-3]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, script))
    module = importlib.util.module_from_spec(spec)
    path_before = list(sys.path)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    finally:
        del sys.modules[name]
        sys.path[:] = path_before
    assert callable(module.main)
    gone = "tools.bench_gaps"
    assert importlib.util.find_spec(gone) is None and gone not in sys.modules
    return module


@pytest.mark.parametrize(
    "script", TOOLS, ids=[os.path.basename(s)[:-3] for s in TOOLS])
def test_tool_imports_on_its_own(script):
    if script not in ISOLATED:
        load_by_path(script)
        return
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path[:0] = sys.argv[1:3]\n"
         "import test_hand_tools\n"
         "test_hand_tools.load_by_path(sys.argv[3])",
         REPO, os.path.join(REPO, "tests"), script],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


# sweep -> (script, the tuple of default names, the script's own check of one
# name, a name it must refuse)
SWEEPS = {
    "pipeline_bench": (
        "benchmarks/pipeline_bench.py", "PIPELINE_CONFIGS",
        lambda bench, name: bench.parse_config(name), "pp2xdp4"),
    "serve_bench_spec_fused": (
        "benchmarks/serve_bench.py", "SERVE_SPEC_FUSED_CONFIGS",
        lambda bench, name: bench.SPEC_FUSED_NAME.fullmatch(name), "k2"),
}


@pytest.mark.parametrize("sweep", SWEEPS)
def test_default_sweep_is_accepted_by_its_own_parser(sweep):
    script, default, parse, malformed = SWEEPS[sweep]
    bench = load_by_path(script)

    def accepted(name):
        try:
            return bool(parse(bench, name))
        except ValueError:
            return False

    names = getattr(bench, default)
    assert names and all(accepted(name) for name in names), names
    assert not accepted(malformed)


def test_only_the_july_capture_is_tracked_under_bench_results():
    """``perf/run.py`` writes its cache and traces under the ignored
    ``bench_results/*`` rule; the one tracked file is the paper's capture.
    Where git cannot say (a chip-tool copy has no ``.git``; a checkout
    owned by another user is refused), the whitelist of ``.gitignore``
    says what would be tracked."""
    tracked = None
    if os.path.exists(os.path.join(REPO, ".git")):
        proc = subprocess.run(
            ["git", "ls-files", "bench_results"], cwd=REPO,
            capture_output=True, text=True, timeout=60)
        if proc.returncode == 0:
            tracked = proc.stdout.split()
    if tracked is None:
        with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
            lines = [line.strip() for line in f]
        assert "bench_results/*" in lines
        tracked = [line[1:] for line in lines
                   if line.startswith("!bench_results/")]
    assert tracked == ["bench_results/bench.json"]
