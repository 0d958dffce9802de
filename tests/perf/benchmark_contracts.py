"""What the benchmark's own tests may say about ``BENCHMARK.json``, and
the copy on which each of them says it a second time.

The lists of ``BENCHMARK.json`` are open: a later PR appends a
configuration, a cell, a per-layer metric, or its cell to the ``workloads``
of metrics that are there, and edits no file.  So a test speaks only of the
entries it knows: that they are present, carry the fields it needs and keep
their order among themselves (:func:`subsequence`); never what a whole
list is, nor what it ends with.  To make a pin fail in the PR that writes
it, every such assertion is a function of a checkout's root and runs on
the tree and on :func:`grown`: a copy with one more configuration, one
more cell (put on EVERY metric's ``workloads``) and one more per-layer
metric appended.
"""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHECKOUTS = ("tree", "grown")
NEW_CELL, NEW_CONFIG, NEW_METRIC = "other.short", "gpt2_other", "steps_total"


def subsequence(part, whole) -> bool:
    """Every name of ``part`` is in ``whole``, in ``part``'s order."""
    rest = iter(whole)
    return all(name in rest for name in part)


def load(root) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def copy_checkout(tmp_path):
    """A copy of the benchmark's files with the program linked beside it."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "tpudp"), root / "tpudp")
    return root


def grown(tmp_path):
    """:func:`copy_checkout` after a later PR: new files and appended
    entries only, no file that was there changed but ``BENCHMARK.json``."""
    root = copy_checkout(tmp_path)
    cfg = json.loads((root / "perf/configs/gpt2_medium.json").read_text())
    cfg["rehearsal"]["n_layer"] = 1
    (root / f"perf/configs/{NEW_CONFIG}.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "perf/traffic/lm_tokens.json").read_text())
    tr["rehearsal"]["per_chip_batch"] = 1
    (root / "perf/traffic/lm_short.json").write_text(json.dumps(tr))
    (root / f"perf/metrics/{NEW_METRIC}.py").write_text(
        "def read(run):\n    return float(run.window['steps'])\n")
    b = load(root)
    b["configs"].append({"name": NEW_CONFIG, "source": "test",
                         "file": f"perf/configs/{NEW_CONFIG}.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": NEW_CELL, "config": NEW_CONFIG,
                           "traffic": "lm_short", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(NEW_CELL)
    b["per_layer"].append({"name": NEW_METRIC, "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "Trainer loop",
                           "moves": "train_throughput_per_chip",
                           "workloads": [NEW_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def checkout(kind: str, tmp_path) -> str:
    """The root of one of :data:`CHECKOUTS`."""
    return ROOT if kind == "tree" else str(grown(tmp_path))
