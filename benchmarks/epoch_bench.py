"""Real-training epoch throughput — the input pipeline included.

bench.py measures the fused train step with one resident device batch; the
reference's actual measured regime is epoch wall time with the data
pipeline in the loop (``/root/reference/src/Part 2a/main.py:65-67``).
This bench runs the real trainer (``src/Part 2b/main.py``: host loader +
native augment + device prefetch + fused step) for EPOCHS epochs of
synthetic data on whatever device is attached and reports the LAST
epoch's throughput (first epoch pays compile), next to bench.py's
resident-batch number so the input-pipeline gap is quantified
(VERDICT r2 #3).

One JSON line on stdout; the TPU watcher redirects it to
bench_results/epoch.json.  Env knobs: EPOCH_SAMPLES (25600), EPOCH_BATCH
(256), EPOCH_EPOCHS (3), EPOCH_PLATFORM (cpu smoke mode),
EPOCH_TIMEOUT (1200s).
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

METRIC = "vgg11_epoch_images_per_sec"


def _bench_resident_ips() -> float | None:
    """bench.py's freshest resident-batch images/sec for the gap
    comparison (same reader the watcher's gates use)."""
    try:
        from tools.bench_gaps import rows_with_history

        best = None
        for r in rows_with_history(
                os.path.join(REPO, "bench_results", "bench.json")):
            if (r.get("metric") == "vgg11_cifar10_images_per_sec_per_chip"
                    and "error" not in r and r.get("value", 0) > 0):
                best = r
        return best["value"] if best else None
    except Exception:  # noqa: BLE001
        return None


def main() -> None:
    samples = int(os.environ.get("EPOCH_SAMPLES", 25600))
    batch = int(os.environ.get("EPOCH_BATCH", 256))
    epochs = int(os.environ.get("EPOCH_EPOCHS", 3))
    timeout = float(os.environ.get("EPOCH_TIMEOUT", 1200))

    with tempfile.TemporaryDirectory() as td:
        jsonl = os.path.join(td, "metrics.jsonl")
        cmd = [sys.executable, os.path.join(REPO, "src", "Part 2b",
                                            "main.py"),
               "--synthetic-train-size", str(samples),
               "--synthetic-test-size", str(batch),
               "--batch-size", str(batch),
               "--epochs", str(epochs),
               "--metrics-jsonl", jsonl]
        if os.environ.get("EPOCH_PLATFORM"):
            cmd += ["--platform", os.environ["EPOCH_PLATFORM"]]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=timeout)
        except subprocess.TimeoutExpired:
            print(json.dumps({"metric": METRIC, "value": 0.0,
                              "unit": "images/sec",
                              "error": f"trainer hung past {timeout:.0f}s"}))
            raise SystemExit(1)
        rows = []
        if os.path.exists(jsonl):
            with open(jsonl) as f:
                rows = [json.loads(line) for line in f if line.strip()]
        last_epoch = max((r["epoch"] for r in rows if r.get("kind") ==
                          "epoch"), default=None)
        if proc.returncode != 0 or last_epoch is None:
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()
            print(json.dumps({"metric": METRIC, "value": 0.0,
                              "unit": "images/sec",
                              "error": f"trainer rc={proc.returncode}: "
                                       + (tail[-1] if tail else "no output"),
                              }))
            raise SystemExit(1)
        epoch_s = next(r["seconds"] for r in rows
                       if r.get("kind") == "epoch"
                       and r["epoch"] == last_epoch)
        # Denominator = what the trainer ACTUALLY iterated (its banner),
        # not the requested synthetic size: with real CIFAR-10 on disk the
        # loader serves the full dataset and trusting EPOCH_SAMPLES would
        # bank a ~2x-wrong throughput.
        import re

        m = re.search(r"train samples=(\d+)", proc.stdout or "")
        if m:
            samples = int(m.group(1))
        # Steady-state window throughput: last epoch's non-warmup windows
        # (window timing excludes the eval + checkpoint edges that the
        # epoch wall time includes).
        windows = [r["samples_per_sec"] for r in rows
                   if r.get("kind") == "train_window"
                   and r["epoch"] == last_epoch
                   and not r.get("warmup_window")]
        epoch_ips = samples / epoch_s
        resident = _bench_resident_ips()
        gap = (None if not resident
               else round((1.0 - epoch_ips / resident) * 100.0, 1))
        print(json.dumps({
            "metric": METRIC,
            "value": round(epoch_ips, 1),
            "unit": "images/sec",
            "epoch_seconds": round(epoch_s, 3),
            "samples": samples,
            "global_batch": batch,
            "epoch_measured": last_epoch,
            "window_images_per_sec_mean": (
                round(sum(windows) / len(windows), 1) if windows else None),
            "bench_resident_images_per_sec": resident,
            "input_pipeline_gap_pct": gap,
        }))


if __name__ == "__main__":
    main()
