"""Model FLOP/s utilisation: the operations one sample's forward and
backward passes require (perf/harness/flops.py, from shapes; nothing
recomputed counts) x the measured rate per chip / the chip's bf16 peak
(perf/harness/peaks.json).  It is the end-to-end rate again, divided by a
constant, so it cannot pass 100% unless the count is too high."""

from perf.harness.peaks import peaks


def read(run):
    rate = run.end_to_end.get("train_throughput_per_chip")
    if rate is None or run.rehearse:
        return None
    per_sample = run.cell.family.train_flops_per_sample(run.cell.config,
                                                        run.cell.traffic)
    return 100.0 * per_sample * rate / peaks(run.device_kind)[
        "bf16_flops_per_s"]
