"""Share of the chip's peak the serving loop's whole step reaches: the
least time this chip needs for the work the measured window did, over the
window's seconds.  The work is what the driver saw (``run.window["work"]``:
for each of the engine's programs its runs, the token rows, logits, cached
tokens and query-key pairs that the tokens stamped inside the window
required), priced by the family's ``serve_costs``; the least time of a
program is the larger of its operations over the peak FLOP/s and its
bytes over the peak bytes/s (perf/harness/peaks.json), its bytes the
weights once a run and the cached tokens read.  Only what a correct
engine must do is counted, so it cannot pass 100% unless a count is too
high.  A prompt counts whole in the window its first token falls in.
Nothing where the family has no ``serve_costs``, the window no step, or
the run is a rehearsal (a CPU has no peaks: as ``mfu``)."""

from perf.harness.peaks import peaks


def read(run):
    costs = getattr(run.cell.family, "serve_costs", None)
    work = run.window.get("work")
    if costs is None or run.rehearse or not work \
            or not any(w["runs"] for w in work.values()):
        return None
    c = costs(run.cell.config)
    peak = peaks(run.device_kind)
    least = 0.0
    for w in work.values():
        flops = (c["flops_per_token"] * w["tokens"]
                 + c["flops_per_logit"] * w["logits"]
                 + c["flops_per_attended"] * w["attended"])
        nbytes = (c["bytes_per_run"] * w["runs"]
                  + c["bytes_per_cache_token"] * w["cache_tokens"])
        least += max(flops / peak["bf16_flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (run.window["t1"] - run.window["t0"])
