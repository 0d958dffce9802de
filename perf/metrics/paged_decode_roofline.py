"""Share of its roofline the full-attention layers' paged-decode kernel
reaches: the least time an engine step's ``paged_decode`` calls can take
on this chip over ``kernel_ms.paged_decode``.  The least time is the
cached tokens the decode runs of the TRACED segment must read, the same
``run_for`` call the kernel time comes from (``run.traced["engine_stats"]``:
``decode_rows_read``, ``pos + 1`` for every decoding slot of every run,
over its ``steps``; the kernel time is over those steps and the ones that
drain the last first tokens, each side over its own count), at the
family's ``serve_costs`` ``bytes_per_cache_token`` (a token's key and
value in every full-attention layer), over the chip's HBM bandwidth
(perf/harness/peaks.json).  The count is what a correct kernel must read,
whatever implements it; the kernel fetches whole pages, so the share
cannot pass 100%.  Nothing without a trace, the counter (the parent of the
PR that brought it) or the family's ``serve_costs``."""

from perf.harness.layers import kernel_ms
from perf.harness.peaks import peaks


def read(run):
    costs = getattr(run.cell.family, "serve_costs", None)
    took = kernel_ms(run, "paged_decode", "steps_with_drain")
    st = (run.traced or {}).get("engine_stats") or {}
    if costs is None or not took or not st.get("steps") \
            or "decode_rows_read" not in st:
        return None
    nbytes = (costs(run.cell.config)["bytes_per_cache_token"]
              * st["decode_rows_read"] / st["steps"])
    return 100.0 * 1e3 * nbytes / peaks(run.device_kind)[
        "hbm_bytes_per_s"] / took
