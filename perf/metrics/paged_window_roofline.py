"""Share of their roofline the sliding-window layers' two paged attention
kernels reach: the least time an engine step's window-layer attention can
take on this chip over ``kernel_ms.paged_decode_window`` +
``kernel_ms.paged_prefill_window``.  The least time is the family's
``window_attn_least_ms`` of what the engine counted in the TRACED segment,
the same ``run_for`` call the kernel time comes from
(``run.traced["engine_stats"]``: ``window_rows_read`` and ``window_pairs``
over its ``steps``; the kernel time is over those steps and the ones that
drain the last first tokens, each side over its own count): the K and V
rows INSIDE the window of every call read once, or their query-key
products at the MXU's peak if that is larger (perf/harness/peaks.json).
The kernels fetch whole pages, the window starts inside one, and a decode
row uses one lane in eight of a float32 product, so the share is far
under 100%.  Nothing without a trace, the counters (the parent of the PR
that brought them) or the family's function."""

from perf.harness.layers import kernel_ms
from perf.harness.peaks import peaks


def read(run):
    least = getattr(run.cell.family, "window_attn_least_ms", None)
    took = [kernel_ms(run, name, "steps_with_drain")
            for name in ("paged_decode_window", "paged_prefill_window")]
    st = (run.traced or {}).get("engine_stats") or {}
    if least is None or not any(took) or not st.get("steps") or not all(
            k in st for k in ("window_rows_read", "window_pairs")):
        return None
    return 100.0 * least(run.cell.config,
                         st["window_rows_read"] / st["steps"],
                         st["window_pairs"] / st["steps"],
                         peaks(run.device_kind)) / sum(t or 0.0 for t in took)
