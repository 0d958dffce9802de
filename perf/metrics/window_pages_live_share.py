"""Mean share of the window pool's pages that are mapped when a decode run
is dispatched, over the measured window: ``window_pages_live /
(decode_steps x pool pages)`` from ``Engine.metrics()["stats"]`` (the
engine adds the pool's used pages at every decode dispatch) and the
family's ``window_pool_pages`` of the traffic file's engine.  The engine
frees a sliding layer's page once the window has passed it, so a slot
holds ``ceil(window / page) + 1`` pages at the most; 100% would mean every
slot is live at that bound, a share that grows with the context that the
ring never frees.  Nothing where the engine has no such counter (another
family; the parent of the PR that brought it)."""


def read(run):
    pages = getattr(run.cell.family, "window_pool_pages", None)
    st = run.window.get("engine_stats") or {}
    if pages is None or not st.get("decode_steps") \
            or "window_pages_live" not in st:
        return None
    return 100.0 * st["window_pages_live"] / (
        st["decode_steps"] * pages(run.cell.config,
                                   run.cell.traffic["engine"]))
