"""Mean share of ``num_slots`` that decode in an engine step, over the
measured window: ``active_slot_steps / (decode_steps x num_slots)`` from
the counters ``Engine.metrics()["stats"]`` already keeps."""


def read(run):
    st = run.window.get("engine_stats")
    if not st or not st.get("decode_steps"):
        return None
    return 100.0 * st["active_slot_steps"] / (
        st["decode_steps"] * run.window["num_slots"])
