"""Share of the time to first token spent holding a slot and waiting
for a prefill turn: ``ttft_prefill_wait_s / ttft_s`` over the first
tokens committed in the measured window (the engine stamps a request at
submit, at its slot grant and at its first prefill chunk).  The rest is
queueing for a slot (none in a closed loop with a slot a caller) and
the request's own prefill."""


def read(run):
    st = run.window.get("engine_stats")
    if not st or not st.get("first_tokens") or not st.get("ttft_s") \
            or "ttft_prefill_wait_s" not in st:
        return None
    return 100.0 * st["ttft_prefill_wait_s"] / st["ttft_s"]
