"""A closed loop of callers against ``tpudp.serve.Engine``.

Each caller submits its next request the moment its last one completes;
the harness drives ``Engine.step()`` itself and stamps every token with
its own clock as ``step()`` returns it.  The loop keeps running between
the measured window and the traced one, so both see the steady state the
ramp in set-up has built.
"""

from __future__ import annotations

import time
from statistics import median

from perf.harness.loadgen import RequestStream
from perf.harness.stats import percentile


MAX_DRAIN_STEPS = 4096  # a stuck engine must not hang the run
# What a window's tokens required of each of the engine's two programs,
# whatever the model (``serve_mfu`` prices it with the family's
# ``serve_costs``): program runs, token rows through the blocks, rows
# through the output head, cached tokens read, query-key pairs attended.
WORK = ("runs", "tokens", "logits", "cache_tokens", "attended")


def _add_work(work: dict, prompt_len: int, indices: list[int]) -> None:
    """Add what the tokens of one request with these indices required.
    Token 0 is the prefill's: the whole prompt through the blocks, one row
    of logits, the prompt's keys and values moved once, every causal
    query-key pair.  Token ``j >= 1`` is a decode step's: one row, which
    attends over the ``prompt_len + j`` tokens cached by then."""
    for j in indices:
        w = work["prefill" if j == 0 else "decode"]
        w["logits"] += 1
        if j == 0:
            w["tokens"] += prompt_len
            w["cache_tokens"] += prompt_len
            w["attended"] += prompt_len * (prompt_len + 1) // 2
        else:
            w["tokens"] += 1
            w["cache_tokens"] += prompt_len + j
            w["attended"] += prompt_len + j


class _Rec:
    __slots__ = ("handle", "want", "submit", "times", "caller", "end",
                 "prompt_len")

    def __init__(self, handle, want, submit, caller, prompt_len):
        self.handle, self.want, self.submit = handle, want, submit
        self.prompt_len = prompt_len
        self.times: list[float] = []   # one stamp per token
        self.caller = caller
        self.end = None                # when step() returned it finished

    @property
    def failed(self) -> bool:
        h = self.handle
        return h.done and not (h.ok and len(h.tokens) == self.want)


class Driver:
    def __init__(self, cell, run):
        self.cell, self.run = cell, run
        self.traffic, self.config = cell.traffic, cell.config
        self.correct = True
        self.notes: list[str] = []
        self.compared: list[tuple] = []  # (name, reading, its limit)
        self.live: dict[int, _Rec] = {}   # request id -> record, in flight
        self.done: list[_Rec] = []        # finished since the last harvest
        self.engine = None

    # ---------------------------------------------------------- set-up

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from tpudp.serve import Engine

        cfg, tr, fam = self.config, self.traffic, self.cell.family
        span = self.run.spans.span
        model = fam.build_model(cfg)
        wdtype = jnp.dtype(cfg["serve"]["weight_dtype"])
        shape = fam.init_input_shape(cfg)

        def make_weights(key):
            params = model.init(key, jnp.zeros(shape, jnp.int32),
                                train=False)["params"]
            return jax.tree.map(lambda a: a.astype(wdtype), params)

        # one jitted call, on the device, in the type they are served in
        with span("perf.setup.weights"):
            params = jax.jit(make_weights)(
                jax.random.PRNGKey(self.run.seed % (2 ** 31)))
        with span("perf.setup.engine"):
            self.engine = Engine(model, params, **tr["engine"])
        self.stream = RequestStream(tr, self.run.seed, cfg["vocab_size"])

        # correctness, outside the window (chip_smoke.py's serving oracle):
        # every greedy token of two seeded requests lies within
        # ``logit_gap`` of the float32 teacher-forced argmax through the
        # plain reference.  Token equality is not usable at random
        # weights: the top two logits sit 0.005-0.3 apart and bf16
        # resolves ~0.016 (PERF.md section 6, PR 21).
        orc = tr["oracle"]
        rng = np.random.default_rng(self.run.seed + 1)
        prompts = [rng.integers(0, cfg["vocab_size"], size=n, dtype=np.int32)
                   for n in orc["prompt_lens"]]
        with span("perf.setup.oracle"):
            handles = [self.engine.submit(p, orc["max_new_tokens"])
                       for p in prompts]
            self.engine.run_until_complete()
            pad = -(-(max(orc["prompt_lens"]) + orc["max_new_tokens"])
                    // 8) * 8
            ref = jax.jit(lambda p, x: fam.reference_logits(p, x, cfg))
            worst = 0.0
            for p, h in zip(prompts, handles):
                ok = h.ok and len(h.tokens) == orc["max_new_tokens"]
                self.correct &= ok
                if not ok:
                    continue
                seq = np.zeros((1, pad), np.int32)
                seq[0, :p.size + len(h.tokens)] = [*p, *h.tokens]
                with jax.default_matmul_precision("highest"):
                    lg = np.asarray(ref(params, seq))[0]
                rows = lg[p.size - 1:p.size - 1 + len(h.tokens)]
                worst = max(worst, float(np.max(
                    rows.max(-1) - rows[np.arange(len(h.tokens)),
                                        h.tokens])))
            del ref
        ok = worst <= orc["logit_gap"]
        self.correct &= ok
        self.compared.append(("oracle_logit_gap", worst, orc["logit_gap"]))
        pa = self.engine.metrics().get("paged_attn", {})
        self.notes.append(
            f"oracle: worst gap of a greedy token to the float32 "
            f"teacher-forced argmax {worst:.4f} logits (bound "
            f"{orc['logit_gap']}) {'ok' if ok else 'WRONG'}; paged_attn "
            f"resolved {pa.get('resolved')} fallbacks {pa.get('fallbacks')}")

        # the ramp: fill every slot and let the loop reach its steady mix
        # of prefilling and decoding slots before the window opens
        with span("perf.setup.ramp"):
            for caller in range(tr["callers"]):
                self._submit(caller)
            for _ in range(tr["ramp_steps"]):
                self._step()
            self._harvest()  # ramp requests count in no window

    # ------------------------------------------------------------ loop

    def _submit(self, caller: int) -> None:
        prompt, want = self.stream.next()
        now = time.perf_counter()
        handle = self.engine.submit(prompt, want)
        self.live[handle.id] = _Rec(handle, want, now, caller, len(prompt))

    def _step(self) -> float:
        with self.run.spans.span("perf.engine_step"):
            emitted = self.engine.step()
        now = time.perf_counter()
        live = self.live
        for r, _tok in emitted:
            rec = live.get(r.id)
            if rec is not None:
                rec.times.append(now)
        finished = [rec for rec in live.values() if rec.handle.done]
        if finished:
            with self.run.spans.span("perf.submit"):
                for rec in finished:
                    del live[rec.handle.id]
                    rec.end = now
                    self.done.append(rec)
                    self._submit(rec.caller)
        return now

    def _harvest(self) -> list[_Rec]:
        out, self.done = self.done, []
        return out

    def run_for(self, seconds: float) -> dict:
        before = dict(self.engine.metrics()["stats"])
        t0 = time.perf_counter()
        steps = 0
        while True:
            now = self._step()
            steps += 1
            if now - t0 >= seconds:
                break
        t1 = now
        after = dict(self.engine.metrics()["stats"])
        # the window is closed; the loop runs on (same load) until every
        # request submitted in it has its first token, so that the TTFT
        # tail is the tail of ALL of them
        waiting = [rec for rec in self.live.values()
                   if rec.submit <= t1 and not rec.times]
        drain = 0
        while waiting and drain < MAX_DRAIN_STEPS:
            self._step()
            drain += 1
            waiting = [rec for rec in waiting
                       if not rec.times and not rec.handle.done]
        finished = self._harvest()
        in_window = [rec for rec in finished if rec.end <= t1]
        tokens = 0
        ttft, gaps = [], []
        work = {"decode": dict.fromkeys(WORK, 0),
                "prefill": dict.fromkeys(WORK, 0)}
        for rec in finished + list(self.live.values()):
            if rec.failed:
                continue  # a failed request's tokens count for nothing
            inside = [j for j, t in enumerate(rec.times) if t0 <= t <= t1]
            tokens += len(inside)
            _add_work(work, rec.prompt_len, inside)
            if t0 <= rec.submit <= t1 and rec.times:
                ttft.append(rec.times[0] - rec.submit)
            gaps.extend(b - a for a, b in zip(rec.times, rec.times[1:])
                        if t0 <= b <= t1)
        delta = {k: after.get(k, 0) - before.get(k, 0) for k in after
                 if isinstance(after.get(k), (int, float))}
        work["decode"]["runs"] = delta.get("decode_steps", 0)
        work["prefill"]["runs"] = delta.get("prefill_chunks", 0)
        return {"t0": t0, "t1": t1, "steps": steps, "tokens": tokens,
                "steps_with_drain": steps + drain,
                "attempted": len(in_window),
                "failed": sum(rec.failed for rec in in_window), "ttft": ttft,
                "gaps": gaps, "engine_stats": delta, "drain_steps": drain,
                "work": work,
                "num_slots": self.engine.num_slots,
                "step_s": self.run.spans.durations("perf.engine_step",
                                                   t0, t1)}

    def report(self, seg: dict) -> dict:
        dt = seg["t1"] - seg["t0"]
        enough = len(seg["ttft"]) >= 20 and len(seg["gaps"]) >= 20
        e2e = {"serve_tokens_per_s": seg["tokens"] / dt}
        if enough:
            e2e["ttft_p95_ms"] = 1e3 * percentile(seg["ttft"], 95)
            e2e["itl_p95_ms"] = 1e3 * percentile(seg["gaps"], 95)
            self.notes.append(
                f"{seg['attempted']} requests completed, {len(seg['ttft'])} "
                f"submitted in the window; ttft p50 "
                f"{1e3 * median(seg['ttft']):.2f} ms, itl p50 "
                f"{1e3 * median(seg['gaps']):.3f} ms over "
                f"{len(seg['gaps'])} gaps; {seg['steps']} engine steps, "
                f"{seg['drain_steps']} more to drain first tokens")
        return {"end_to_end": e2e, "attempted": seg["attempted"],
                "failed": seg["failed"],
                "correct": self.correct and seg["failed"] == 0 and enough}

    def close(self) -> None:
        if self.engine is not None:
            self.engine.close()
