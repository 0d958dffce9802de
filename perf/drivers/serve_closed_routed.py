"""``serve_closed`` for a model that routes tokens to experts: the same
loop, window and metrics (``_submit``, ``_step``, ``_harvest``, ``run_for``,
``report`` and ``close`` are the base class's, untouched), and three more
comparisons in ``correct``.

A bf16 residual stream moves a router's scores by about a hundredth, which
is more than the 8th and 9th of 256 scores differ on some tokens, so the
system and a float32 reference choose one expert differently on a share of
them, and each such flip swaps an eighth of a layer's routed output.  The
base class's oracle (every greedy token of two requests, served together,
within ``logit_gap`` of the FREE-routing reference's largest logit) is
therefore set by flips as much as by rounding; it is held at a limit wide
enough for a flip and far under what a wrong token reads.

What holds the system to its stated precision is read on ONE seeded
sequence, a prompt of more than four prefill chunks and then
``decode_positions`` decoded tokens, before the ramp:

  * the ENGINE serves the prompt in company and emits the tokens:
    ``check.beside`` says how many other requests are submitted before it
    (they decode, each at its own depth, while it prefills and decodes)
    and after it (their prefill chunks run in the steps in which it
    decodes), so its tokens come out of the timed programs as the window
    runs them, a prefill chunk and a decode run of many live rows a step;
  * the LIBRARY's paged forward (``tpudp.models.generate._forward_paged``,
    what the engine's two programs are made of) is fed the prompt a
    page-aligned chunk at a time and then the engine's tokens one at a
    time (teacher-forced), at the engine's decode shape with one slot
    active and the others idle, into a small pool of its own, and returns
    every position's logits and the experts every position chose;
  * the REFERENCE (the family's, float32, expanded attention, no cache)
    runs the whole sequence at once with those choices forced.

  *choice*: every expert the system chose has a reference score within
  ``route_tie_margin`` of the reference's k-th best on the same input;
  *logits*: every position's logits within ``routed_logit_atol`` of the
  reference's; *engine*: each token the engine emitted has a library
  logit within ``engine_logit_gap`` of that position's largest (the
  scheduler, the block table, the slots and the rows beside it did to the
  forward what the check's own plain loop does).  The limits are the
  traffic file's, with their readings beside them.

The engine gives tokens, not logits, so what sets the timed programs
against the float32 reference is a chain: their tokens against the
library forward, the library forward against the reference.  A reading of
the engine's tokens on the REFERENCE's logits was tried and does not
separate a precision (float8 0.037 / 0.056 where sound runs read up to
0.023: PERF.md section 6), nor does the base class's oracle.

The two passes are separate methods so that a control can run the system
on damaged weights, free them, and give the reference the sound ones: two
copies of a 9.8 GB tree do not fit one chip.
"""

from __future__ import annotations

from perf.drivers import serve_closed

CHECKS = (("choice_gap", "route_tie_margin"),
          ("routed_logit_gap", "routed_logit_atol"),
          ("engine_logit_gap", "engine_logit_gap"))
MAX_CHECK_STEPS = 4096  # a stuck engine must not hang the run


class Driver(serve_closed.Driver):
    def setup(self) -> None:
        tr = self.traffic
        # The base class builds the weights and the engine and runs its
        # free-routing oracle; its ramp waits until the routed check has
        # had the engine.
        self.traffic = {**tr, "callers": 0, "ramp_steps": 0}
        super().setup()
        self.traffic = tr
        params = self.engine.params
        with self.run.spans.span("perf.setup.reference"):
            r = self.reference_pass(params,
                                    self.system_pass(params, self.engine))
        ok = self.routed_ok(r)
        self.correct &= ok
        self.compared += [(name, r[name], tr[limit])
                          for name, limit in CHECKS]
        self.notes.append(
            f"routed check on {r['positions']} positions: expert choices "
            f"vs the reference's scores, largest shortfall "
            f"{r['choice_gap']:.3e} (margin {tr['route_tie_margin']}); "
            f"with the choices forced, logits: max |diff| "
            f"{r['routed_logit_gap']:.3e} (bound "
            f"{tr['routed_logit_atol']}); the engine's {r['engine_tokens']} "
            f"tokens, decoded beside {r['rows_beside']} other live rows at "
            f"the least, against the library forward's logits: largest gap "
            f"{r['engine_logit_gap']:.3e} (bound {tr['engine_logit_gap']}) "
            f"{'ok' if ok else 'WRONG'}")
        with self.run.spans.span("perf.setup.ramp"):
            for caller in range(tr["callers"]):
                self._submit(caller)
            for _ in range(tr["ramp_steps"]):
                self._step()
            self._harvest()  # ramp requests count in no window

    def routed_ok(self, readings: dict) -> bool:
        """Every reading within the traffic file's limit (a NaN is not)."""
        return all(readings[name] <= self.traffic[limit]
                   for name, limit in CHECKS)

    def system_pass(self, params, engine, token_fault_hook=None) -> dict:
        """The engine and the library forward on the check's sequence
        (module docstring), both on ``params``: the sequence, every
        position's logits, every expert layer's choices, and the engine's
        reading against the library.  ``token_fault_hook``
        is handed to the engine for the check's requests (a control plants
        a wrong token with it)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from tpudp.models.generate import _forward_paged, page_type

        cfg = engine.config
        chunk, slots = engine.prefill_chunk, engine.num_slots
        n_prompt = self.traffic["check"]["prompt_len"]
        n_new = self.traffic["check"]["decode_positions"] + 1
        rng = np.random.default_rng(self.run.seed + 2)
        prompt = rng.integers(0, cfg.vocab_size, size=n_prompt,
                              dtype=np.int32)
        beside = self.traffic["check"]["beside"]
        before = beside["before"]
        requests = [(rng.integers(0, cfg.vocab_size, dtype=np.int32,
                                  size=rng.integers(*beside["prompt_len"])),
                     beside["new_tokens_before" if i < before
                            else "new_tokens_after"])
                    for i in range(before + beside["after"])]
        requests.insert(before, (prompt, n_new))
        engine.token_fault_hook = token_fault_hook
        handles = [engine.submit(p, n) for p, n in requests]
        handle = handles[before]
        rows_beside = []  # live rows in each step that gave it a token
        for _ in range(MAX_CHECK_STEPS):
            emitted = engine.step()
            if any(r.id == handle.id for r, _tok in emitted):
                rows_beside.append(len(emitted) - 1)
            if all(h.done for h in handles):
                break
        engine.token_fault_hook = None
        for h, (_p, want) in zip(handles, requests):
            if not (h.ok and len(h.tokens) == want):
                raise RuntimeError(f"a request of the check failed: {h!r}")
        emitted = np.asarray(handle.tokens, np.int32)
        seq = np.concatenate([prompt, emitted[:-1]])

        @jax.jit
        def chunk_fwd(params, pool, row, tokens, pos):
            routed: list = []
            logits, pool = _forward_paged(
                cfg, params, tokens, pool, row[None], pos,
                jnp.ones((1,), bool), routed=routed)
            return logits[0], pool, [c for c, _ in routed]

        @jax.jit
        def decode_fwd(params, pool, table, tokens, lengths, active):
            routed: list = []
            logits, pool = _forward_paged(
                cfg, params, tokens[:, None], pool, table, lengths, active,
                routed=routed)
            return logits[0, 0], pool, [c[0] for c, _ in routed]

        pages = -(-seq.size // chunk)
        max_pages = engine.max_len // chunk
        pool = page_type(cfg).zeros(cfg, pages + 1, chunk)
        table = np.full((slots, max_pages), -1, np.int32)
        table[0, :pages] = np.arange(pages)
        logits, chosen = [], None
        for start in range(0, n_prompt, chunk):
            buf = np.zeros((1, chunk), np.int32)
            n = min(chunk, n_prompt - start)
            buf[0, :n] = prompt[start:start + n]
            lg, pool, ch = chunk_fwd(params, pool, table[0], buf,
                                     np.int32(start))
            logits.append(np.asarray(lg[:n]))
            ch = [np.asarray(c[:n]) for c in ch]
            chosen = ch if chosen is None else [
                np.concatenate(p) for p in zip(chosen, ch)]
        active = np.zeros(slots, bool)
        active[0] = True
        for j in range(n_prompt, seq.size):
            toks = np.zeros(slots, np.int32)
            toks[0] = seq[j]
            lens = np.zeros(slots, np.int32)
            lens[0] = j
            lg, pool, ch = decode_fwd(params, pool, table, toks, lens,
                                      active)
            logits.append(np.asarray(lg)[None])
            chosen = [np.concatenate([p, np.asarray(c)[None]])
                      for p, c in zip(chosen, ch)]
        logits = np.concatenate(logits)
        rows = logits[n_prompt - 1:]  # what each emitted token was drawn from
        gap = float(np.max(rows.max(-1)
                           - rows[np.arange(n_new), emitted]))
        return {"seq": seq, "logits": logits, "chosen": chosen,
                "engine_logit_gap": gap, "engine_tokens": n_new,
                # its first token is the prefill program's
                "rows_beside": min(rows_beside[1:])}

    def reference_pass(self, params, system: dict) -> dict:
        """The readings of :data:`CHECKS`: the reference on ``params``
        with :meth:`system_pass`'s choices forced, against its logits."""
        import jax
        import numpy as np

        fam, cfg = self.cell.family, self.config
        seq, n = system["seq"], system["seq"].size
        pad = -(-n // 8) * 8
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :n] = seq
        routing = [np.concatenate([c, np.zeros((pad - n, c.shape[1]),
                                               c.dtype)])
                   for c in system["chosen"]]
        with jax.default_matmul_precision("highest"):
            want, scores = jax.jit(
                lambda p, x, c: fam.reference_forward(p, x, cfg, c))(
                    params, tokens, routing)
        choice = max(float(fam.choice_gap(s[:n], c))
                     for s, c in zip(scores, system["chosen"], strict=True))
        logit = float(np.max(np.abs(np.asarray(want)[0, :n]
                                    - system["logits"])))
        return {"choice_gap": choice, "routed_logit_gap": logit,
                "engine_logit_gap": system["engine_logit_gap"],
                "engine_tokens": system["engine_tokens"],
                "rows_beside": system["rows_beside"], "positions": n}
