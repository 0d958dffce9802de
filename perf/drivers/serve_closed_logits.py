"""``serve_closed`` with the check held on logits and on the state: the
same loop, window and metrics (``_submit``, ``_step``, ``_harvest``,
``run_for``, ``report`` and ``close`` are the base class's, untouched),
and three more comparisons in ``correct``.

The base class's oracle compares greedy tokens; with random weights the top
two logits can sit closer than bf16 resolves, so a token check is loose
where a logit check is tight.  Read on ONE seeded sequence, a prompt of
several prefill chunks and fold blocks and then ``decode_positions``
decoded tokens, before the ramp:

  * the ENGINE serves the prompt in company and emits the tokens:
    ``check.beside`` says how many other requests are submitted before it
    (they decode, each at its own depth, while it prefills and decodes) and
    after it (their prefill chunks run in the steps in which it decodes),
    so its tokens come out of the timed programs as the window runs them;
  * the LIBRARY's forward over the engine's own slot arena
    (``tpudp.models.generate._forward_state`` with the engine's decode
    read, what the engine's two programs are made of, at the engine's
    shape: every slot of the arena, the engine's stale states in them) is
    fed the check's sequence in the slot the engine served it in: the
    ``beside`` prompts are prefilled first into every other slot (idle
    slots between them), one of them cut to end in the check's fold phase,
    then the check's prompt a chunk at a time, then the engine's tokens
    one a decode step (teacher-forced) with every prefilled slot decoding
    beside it; it returns every position of the check's logits;
  * the REFERENCE (the family's ``reference_logits``, float32, no state)
    runs the whole sequence at once, and its ``reference_fold`` (float64,
    on the host) folds the rows the library's decode folded into the
    check's slot into the state the slot held before that step.

  *logits*: every position's logits within ``logit_atol`` of the
  reference's, and every position from the second prefill chunk on within
  ``late_logit_atol`` (in the first positions a query meets a handful of
  keys, and the ratios of their squared scores, which bf16 rounding of q
  and k moves, decide its output: a sound forward reads up to several
  times its later gap there, so one limit over all positions would have
  to pass what damaged weights read later on); *engine*: each token the engine emitted has a library logit
  within ``engine_logit_gap`` of that position's largest (the scheduler,
  the slots and the rows beside it did to the forward what the check's own
  loop does); *state*: after each fold the decode makes in the check's
  slot, every layer's and KV head's ``S`` and ``z`` within ``state_rtol``
  of the reference fold, relative to the largest entry (a state rounded to
  a narrower type than the configuration states reads here, where the
  logits cannot tell it).  The limits are the traffic file's, with their
  readings beside them.

:meth:`Driver.system_pass` is the engine's pass and then the library's on
the engine's arena, which it lends and takes back (two arenas do not fit
one chip beside the weights); :meth:`Driver.library_pass` takes any arena,
configuration and weights, so that a control can run the library forward
on damaged ones.
"""

from __future__ import annotations

import gc
import time

from perf.drivers import serve_closed

CHECKS = (("logit_gap", "logit_atol"),
          ("late_logit_gap", "late_logit_atol"),
          ("engine_logit_gap", "engine_logit_gap"),
          ("state_gap", "state_rtol"))
MAX_CHECK_STEPS = 4096  # a stuck engine must not hang the run


class Driver(serve_closed.Driver):
    def setup(self) -> None:
        tr = self.traffic
        # The base class builds the weights and the engine and runs its
        # token oracle; its ramp waits until the check has had the engine.
        self.traffic = {**tr, "callers": 0, "ramp_steps": 0}
        super().setup()
        self.traffic = tr
        params = self.engine.params
        with self.run.spans.span("perf.setup.reference"):
            r = self.reference_pass(params,
                                    self.system_pass(params, self.engine))
        ok = self.logits_ok(r)
        self.correct &= ok
        self.compared += [(name, r[name], tr[limit])
                          for name, limit in CHECKS]
        self.notes.append(
            f"logit check on {r['positions']} positions: max |diff| "
            f"{r['logit_gap']:.3e} (bound {tr['logit_atol']}), from "
            f"position {r['late_from']} on {r['late_logit_gap']:.3e} "
            f"(bound {tr['late_logit_atol']}); the "
            f"engine's {r['engine_tokens']} tokens, decoded beside "
            f"{r['rows_beside']} other live rows at the least, against the "
            f"library forward's logits, run in slot {r['slot']} beside "
            f"{r['slots_live']} other live slots of {r['slots']}: largest "
            f"gap {r['engine_logit_gap']:.3e} (bound "
            f"{tr['engine_logit_gap']}); the slot's state after "
            f"{r['folds_held']} decode folds against the float64 fold: "
            f"{r['state_gap']:.3e} of its largest entry (bound "
            f"{tr['state_rtol']}) {'ok' if ok else 'WRONG'}")
        with self.run.spans.span("perf.setup.ramp"):
            for caller in range(tr["callers"]):
                self._submit(caller)
            for _ in range(tr["ramp_steps"]):
                self._step()
            self._harvest()  # ramp requests count in no window
        self._freeze_heap()

    def _freeze_heap(self) -> None:
        """Collect once and freeze what set-up left on the heap (JAX's
        caches, the engine, the ramp's records) out of the collector, as a
        server does after its warm-up.  Unfrozen, a full collection walks
        all of it (~330,000 objects, ~0.1 s on a v5e host): where one
        falls inside the window, it takes half a percent of the window's
        steps, half the spread this cell is admitted at (PERF.md).
        :meth:`close` unfreezes."""
        t = time.perf_counter()
        gc.collect()
        took = time.perf_counter() - t
        gc.freeze()
        self.notes.append(
            f"heap frozen after set-up: {gc.get_freeze_count()} objects, "
            f"their full collection {1e3 * took:.1f} ms")

    def close(self) -> None:
        gc.unfreeze()
        super().close()

    def logits_ok(self, readings: dict) -> bool:
        """Every reading within the traffic file's limit (a NaN is not)."""
        return all(readings[name] <= self.traffic[limit]
                   for name, limit in CHECKS)

    def system_pass(self, params, engine, token_fault_hook=None) -> dict:
        """The engine and the library forward on the check's sequence
        (module docstring), both on ``params``, the library on the
        engine's own arena, lent and taken back: the sequence, every
        position's logits, and the readings of the engine and the state.
        ``token_fault_hook`` is handed to the engine for the check's
        requests (a control plants a wrong token with it)."""
        served = self.engine_pass(engine, token_fault_hook)
        ms = engine._mstates[None]  # the engine's model state: its arena
        arena, ms.cache = ms.cache, None
        system, ms.cache = self.library_pass(served, engine.config, params,
                                             arena, engine.paged_attn)
        return system

    def engine_pass(self, engine, token_fault_hook=None) -> dict:
        """The engine serves the check's sequence in company: its prompt,
        the tokens it emitted and the slot it held, the live rows beside
        it, and the other requests' prompts."""
        import numpy as np

        cfg = engine.config
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
        slot = None
        for _ in range(MAX_CHECK_STEPS):
            emitted = engine.step()
            if slot is None:
                slot = handle._slot
            if any(r.id == handle.id for r, _tok in emitted):
                rows_beside.append(len(emitted) - 1)
            if all(h.done for h in handles):
                break
        engine.token_fault_hook = None
        for h, (_p, want) in zip(handles, requests):
            if not (h.ok and len(h.tokens) == want):
                raise RuntimeError(f"a request of the check failed: {h!r}")
        return {"prompt": prompt, "emitted": np.asarray(handle.tokens,
                                                        np.int32),
                "chunk": engine.prefill_chunk, "slot": slot,
                "beside": [p for i, (p, _n) in enumerate(requests)
                           if i != before],
                # its first token is the prefill program's
                "rows_beside": min(rows_beside[1:])}

    def library_pass(self, served: dict, cfg, params, arena,
                     impl=None) -> tuple[dict, object]:
        """The library forward teacher-forced on what :meth:`engine_pass`
        served, under ``cfg`` and ``params`` (a control's may be damaged),
        over ``arena`` (a ``generate.RetentionState`` of any number of
        slots, donated), as the module docstring says.  ``impl``: the
        decode read (unset: the engine's rule).  Returns the check's
        readings (every position's logits, the engine's reading against
        them, the state's against the reference fold) and the arena."""
        import functools

        import jax
        import jax.numpy as jnp
        import numpy as np

        from tpudp.models.generate import _forward_state

        fam = self.cell.family
        prompt, emitted, chunk = (served["prompt"], served["emitted"],
                                  served["chunk"])
        n_prompt, n_new = prompt.size, emitted.size
        seq = np.concatenate([prompt, emitted[:-1]])
        slots, c = arena.S.shape[1], cfg.fold_tokens
        slot = served["slot"] % slots
        rng = np.random.default_rng(self.run.seed + 3)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chunk_fwd(params, state, tokens, start, slot, last):
            logits, state = _forward_state(
                cfg, params, tokens, state, start, jnp.ones((1,), bool),
                impl, slot=slot, last=last, every_row=True)
            return logits[0], state

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_fwd(params, state, tokens, lengths, active, slot):
            logits, state = _forward_state(
                cfg, params, tokens[:, None], state, lengths, active, impl)
            return logits[slot, 0], state

        def prefill(state, s, tokens, keep):
            for start in range(0, tokens.size, chunk):
                buf = np.zeros((1, chunk), np.int32)
                n = min(chunk, tokens.size - start)
                buf[0, :n] = tokens[start:start + n]
                lg, state = chunk_fwd(params, state, buf, np.int32(start),
                                      np.int32(s), np.int32(n - 1))
                if keep is not None:
                    keep.append(np.asarray(lg[:n]))
            return state

        # the other slots: every other one, the beside prompts in them,
        # the first cut to fold on the step on which the check's slot does
        others = [s for s in range(slots) if s != slot][::2]
        others = others[:len(served["beside"])]
        fills = [p for p, _s in zip(served["beside"], others)]
        if fills:
            cut = fills[0].size - (fills[0].size - n_prompt) % c
            fills[0] = fills[0][:cut]
        state = arena
        for s, p in zip(others, fills):
            state = prefill(state, s, p, None)
        logits = []
        state = prefill(state, slot, prompt, logits)
        lengths = np.zeros(slots, np.int32)
        active = np.zeros(slots, bool)
        for s, p in zip([*others, slot], [*fills, prompt]):
            lengths[s], active[s] = p.size, True

        def held(*arrays):  # the check's slot, every layer, on the host
            return [np.asarray(a[:, slot]) for a in arrays]

        gaps = []  # each (layer, KV head)'s, of S and of z, at each fold
        for j in range(n_prompt, seq.size):
            tokens = rng.integers(0, cfg.vocab_size, size=slots,
                                  dtype=np.int32)
            tokens[slot] = seq[j]
            fold = j % c == c - 1
            if fold:
                S0, z0 = held(state.S, state.z)
            lg, state = decode_fwd(params, state, tokens, lengths, active,
                                   np.int32(slot))
            logits.append(np.asarray(lg)[None])
            lengths[active] += 1
            if fold:
                S1, z1, k, v, g = held(*state)
                S_ref, z_ref = fam.reference_fold(S0, z0, k, v, g)
                for got, want in ((S1, S_ref), (z1, z_ref)):
                    lead = want.shape[:2]  # (layers, KV heads)
                    err = np.abs(got - want).reshape(*lead, -1).max(-1)
                    top = np.abs(want).reshape(*lead, -1).max(-1)
                    gaps.append(err / top)
        if not gaps:
            raise ValueError("the check's decode crosses no fold: its "
                             "prompt_len mod fold_tokens plus its "
                             "decode_positions must reach fold_tokens")
        logits = np.concatenate(logits)
        rows = logits[n_prompt - 1:]  # what each emitted token was drawn from
        gap = float(np.max(rows.max(-1)
                           - rows[np.arange(n_new), emitted]))
        return {"seq": seq, "logits": logits, "chunk": chunk,
                "engine_logit_gap": gap,
                "engine_tokens": n_new, "rows_beside": served["rows_beside"],
                # np.max: a NaN anywhere reads NaN, which no limit passes
                "state_gap": float(np.max(gaps)),
                "folds_held": len(gaps) // 2, "slot": slot, "slots": slots,
                "slots_live": len(others)}, state

    def reference_pass(self, params, system: dict) -> dict:
        """The readings of :data:`CHECKS`: the reference on ``params``
        against :meth:`library_pass`'s logits, a block of vocabulary
        columns at a time (the family's ``reference_hidden`` and
        ``head_block``: the whole ``(T, vocab)`` float32 tensor would not
        fit beside the engine's state), and the readings the library pass
        made."""
        import jax
        import numpy as np

        fam, cfg = self.cell.family, self.config
        seq, n = system["seq"], system["seq"].size
        pad = -(-n // 8) * 8
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :n] = seq
        kernel = params["lm_head"]["kernel"]
        cols = fam.head_columns(kernel.shape[1])
        with jax.default_matmul_precision("highest"):
            h = jax.jit(lambda p, x: fam.reference_hidden(p, x, cfg))(
                params, tokens)[0, :n]
            block = jax.jit(fam.head_block)
            # each position's largest |diff| over the vocabulary
            per = np.max([np.max(np.abs(
                np.asarray(block(h, kernel, j))
                - system["logits"][:, j * cols:(j + 1) * cols]), axis=1)
                for j in range(kernel.shape[1] // cols)], axis=0)
        late = system["chunk"]
        # np.max: a NaN anywhere reads NaN, which no limit passes
        return {"logit_gap": float(np.max(per)),
                "late_logit_gap": float(np.max(per[late:])),
                "late_from": late, "positions": n,
                **{k: system[k] for k in (
                    "engine_logit_gap", "engine_tokens", "rows_beside",
                    "state_gap", "folds_held", "slot", "slots",
                    "slots_live")}}
