"""Training fed as ``examples/train_gpt2.py`` feeds it.

The loop is that example's: ``make_train_step(model, tx, mesh, sync)`` on
a 1-D data mesh, a batch drawn on the host and ``device_put`` before
every step, a barrier and a loss fetch every ``steps_per_reading`` steps
(the example's ``--log-every``, 10).  The rate is all the samples of the
window over all its time; the groups of steps between barriers are kept
as readings for the per-layer median.  Nothing here times one resident
batch in a tight loop.
"""

from __future__ import annotations

import math
import time

from perf.harness.loadgen import token_batch

# ``correct`` holds the system to the plain float32 reference twice, on the
# first batch and the seed's weights, before the window:
#  - per token: the cross entropies of one sequence through the system's
#    model (bf16, flash attention; ``loss_fn``'s own path before its mean)
#    against the reference's, by the largest difference of any token
#    (``token_loss_atol``).  Nothing averages out of a maximum, so this is
#    the check a precision below bf16 fails;
#  - the measured program: the first step's loss (a mean over the batch)
#    against the reference's mean (``loss_atol``).  It shows a step that
#    drops a layer or mishandles the batch; a mean over 8,192 tokens
#    averages rounding away, so it says little about precision.
# Both bounds are the traffic file's, each about three times the worst
# chip reading (PERF.md section 6, PR 24): the largest token difference
# reads 0.037-0.043 in bf16 over seven seeds and 0.31-0.38 with the
# weights rounded to fp8 (bound 0.12); the first step's loss differs by
# 6e-6 to 1.8e-4 (bound 5e-4).


class Driver:
    def __init__(self, cell, run):
        self.cell, self.run = cell, run
        self.traffic, self.config = cell.traffic, cell.config
        self.loss_sum_seen = 0.0
        self.correct = True
        self.notes: list[str] = []
        self.compared: list[tuple] = []  # (name, reading, its limit)

    # ---------------------------------------------------------- set-up

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from tpudp.train import TrainState, make_optimizer, make_train_step

        cfg, tr, fam = self.config, self.traffic, self.cell.family
        devices = self.run.devices
        mesh = Mesh(np.asarray(devices), ("data",))
        self.batch = tr["per_chip_batch"] * len(devices)
        self.seq = tr["seq_len"]
        model = fam.build_model(cfg, attn_impl=cfg["train"]["attn_impl"])
        tx = make_optimizer(learning_rate=cfg["train"]["learning_rate"],
                            weight_decay=cfg["train"]["weight_decay"],
                            optimizer=cfg["train"]["optimizer"])
        shape = fam.init_input_shape(cfg)
        rep = NamedSharding(mesh, P())

        def make_state(key):
            params = model.init(key, jnp.zeros(shape, jnp.int32),
                                train=False)["params"]
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              batch_stats={}, opt_state=tx.init(params),
                              loss_sum=jnp.zeros((), jnp.float32))

        # weights and optimizer state: one jitted call, on the device,
        # from the seed, in the type they are trained in
        with self.run.spans.span("perf.setup.weights"):
            self.state = jax.jit(make_state, out_shardings=rep)(
                jax.random.PRNGKey(self.run.seed % (2 ** 31)))
        self.step = make_train_step(model, tx, mesh, tr["sync"])
        self.data_sharding = NamedSharding(mesh, P("data"))
        self.rng = np.random.default_rng(self.run.seed)
        self.vocab = cfg["vocab_size"]

        # correctness, outside the window (the step donates the state, so
        # the reference goes first)
        first = token_batch(self.rng, self.vocab, self.batch, self.seq)
        with self.run.spans.span("perf.setup.reference"):
            one = jax.jit(
                lambda p, x, y: fam.reference_token_losses(p, x, y, cfg))
            with jax.default_matmul_precision("highest"):
                # a sequence at a time: the float32 logits of a whole
                # batch would crowd the state the window needs
                ref = [np.asarray(one(self.state.params, first[i:i + 1, :-1],
                                      first[i:i + 1, 1:]))
                       for i in range(self.batch)]
            got_tokens = np.asarray(jax.jit(
                lambda p, x, y: fam.system_token_losses(model, p, x, y))(
                    self.state.params, first[:1, :-1], first[:1, 1:]))
            del one
        token_gap = float(np.max(np.abs(got_tokens - ref[0])))
        ref_mean = float(np.mean(ref))
        with self.run.spans.span("perf.setup.warmup"):
            self.state, loss = self.step(self.state,
                                         *self._next_batch(first))
            got = float(loss)
            for _ in range(2):
                self.state, _ = self.step(self.state, *self._next_batch())
            jax.block_until_ready(self.state.params)
            self.loss_sum_seen = float(self.state.loss_sum)
        ok = (token_gap <= tr["token_loss_atol"]  # a NaN fails both
              and abs(got - ref_mean) <= tr["loss_atol"])
        self.correct &= ok
        self.compared += [
            (name, value, tr[limit]) for name, value, limit in (
                ("token_loss_gap", token_gap, "token_loss_atol"),
                ("first_step_loss_gap", abs(got - ref_mean), "loss_atol"))
            if math.isfinite(tr[limit])]  # a limit of inf: read, not held
        self.notes.append(
            f"one sequence's token losses vs the float32 reference: max "
            f"|diff| {token_gap:.3e} (bound {tr['token_loss_atol']}); "
            f"first-step loss {got:.6f} vs {ref_mean:.6f} (|diff| "
            f"{abs(got - ref_mean):.2e}, bound {tr['loss_atol']}) "
            f"{'ok' if ok else 'WRONG'}")

    def _next_batch(self, host=None):
        import jax

        a = token_batch(self.rng, self.vocab, self.batch, self.seq) \
            if host is None else host
        return (jax.device_put(a[:, :-1], self.data_sharding),
                jax.device_put(a[:, 1:], self.data_sharding))

    # ---------------------------------------------------------- window

    def run_for(self, seconds: float) -> dict:
        """Whole readings (``steps_per_reading`` steps, then the example's
        barrier and loss fetch) until ``seconds`` have passed.  The window
        ends at a barrier, so all its work is done inside its time."""
        import jax

        span = self.run.spans.span
        per_reading = self.traffic["steps_per_reading"]
        readings, bad = [], 0
        t0 = t_read = time.perf_counter()
        while t_read - t0 < seconds:
            for _ in range(per_reading):
                with span("perf.next_batch"):
                    tokens, targets = self._next_batch()
                with span("perf.train_step"):
                    self.state, _ = self.step(self.state, tokens, targets)
            with span("perf.barrier"):
                jax.block_until_ready(self.state.params)
                cum = float(self.state.loss_sum)
            now = time.perf_counter()
            window_loss = (cum - self.loss_sum_seen) / per_reading
            self.loss_sum_seen = cum
            if not math.isfinite(window_loss):
                bad += per_reading
            readings.append((now - t_read,
                             per_reading * self.batch * self.seq))
            t_read = now
        steps = len(readings) * per_reading
        return {"t0": t0, "t1": t_read, "steps": steps, "failed": bad,
                "samples": steps * self.batch * self.seq, "readings": readings,
                "data_wait_s": sum(self.run.spans.durations(
                    "perf.next_batch", t0, t_read)),
                "last_loss": window_loss}

    def report(self, seg: dict) -> dict:
        # all the window's samples over all its time
        rate = (seg["samples"] / (seg["t1"] - seg["t0"])
                / len(self.run.devices))
        self.notes.append(
            f"{len(seg['readings'])} readings of "
            f"{self.traffic['steps_per_reading']} steps in "
            f"{seg['t1'] - seg['t0']:.3f} s ("
            + " ".join(f"{dt:.3f}" for dt, _ in seg["readings"])
            + f"); last window loss {seg['last_loss']:.4f}")
        return {"end_to_end": {"train_throughput_per_chip": rate},
                "attempted": seg["steps"], "failed": seg["failed"],
                "correct": self.correct and seg["failed"] == 0}

    def close(self) -> None:
        pass
