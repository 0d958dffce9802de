"""``train_stream`` for a model that routes tokens to experts: the same
loop, window and rate, and three more comparisons in ``correct``.

A bf16 residual stream moves a router's scores by about a hundredth, which
is more than the 4th and 5th of 32 scores differ on some tokens, so the
system and a float32 reference choose one expert differently on a share of
them, and each such flip swaps a quarter of a layer's output.  The base
class's two free-routing numbers are therefore set by flips, and no limit
on either has room on both sides (traffic file, ``limits``): the largest
token difference of a sound run and of a float8 run read within 1.5 x of
each other, and a dropped expert layer moves the first step's mean loss by
less than flips move a sound run's.  The base class reads and notes both;
this driver does not hold them (a NaN still fails).

What holds the system to its stated precision is, on one sequence at the
timed length, through the system's own ``loss_fn`` path (the expert
choices read from what the layer sows):

  * choice: every expert the system chose has a reference score within
    ``route_tie_margin`` of the reference's k-th best on the same input
    (its choice is a valid top-k up to ties);
  * arithmetic, forward: with the reference given the system's choices,
    the per-token losses agree within ``routed_token_loss_atol``;
  * arithmetic, backward: the gradient of that sequence's mean loss with
    respect to every parameter, through the kernels' custom VJPs, against
    the reference's with the same choices: the largest relative
    difference of any leaf within ``routed_grad_rtol``.

The limits are the traffic file's, with their readings beside them.
"""

from __future__ import annotations

import math

from perf.drivers import train_stream
from perf.harness.loadgen import token_batch

CHECKS = (("choice_gap", "route_tie_margin"),
          ("routed_token_gap", "routed_token_loss_atol"),
          ("routed_grad_gap", "routed_grad_rtol"))


class Driver(train_stream.Driver):
    def setup(self) -> None:
        # read and noted by the base class, not held (module docstring)
        self.traffic = {**self.traffic, "token_loss_atol": math.inf,
                        "loss_atol": math.inf}
        super().setup()
        with self.run.spans.span("perf.setup.reference"):
            r = self.routed_readings(self.state.params)
        ok = self.routed_ok(r)
        self.correct &= ok
        self.compared += [(name, r[name], self.traffic[limit])
                          for name, limit in CHECKS]
        tr = self.traffic
        self.notes.append(
            f"expert choices vs the reference's scores: largest shortfall "
            f"{r['choice_gap']:.3e} (margin {tr['route_tie_margin']}); "
            f"with the choices forced, token losses: max |diff| "
            f"{r['routed_token_gap']:.3e} (bound "
            f"{tr['routed_token_loss_atol']}), gradients: largest relative "
            f"difference of a leaf {r['routed_grad_gap']:.3e} at "
            f"{r['routed_grad_leaf']} (bound {tr['routed_grad_rtol']}) "
            f"{'ok' if ok else 'WRONG'}")

    def routed_ok(self, readings: dict) -> bool:
        """Every reading within the traffic file's limit (a NaN is not)."""
        return all(readings[name] <= self.traffic[limit]
                   for name, limit in CHECKS)

    def routed_readings(self, params, system_params=None) -> dict:
        """The three readings of :data:`CHECKS` on one fresh sequence of
        the cell's length: the system on ``system_params`` (``params``
        unless given: a control hands the system rounded or damaged ones)
        against the reference on ``params``; beside the largest gradient
        difference the leaf it is at, and every leaf's."""
        import jax
        import numpy as np

        fam, cfg = self.cell.family, self.config
        model = fam.build_model(cfg, attn_impl=cfg["train"]["attn_impl"])
        mine = params if system_params is None else system_params
        one = token_batch(self.rng, self.vocab, 1, self.seq)
        x, y = one[:, :-1], one[:, 1:]
        got, chosen, grads = jax.jit(
            lambda p, x, y: fam.system_routed(model, p, x, y))(mine, x, y)
        with jax.default_matmul_precision("highest"):
            want, scores = jax.jit(
                lambda p, x, y, c: fam.reference_token_losses_routed(
                    p, x, y, cfg, c))(params, x, y, chosen)
            grad_gaps = jax.jit(
                lambda p, x, y, c, g: fam.reference_grad_gaps(
                    p, x, y, cfg, c, g))(params, x, y, chosen, grads)
        gaps = [float(fam.choice_gap(s, c))
                for s, c in zip(scores, chosen, strict=True)]
        by_leaf = {jax.tree_util.keystr(path): float(g) for path, g in
                   jax.tree_util.tree_flatten_with_path(grad_gaps)[0]}
        # a NaN compares false with everything: put it first
        leaf = max(by_leaf, key=lambda k: (by_leaf[k] != by_leaf[k],
                                           by_leaf[k]))
        return {"choice_gap": max(gaps),
                "routed_token_gap": float(np.max(np.abs(
                    np.asarray(got) - np.asarray(want)))),
                "routed_grad_gap": by_leaf[leaf], "routed_grad_leaf": leaf,
                "routed_grad_gaps": by_leaf}
