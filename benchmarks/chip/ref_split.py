"""Plain reference of split learning with alternate mini-batch turns
(``sl_am``, the surveyed paper's section 3.4).

Hospitals take turns by mini-batch: in turn ``b`` every hospital that
still has a ``b``-th batch trains on it, in hospital order, and a
hospital whose batches are used up drops out.  Each step, the hospital's
segment and the server's segment are updated from one loss, the mean
over the batch, each with its own Adam (one state per hospital segment,
one for the server; b1 0.9, b2 0.999, eps 1e-8).  Batches follow
``ref_train.epoch_batches``.

The gradient of a step is the mean of its images' gradients, one image
at a time, so that a step at the cells' sizes fits the chip without any
recomputation: the images of a batch do not interact (group norm is per
image), so this is the batch's gradient up to the order of summation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip import numerics as N
from chip.ref_train import adam, adam_init, mhat


def schedule(n_batches) -> list[tuple[int, int]]:
    """Rows of one epoch: per step, ``(hospital, batch index)``."""
    return [(c, b) for b in range(max(n_batches, default=0))
            for c, nb in enumerate(n_batches) if b < nb]


class Trajectory:
    """The reference's split-learning run over one epoch's steps."""

    def __init__(self, family, model: dict, link: str, lr: float, prec: str,
                 half_batch: bool = False):
        """``half_batch`` plants a fault: each step's loss is the mean
        over the first half of the batch only."""

        def loss1(front, server, x, y):
            z = family.logits({"front": front, "middle": server}, x[None],
                              model, prec, link)
            return N.bce(z, y[None])

        grad1 = jax.value_and_grad(loss1, argnums=(0, 1))

        def step(front, server, cst, sst, x, y):
            if half_batch:
                x, y = x[: max(1, len(x) // 2)], y[: max(1, len(y) // 2)]
            losses, (gf, gs) = jax.lax.map(
                lambda xy: grad1(front, server, *xy), (x, y))
            gf, gs = jax.tree.map(lambda g: g.mean(axis=0), (gf, gs))
            front, cst = adam(front, gf, cst, lr)
            server, sst = adam(server, gs, sst, lr)
            return front, server, cst, sst, losses.mean()

        self._step = jax.jit(step)

    def run(self, fronts, server, data: list[dict], orders: list, rows):
        """Follow ``rows`` (see ``schedule``) from ``(fronts, server)``,
        hospital segments stacked on a leading axis; ``data[c]`` is
        hospital ``c``'s train split and ``orders[c]`` its batches.
        Returns the losses of every step and the state after the last."""
        n = len(data)
        fronts = [jax.tree.map(lambda a, c=c: a[c], fronts) for c in range(n)]
        csts = [adam_init(f) for f in fronts]
        sst = adam_init(server)
        losses = []
        for c, b in rows:
            s = orders[c][b]
            x = jnp.asarray(data[c]["image"][s])
            y = jnp.asarray(data[c]["label"][s])
            fronts[c], server, csts[c], sst, loss = self._step(
                fronts[c], server, csts[c], sst, x, y)
            losses.append(loss)

        def stack(trees):
            return jax.tree.map(lambda *a: jnp.stack(a), *trees)

        return {"losses": np.asarray([np.asarray(v) for v in losses]),
                "fronts": stack(fronts), "server": server,
                "mhat_fronts": stack([mhat(s) for s in csts]),
                "mhat_server": mhat(sst)}
