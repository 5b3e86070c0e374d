"""Plain reference of the training methods the cells run.

Follows the program's first ``Strategy.run`` step by step from the same
weights and on the same batches, in the precision it is given.  SplitFed
v3 (``sflv3_*``): every step, each hospital runs its own segment on its
next batch (a hospital that has used all its batches starts them again),
the server's segment is updated once with the mean of the hospitals'
server gradients, and each hospital's segment with its own gradient.

Adam (b1 0.9, b2 0.999, eps 1e-8) keeps one state per hospital segment
and one for the server.  Batches follow the epoch's shuffle: each
hospital's indices permuted by the run's generator, in hospital order,
cut into whole batches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip import numerics as N

B1, B2, EPS = 0.9, 0.999, 1e-8


def epoch_batches(rng: np.random.Generator, sizes, batch: int) -> list:
    """Per hospital, its epoch's batches as index arrays."""
    out = []
    for n in sizes:
        idx = np.arange(n)
        rng.shuffle(idx)
        out.append([idx[j * batch:(j + 1) * batch]
                    for j in range(n // batch)])
    return out


def schedule(method: str, n_batches) -> list[tuple[int, ...]]:
    """Rows of one epoch: per step, the batch index of every hospital."""
    if method.rsplit("_", 1)[0] != "sflv3":
        raise ValueError(f"the reference follows SplitFed v3, not {method!r}")
    return [tuple(s % nb for nb in n_batches) for s in range(max(n_batches))]


def adam_init(tree, lead=()):
    z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), tree)
    return {"t": jnp.zeros(lead, jnp.float32), "m": z, "v": z}


def adam(params, grads, st, lr):
    t = st["t"] + 1
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, st["m"], grads)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, st["v"], grads)

    def upd(p, a, b):
        shape = (-1,) + (1,) * (p.ndim - t.ndim) if t.ndim else ()
        c1 = (1 - B1 ** t).reshape(shape)
        c2 = (1 - B2 ** t).reshape(shape)
        return p - lr * (a / c1) / (jnp.sqrt(b / c2) + EPS)

    return jax.tree.map(upd, params, m, v), {"t": t, "m": m, "v": v}


def mhat(st):
    """The optimizer's bias-corrected first moment: the gradient as the
    update sees it."""
    t = st["t"]

    def one(a):
        shape = (-1,) + (1,) * (a.ndim - t.ndim) if t.ndim else ()
        return a / (1 - B1 ** t).reshape(shape)

    return jax.tree.map(one, st["m"])


class Trajectory:
    """The reference's SplitFed v3 run over one epoch's steps."""

    def __init__(self, family, model: dict, link: str, lr: float, prec: str,
                 half_batch: bool = False):
        """``half_batch`` plants a fault: each step's loss is the mean
        over the first half of the batch only."""
        self.lr = lr

        def loss(front, server, x, y):
            if half_batch:
                x, y = x[: max(1, len(x) // 2)], y[: max(1, len(y) // 2)]
            z = family.logits({"front": front, "middle": server}, x, model,
                              prec, link)
            return N.bce(z, y)

        grad = jax.value_and_grad(loss, argnums=(0, 1))

        def sflv3(fronts, server, cst, sst, x, y):
            losses, (gf, gs) = jax.vmap(grad, in_axes=(0, None, 0, 0))(
                fronts, server, x, y)
            gs = jax.tree.map(lambda g: g.mean(axis=0), gs)
            fronts, cst = adam(fronts, gf, cst, lr)
            server, sst = adam(server, gs, sst, lr)
            return fronts, server, cst, sst, losses

        self._step = jax.jit(sflv3)

    def run(self, fronts, server, data: list[dict], orders: list, rows):
        """Follow ``rows`` (see ``schedule``) from ``(fronts, server)``;
        ``data[c]`` is hospital ``c``'s train split and ``orders[c]`` its
        batches.  Returns the losses of every step and the state after the
        last."""
        n = len(data)
        cst = adam_init(fronts, (n,))
        sst = adam_init(server)
        losses = []
        for row in rows:
            sel = [orders[c][b] for c, b in enumerate(row)]
            x = np.stack([data[c]["image"][s] for c, s in enumerate(sel)])
            y = np.stack([data[c]["label"][s] for c, s in enumerate(sel)])
            fronts, server, cst, sst, loss = self._step(
                fronts, server, cst, sst, x, y)
            losses.append(loss)
        return {"losses": np.stack([np.asarray(v) for v in losses]),
                "fronts": fronts, "server": server,
                "mhat_fronts": mhat(cst), "mhat_server": mhat(sst)}
