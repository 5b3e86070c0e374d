"""Split-learning traffic: whole ``Strategy.run`` calls of a split-learning
method (``sl_am``: alternate mini-batch turns), one epoch each.

Set-up, the measured window and the first run's snapshot are
``drivers/train.Cell``'s; the reference that checks the first run is
``ref_split``: the hospitals' steps in turn, each updating its own
segment and the one server segment.  ``train_images_per_s``: the images
of every step of the window's runs over the window.

The compared numbers are ``compare.train_numbers`` and ``cut_gap``: per
array that crosses the cut, the norm of the gap between what the run's
first batch sends, as the program's hospital segment computes it
(``SplitAdapter.apply_seg``) and as the reference's does, over the
reference array's norm; the worst array.  Taken from the seed's weights,
before the link.  The loss is a scalar that a smooth max over every
pixel rounds alike at any precision, and the run's change is set by Adam
at about the learning rate per coordinate: neither sees the precision of
the products at the cell's size.  The cut's activations do, each
element the end of a chain of products.  The gradient does not serve:
below the decoder's first units its leaves are sums of terms that group
norm makes cancel, and float32 summation in any order leaves a gap there
that ``high3`` does not widen.
"""

from __future__ import annotations

import jax
import numpy as np

from chip import gen, numerics, ref_split
from chip.compare import train_numbers
from chip.drivers import train
from chip.ref_train import epoch_batches


class Cell(train.Cell):
    def __init__(self, ctx):
        if ctx.mix["method"] != "sl_am":
            raise ValueError("the reference follows sl_am, not "
                             f"{ctx.mix['method']!r}")
        super().__init__(ctx)

    def first_cut(self):
        """What the program's hospital segment sends across the cut for
        the run's first batch, from the seed's weights, before the link."""
        ctx = self.ctx
        c, s = _first_batch(_orders(ctx.seed, self.data, ctx.mix))
        batch = {k: self.data[c][k][s] for k in ("image", "label")}
        adapter = self.strat.adapter
        front = jax.tree.map(lambda a: a[c], self.init["fronts"])
        out = jax.jit(lambda f, b: adapter.apply_seg(
            "front", f, adapter.inputs(b), b, True))(front, batch)
        return jax.tree.map(np.asarray, out)

    def check(self) -> dict:
        ctx = self.ctx
        prog = dict(self.first, first_cut=self.first_cut())
        return numbers(prog, reference_run(
            ctx.family, ctx.cfg["model"], ctx.mix, self.data, self.init,
            ctx.seed, "highest"), self.init)


def cut_gap(prog, ref) -> float:
    """The worst array's gap between two cuts, over the reference array's
    norm."""
    return max(float(np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     / np.linalg.norm(np.asarray(b, np.float64)))
               for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)))


def numbers(prog: dict, ref: dict, init: dict) -> dict:
    """``compare.train_numbers`` with ``cut_gap`` after the first loss's
    gap; ``prog`` and ``ref`` carry ``first_cut``."""
    out = train_numbers(prog, ref, init)
    return {"first_loss_gap": out.pop("first_loss_gap"),
            "cut_gap": cut_gap(prog["first_cut"], ref["first_cut"]), **out}


def _orders(seed, data, mix) -> list:
    """Per hospital, the first run's batches (its epoch's shuffle)."""
    rng = gen.seed_rng(seed, train.RUN_STREAM)
    return epoch_batches(rng, [len(d["label"]) for d in data],
                         int(mix["batch"]))


def _first_batch(orders):
    """``(hospital, image indices)`` of the run's first step."""
    c, b = ref_split.schedule([len(o) for o in orders])[0]
    return c, orders[c][b]


def reference_run(family, model, mix, data, init, seed, prec,
                  half_batch=False) -> dict:
    """The reference's first run, from ``init``, in precision ``prec``
    (``half_batch``: with that fault planted), and what its first batch
    sends across the cut before the link (``first_cut``)."""
    orders = _orders(seed, data, mix)
    rows = ref_split.schedule([len(o) for o in orders])
    c, s = _first_batch(orders)
    cut = jax.jit(lambda f, x: family.segment(f, "front", x, model, prec))(
        jax.tree.map(lambda a: a[c], init["fronts"]),
        data[c]["image"][s].astype(numerics.act_dtype(prec)))
    traj = ref_split.Trajectory(family, model, mix["link"], mix["lr"], prec,
                                half_batch)
    out = traj.run(jax.device_put(init["fronts"]),
                   jax.device_put(init["server"]), data, orders, rows)
    return jax.tree.map(np.asarray, dict(out, first_cut=cut))
