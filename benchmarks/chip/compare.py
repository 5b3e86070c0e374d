"""The comparison that decides ``correct``: numbers of a program's output
against the plain reference's, each checked against its limit.

Training (per cell, the program's first run against the reference
following the same steps from the same weights on the same batches):

* ``first_loss_gap`` — the largest absolute gap between the loss of the
  run's first step as the program reports it and as the reference
  computes it, over every hospital: the whole forward pass at the
  seed's weights, through the link.
* ``change_gap`` — per parameter leaf (each hospital's segment and the
  server's), the gap between the norms of the leaf's change over the run,
  program against reference, over the larger of that leaf's reference
  norm and the median leaf's; the worst leaf.  Leaves whose reference
  first moment is under a thousandth of the median leaf's are left out:
  they move by round-off alone.
* not compared, reported: ``run_loss_gap``, the same as the first over
  every step of the run, and ``moment_gap``, the worst leaf's gap of the
  optimizer's bias-corrected first moment after the run.  Adam moves
  every coordinate by about the learning rate whatever its gradient, so
  two correct programs that sum in another order part after a few steps
  and these read that parting, not a fault.
"""

from __future__ import annotations

import numpy as np


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


def leaf_norms(fronts, server) -> list[float]:
    """Norms of every leaf: each hospital's slice of each stacked segment
    leaf, then each server leaf (a fixed order for any state)."""
    import jax
    out = []
    for leaf in jax.tree.leaves(fronts):
        out += [_norm(leaf[c]) for c in range(leaf.shape[0])]
    out += [_norm(leaf) for leaf in jax.tree.leaves(server)]
    return out


def _gaps(prog: list, ref: list, keep=None) -> float:
    p, r = np.asarray(prog), np.asarray(ref)
    if keep is not None:
        p, r = p[keep], r[keep]
    den = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / den))


def train_numbers(prog: dict, ref: dict, init: dict) -> dict:
    """``prog``/``ref``: ``losses`` (steps x hospitals, or steps),
    ``fronts``/``server`` after the run, ``mhat_fronts``/``mhat_server``;
    ``init``: ``fronts``/``server`` before it."""
    import jax
    gap = np.abs(np.asarray(prog["losses"], np.float64)
                 - np.asarray(ref["losses"], np.float64))
    g_p = leaf_norms(prog["mhat_fronts"], prog["mhat_server"])
    g_r = leaf_norms(ref["mhat_fronts"], ref["mhat_server"])

    def change(s):
        return leaf_norms(
            jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         s["fronts"], init["fronts"]),
            jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         s["server"], init["server"]))

    keep = np.asarray(g_r) >= 1e-3 * np.median(g_r)
    return {"first_loss_gap": float(np.max(gap[0])),
            "change_gap": _gaps(change(prog), change(ref), keep),
            "run_loss_gap": float(np.max(gap)),
            "moment_gap": _gaps(g_p, g_r)}


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """Each compared number beside its limit; correct when every one is
    within it.  A number without a limit is reported and not compared."""
    lines, ok = [], True
    for name, value in numbers.items():
        limit = limits.get(name)
        if limit is None:
            lines.append(f"{name} {value!r} (not compared)")
            continue
        good = bool(np.isfinite(value)) and value <= limit
        ok &= good
        lines.append(f"{name} {value!r} limit {limit!r}"
                     f"{'' if good else ' FAILED'}")
    return ok, lines
