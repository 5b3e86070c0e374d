"""The control of every cell comes out not correct: the reference in the
nearest precision below the configuration's (``high3`` for float32 at
``highest``) put in the program's place, at a tiny size, against the
tiny cell's limits (``conftest.TINY_LIMITS``).  On the chip at the
cells' own sizes the same readings come from ``control.py``."""

import pytest

from chip import compare, harness

CELLS = ["densenet121.sflv3_int8"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny, cell):
    import jax
    import numpy as np
    from chip import gen, weights
    bench, lib = tiny
    c = {w["name"]: w for w in bench["workloads"]}[f"tiny_{cell}"]
    ctx = harness.Context(bench, c, lib, 2**31 + 3, 0.5, False)
    assert ctx.cfg["matmul_precision"] == "highest"
    driver = lib.module("drivers", ctx.mix["driver"])
    model = ctx.cfg["model"]
    data = [d["train"] for d in gen.cxr_clients(
        ctx.seed, ctx.mix["train_per_client"], ctx.cfg["image_size"])]
    fronts, server = weights.make(ctx.family, model, ctx.seed, len(data))
    init = {"fronts": jax.tree.map(np.asarray, fronts),
            "server": jax.tree.map(np.asarray, server)}
    runs = {p: driver.reference_run(ctx.family, model, ctx.mix, data, init,
                                    ctx.seed, p)
            for p in ("highest", "high3")}
    numbers = compare.train_numbers(runs["high3"], runs["highest"], init)
    sound = compare.train_numbers(runs["highest"], runs["highest"], init)
    assert compare.verdict(sound, ctx.limits)[0] is True
    ok, lines = compare.verdict(numbers, ctx.limits)
    assert ok is False, lines
