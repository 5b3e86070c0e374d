"""Every cell's driver, end to end on the CPU at a tiny size, past the
device gate: the result line, the correctness check, and the check
failing when the timed path is broken underneath."""

import json

import numpy as np
import pytest

from chip.conftest import CPU

CELLS = ["densenet121.sflv3_int8"]
SEED = 2**31 + 7


def run(tiny, cell, seed=SEED, trace=False):
    from chip import harness
    bench, lib = tiny
    return harness.run_cell(bench, f"tiny_{cell}", seed, 0.5, trace,
                            lib=lib, device=dict(CPU))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny, cell, capsys):
    out = run(tiny, cell)
    line = json.loads(json.dumps(out))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = tiny[0]
    want = {m["name"] for m in bench["end_to_end"]
            if f"tiny_{cell}" in m.get("workloads", [f"tiny_{cell}"])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    err = capsys.readouterr().err.strip().splitlines()
    compared = [x for x in err if x.startswith("compared ")]
    assert compared and err[-len(compared):] == compared


def _keep_state(monkeypatch):
    """Every whole-run program returns the state it was given."""
    import jax
    import jax.numpy as jnp
    from repro.core.strategies import engine

    def wrap(make):
        def made(*a, **k):
            fn = make(*a, **k)

            def run(*args):
                kept = jax.tree.map(jnp.copy, args[:4])
                return (*kept, *fn(*args)[4:])
            return run
        return made

    monkeypatch.setattr(engine, "make_sflv3_run",
                        wrap(engine.make_sflv3_run))


def _half_batch(monkeypatch):
    """Every training loss is the mean over the first half of its batch."""
    from repro.core.partition import SplitAdapter
    full = SplitAdapter.full_loss

    def half(self, params, batch, *a, **k):
        n = max(1, len(batch["label"]) // 2)
        return full(self, params, {key: v[:n] for key, v in batch.items()},
                    *a, **k)

    monkeypatch.setattr(SplitAdapter, "full_loss", half)


FAULTS = [(c, f) for c in CELLS for f in (_keep_state, _half_batch)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(tiny, cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run(tiny, cell)
    assert out["correct"] is False, out["compared"]
