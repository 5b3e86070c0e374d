#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

For one cell and several seeds, in one process::

    python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        [--controls high3,bf16,default] [--out FILE]

prints one JSON line per reading, each the cell's compared numbers:

* ``program`` — the program as the cell runs it, against the reference
  (the lower readings);
* ``control:<precision>`` — the reference computed in that precision put
  in the program's place (see ``numerics``), against the reference;
* ``fault:half_batch`` — the reference with each step's loss taken over
  half the batch.  A state returned unchanged reads 1 on ``change_gap``
  without a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "benchmarks")]

from chip import harness  # noqa: E402


def readings(ctx, controls) -> list[tuple[str, dict]]:
    from chip import compare
    out = []
    driver = ctx.lib.module("drivers", ctx.mix["driver"])
    t = time.perf_counter()
    cell = driver.Cell(ctx)
    ctx.log(f"set-up {time.perf_counter() - t} s")
    model = ctx.cfg["model"]
    cell.release()
    t = time.perf_counter()
    ref = driver.reference_run(ctx.family, model, ctx.mix, cell.data,
                               cell.init, ctx.seed, "highest")
    ctx.log(f"reference {time.perf_counter() - t} s")
    out.append(("program", compare.train_numbers(cell.first, ref,
                                                  cell.init)))
    for prec in controls:
        alt = driver.reference_run(ctx.family, model, ctx.mix, cell.data,
                                   cell.init, ctx.seed, prec)
        out.append((f"control:{prec}",
                    compare.train_numbers(alt, ref, cell.init)))
    alt = driver.reference_run(ctx.family, model, ctx.mix, cell.data,
                               cell.init, ctx.seed, "highest",
                               half_batch=True)
    out.append(("fault:half_batch",
                compare.train_numbers(alt, ref, cell.init)))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="high3")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    import jax
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = {c["name"]: c for c in bench["workloads"]}[args.workload]
    device = harness.device_gate(cell["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {device} cache {enable_compile_cache()}", flush=True)
    controls = [c for c in args.controls.split(",") if c]
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(bench, cell, harness.Library(), seed, 0.0,
                              False)
        ctx.clock = harness.CompileClock()
        prec = ctx.cfg["matmul_precision"]
        with jax.default_matmul_precision(prec):
            for what, nums in readings(ctx, controls):
                line = json.dumps({"workload": args.workload, "seed": seed,
                                   "program_precision": prec,
                                   "reading": what, **nums})
                print(line, flush=True)
                lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
