#!/usr/bin/env python3
"""``control.py``'s readings for a split-learning cell, with the split
driver's compared numbers (``drivers/split.numbers``: ``cut_gap``
besides ``compare.train_numbers``) and each reading's verdict at the
cell's limits.  For one cell and several seeds, in one process::

    python benchmarks/chip/control_split.py --workload unet768.sl_am_int8 \\
        --seeds 1,2,3 [--controls high3,default] [--out FILE]

prints one JSON line per reading (``program``, ``control:<precision>``,
``fault:half_batch``; see ``control.py``), with ``correct`` as
``compare.verdict`` gives it.
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
    driver = ctx.lib.module("drivers", ctx.mix["driver"])
    t = time.perf_counter()
    cell = driver.Cell(ctx)
    ctx.log(f"set-up {time.perf_counter() - t} s")
    cell.release()
    prog = dict(cell.first, first_cut=cell.first_cut())

    def ref(prec, half_batch=False):
        return driver.reference_run(ctx.family, ctx.cfg["model"], ctx.mix,
                                    cell.data, cell.init, ctx.seed, prec,
                                    half_batch=half_batch)

    t = time.perf_counter()
    base = ref("highest")
    ctx.log(f"reference {time.perf_counter() - t} s")
    out = [("program", driver.numbers(prog, base, cell.init))]
    for prec in controls:
        out.append((f"control:{prec}",
                    driver.numbers(ref(prec), base, cell.init)))
    out.append(("fault:half_batch",
                driver.numbers(ref("highest", True), base, cell.init)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="high3")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    from chip.compare import verdict
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
                                   "reading": what,
                                   "correct": verdict(nums, ctx.limits)[0],
                                   **nums})
                print(line, flush=True)
                lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
