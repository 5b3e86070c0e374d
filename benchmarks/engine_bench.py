"""Engine benchmark — stepwise (host-loop) vs compiled (scan/vmap) epochs
and whole runs.

For each method and hospital count, trains the same synthetic CXR task
with both execution engines and reports steps/sec and epoch wall-clock
(median over timed epochs, compile/warm-up epoch excluded for BOTH
engines — the comparison is steady-state dispatch cost, which is what
dominates the many-hospital sweeps in ROADMAP's production target).  A
second column times ``Strategy.run(n_epochs=RUN_EPOCHS)`` — the compiled
engine executes the whole run as ONE XLA program, the stepwise engine as
a per-epoch loop.

A telemetry column runs each compiled whole-run twice — with and without
the full ``repro.obs.Telemetry`` tap spec — and records the steady-state
overhead the extra scan outputs cost; with ``--check-against`` the
overhead must stay under ``--max-telemetry-overhead`` (default 5%).

Writes ``benchmarks/results/BENCH_engine.json``:

    {"results": [{"method", "n_clients", "engine", "mode",
                  "steps_per_epoch", "epoch_seconds", "steps_per_sec"},
                 ...],                  # run3 rows add compile_seconds,
                                        # dispatches_per_run, observed,
                                        # precision, fused (+ the raw-speed
                                        # rows: hlo_compile_seconds and the
                                        # compiler's "memory" buffer view)
     "speedup": {"fl@10": 7.3,          # compiled / stepwise, one epoch
                 "fl@10:run3": 9.1,     # whole 3-epoch run
                 "sl_am@10:fused": 1.4, # fused kernel  vs fp32-unfused
                 "sl_am@10:bf16": 1.6}, # bf16 compute  vs fp32-unfused
     "telemetry_overhead": {"fl@10": 0.012}}

A raw-speed grid (compiled whole-run only) times each method at
(fp32, unfused int8 transport) -> (fp32, fused cut-layer kernel) ->
(bf16, fused); the ``:fused`` / ``:bf16`` keys are steps/s ratios over
the fp32-unfused oracle and gate under ``--check-against`` exactly like
the engine speedups — a drop below 80% of the committed floor fails.
The variants are timed ROUND-ROBIN (one run each per pass, median per
variant — ``time_raw_grid``): shared hosts drift the SAME program ±30%
with position in the process, so sequential per-variant timing measures
schedule, not code.  On a CPU runner the fused and unfused programs
compile to the SAME optimized HLO (interpret-mode Pallas re-fuses under
XLA), so the CPU ratio is ~1.0 and its gate is a pure no-regression
guard; the kernel's single-VMEM-pass saving is real hardware's story.
bf16 on CPU runs through f32 converts and may sit slightly under 1.0 —
also honest.

``--shard`` additionally times the compiled engine with
``make_strategy(..., shard=True)`` — the hospital axis placed on the
``core.placement`` "hosp" mesh (pad-to-mesh phantom hospitals when the
count does not divide the device count) — recorded as ``:shard`` rows
and speedup keys.  Run it under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` or on a real
multi-device host; on one device shard=True is a no-op and the column
just duplicates the compiled numbers.

``--participation`` adds the cross-device column: fl compiled whole-runs
with ``Participation(n_global=N, k=10)`` at N in {100, 1000} — fixed-K
cohorts packed into a K-slot axis, so steps/s should stay roughly flat
in N (the ``fl@1000:part_scale`` speedup key records the N=1000/N=100
ratio).  ``--participation-only`` runs just this column and merges its
rows into an existing ``--out`` JSON (the full grid is expensive; the
participation column can be refreshed alone).

``--check-against BENCH.json`` re-reads a committed baseline and FAILS
(exit 1) if any matching compiled-vs-stepwise speedup regressed by more
than 20%.  Speedups are regime-sensitive (steps per epoch change how far
dispatch overhead is amortized), so gate like against like: the slow CI
job runs the smoke grid against the committed
``benchmarks/results/BENCH_engine_smoke.json``, and the multi-device job
runs ``--smoke --shard`` against
``benchmarks/results/BENCH_engine_smoke_shard.json``.

  PYTHONPATH=src python -m benchmarks.engine_bench [--smoke] [--shard]
      [--methods fl,sl_am,sflv3_ac] [--clients 3,10,50] [--epochs N]
      [--run-epochs 3] [--check-against PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet

DEFAULT_METHODS = ["fl", "sl_am", "sflv3_ac"]
DEFAULT_CLIENTS = [3, 10, 50]
OUT = os.path.join(os.path.dirname(__file__), "results",
                   "BENCH_engine.json")


def build_setup(n_clients: int, train_per_client: int, image_size: int):
    """Dispatch-bound regime: per-step compute is kept tiny so the numbers
    isolate what the engine changes — host dispatch and hospital-axis
    parallelism — rather than conv throughput."""
    clients = make_cxr_clients(seed=0, n_clients=n_clients,
                               train_per_client=train_per_client,
                               val_per_client=8, test_per_client=8,
                               image_size=image_size)
    cfg = DenseNetConfig(growth=2, blocks=(1, 1), stem_ch=4, cut_layer=1)
    return clients, cnn_adapter(build_densenet(cfg))


def time_engine(method, engine, clients, adapter, batch_size, epochs,
                shard=False):
    strat = make_strategy(method, adapter, lambda: O.adam(1e-3),
                          len(clients), engine=engine, shard=shard)
    state = strat.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    data = [c.train for c in clients]
    # warm-up epoch: tracing + compilation for both engines
    state, log = strat.run_epoch(state, data, rng, batch_size)
    times = []
    for _ in range(epochs):
        jax.block_until_ready(jax.tree.leaves(
            state.get("params", state.get("server")))[0])
        t0 = time.perf_counter()
        state, log = strat.run_epoch(state, data, rng, batch_size)
        jax.block_until_ready(jax.tree.leaves(
            state.get("params", state.get("server")))[0])
        times.append(time.perf_counter() - t0)
    sec = float(np.median(times))
    return {"method": method, "n_clients": len(clients), "engine": engine,
            "mode": "epoch", "shard": bool(shard),
            "steps_per_epoch": log.steps, "epoch_seconds": sec,
            "steps_per_sec": log.steps / sec if sec > 0 else float("inf")}


def time_whole_run(method, engine, clients, adapter, batch_size,
                   run_epochs, reps, shard=False, observe=False,
                   precision="fp32", fuse=None, cost=False):
    """Time ``Strategy.run(n_epochs=run_epochs)`` — ONE program under the
    compiled engine, a per-epoch loop under stepwise.  ``observe=True``
    runs with the full telemetry spec (repro.obs) — the taps ride the
    run scan as extra outputs, so the steady-state cost they add is what
    the telemetry-overhead gate measures.  ``fuse`` (raw-speed grid) adds
    an int8 cut-layer transport with the fused kernel on or off;
    ``precision`` selects bf16 compute; ``cost=True`` re-lowers the
    compiled run for the compiler's peak-HBM view (``obs.profile``)."""
    from repro.obs import Telemetry
    transport = None
    if fuse is not None:
        from repro.wire import Transport
        transport = Transport("int8", fuse=fuse)
    strat = make_strategy(method, adapter, lambda: O.adam(1e-3),
                          len(clients), engine=engine, shard=shard,
                          transport=transport, precision=precision,
                          observe=Telemetry() if observe else None)
    state = strat.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    data = [c.train for c in clients]
    t0 = time.perf_counter()
    state, logs = strat.run(state, data, rng, batch_size, run_epochs)
    first_call = time.perf_counter() - t0        # trace+compile dominated
    times = []
    for _ in range(reps):
        jax.block_until_ready(jax.tree.leaves(
            state.get("params", state.get("server")))[0])
        t0 = time.perf_counter()
        state, logs = strat.run(state, data, rng, batch_size, run_epochs)
        jax.block_until_ready(jax.tree.leaves(
            state.get("params", state.get("server")))[0])
        times.append(time.perf_counter() - t0)
    sec = float(np.median(times))
    steps = sum(l.steps for l in logs)
    row = {"method": method, "n_clients": len(clients), "engine": engine,
           "mode": f"run{run_epochs}" + (":obs" if observe else ""),
           "shard": bool(shard), "observed": bool(observe),
           "precision": precision,
           "fused": None if fuse is None else bool(fuse),
           "steps_per_epoch": steps, "epoch_seconds": sec,
           "compile_seconds": first_call - sec,
           "dispatches_per_run": strat._dispatches // (reps + 1),
           "steps_per_sec": steps / sec if sec > 0 else float("inf")}
    if cost:
        from repro.obs.profile import hlo_cost
        hc = hlo_cost(strat)
        if hc is not None:
            row["hlo_compile_seconds"] = hc["compile_seconds"]
            row["memory"] = hc["memory"]
    return row


def time_raw_grid(method, clients, adapter, batch_size, run_epochs, reps,
                  variants):
    """Round-robin timing of the raw-speed variants — one timed run per
    variant per pass.  Sequential per-variant timing is unusable for a
    ratio gate on shared hosts: the SAME program drifts ±30% with
    position in the process (allocator growth, cgroup throttle phases),
    so whichever variant runs later eats the drift.  Interleaving spreads
    it across all variants; the per-variant median over passes is fair."""
    from repro.obs.profile import hlo_cost
    data = [c.train for c in clients]
    recs = []
    for prec, fu in variants:
        transport = None
        if fu is not None:
            from repro.wire import Transport
            transport = Transport("int8", fuse=fu)
        strat = make_strategy(method, adapter, lambda: O.adam(1e-3),
                              len(clients), transport=transport,
                              precision=prec)
        state = strat.setup(jax.random.key(0))
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        state, logs = strat.run(state, data, rng, batch_size, run_epochs)
        recs.append({"prec": prec, "fu": fu, "strat": strat, "state": state,
                     "rng": rng, "logs": logs,
                     "first": time.perf_counter() - t0, "times": []})
    for _ in range(reps):
        for rec in recs:
            s = rec["state"]
            jax.block_until_ready(jax.tree.leaves(
                s.get("params", s.get("server")))[0])
            t0 = time.perf_counter()
            rec["state"], rec["logs"] = rec["strat"].run(
                rec["state"], data, rec["rng"], batch_size, run_epochs)
            jax.block_until_ready(jax.tree.leaves(
                rec["state"].get("params", rec["state"].get("server")))[0])
            rec["times"].append(time.perf_counter() - t0)
    rows = []
    for rec in recs:
        sec = float(np.median(rec["times"]))
        steps = sum(l.steps for l in rec["logs"])
        row = {"method": method, "n_clients": len(clients),
               "engine": "compiled", "mode": f"run{run_epochs}",
               "shard": False, "observed": False,
               "precision": rec["prec"],
               "fused": None if rec["fu"] is None else bool(rec["fu"]),
               "steps_per_epoch": steps, "epoch_seconds": sec,
               "compile_seconds": rec["first"] - sec,
               "dispatches_per_run": rec["strat"]._dispatches // (reps + 1),
               "steps_per_sec": steps / sec if sec > 0 else float("inf")}
        hc = hlo_cost(rec["strat"])
        if hc is not None:
            row["hlo_compile_seconds"] = hc["compile_seconds"]
            row["memory"] = hc["memory"]
        rows.append(row)
    return rows


PART_CLIENTS = [100, 1000]
PART_K = 10


def time_participation(n_global, k, batch_size, run_epochs, reps):
    """Cross-device column: fl with ``Participation(n_global, k)`` — each
    round trains a K-hospital cohort out of N enrolled.  The compiled
    run packs cohorts into a fixed K-slot axis, so steps/s should be
    roughly FLAT in N (compute scales with K; only host-side packing
    and data bookkeeping grow with N) — the ``:part_scale`` speedup key
    gates that ratio.  Tiny per-hospital data keeps the N=1000 setup
    affordable on CPU."""
    from repro.core.participation import Participation
    clients, adapter = build_setup(n_global, 16, image_size=8)
    strat = make_strategy("fl", adapter, lambda: O.adam(1e-3), n_global,
                          participation=Participation(n_global=n_global,
                                                      k=k))
    state = strat.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    data = [c.train for c in clients]
    t0 = time.perf_counter()
    state, logs = strat.run(state, data, rng, batch_size, run_epochs)
    first_call = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        jax.block_until_ready(jax.tree.leaves(state["params"])[0])
        t0 = time.perf_counter()
        state, logs = strat.run(state, data, rng, batch_size, run_epochs)
        jax.block_until_ready(jax.tree.leaves(state["params"])[0])
        times.append(time.perf_counter() - t0)
    sec = float(np.median(times))
    steps = sum(l.steps for l in logs)
    return {"method": "fl", "n_clients": n_global, "engine": "compiled",
            "mode": f"run{run_epochs}:part", "shard": False,
            "participation_k": k, "steps_per_epoch": steps,
            "epoch_seconds": sec, "compile_seconds": first_call - sec,
            "dispatches_per_run": strat._dispatches // (reps + 1),
            "steps_per_sec": steps / sec if sec > 0 else float("inf")}


def run_participation_grid(batch_size, run_epochs, reps):
    rows, part = [], {}
    for n in PART_CLIENTS:
        r = time_participation(n, PART_K, batch_size, run_epochs, reps)
        rows.append(r)
        part[f"fl@{n}:k{PART_K}"] = round(r["steps_per_sec"], 1)
        print(f"{'fl':10s} n={n:4d} part(K={PART_K})    "
              f"run{run_epochs:<3d} {r['steps_per_sec']:9.1f} steps/s "
              f"({r['epoch_seconds'] * 1e3:8.1f} ms, "
              f"{r['dispatches_per_run']} dispatch/run)")
    lo, hi = PART_CLIENTS[0], PART_CLIENTS[-1]
    scale = (part[f"fl@{hi}:k{PART_K}"]
             / max(part[f"fl@{lo}:k{PART_K}"], 1e-9))
    print(f"{'fl':10s} part scaling N={hi} vs N={lo}: {scale:5.2f}x "
          "(fixed-K cohorts: ~flat in N)")
    return rows, part, round(scale, 2)


def check_telemetry_overhead(overhead: dict,
                             max_overhead: float = 0.05) -> list[str]:
    """Gate the steady-state cost of telemetry: an observed run's steps/s
    within ``max_overhead`` of the unobserved run's (the taps are extra
    scan outputs, not extra dispatches — >5% means something regressed
    into the hot path)."""
    failures = []
    for key, ov in overhead.items():
        status = "OK" if ov <= max_overhead else "REGRESSED"
        print(f"  telemetry {key:16s} overhead {ov * 100:6.2f}% "
              f"(max {max_overhead * 100:.0f}%)  {status}")
        if ov > max_overhead:
            failures.append(key)
    return failures


def check_against(baseline_path: str, speedup: dict,
                  max_regression: float = 0.2) -> list[str]:
    """Compare fresh compiled-vs-stepwise speedups to a committed
    baseline; a key regressing below ``(1 - max_regression) x`` its
    committed value is a failure."""
    with open(baseline_path) as f:
        committed = json.load(f).get("speedup", {})
    failures = []
    for key, new in speedup.items():
        old = committed.get(key)
        if old is None:
            continue
        floor = (1.0 - max_regression) * old
        status = "OK" if new >= floor else "REGRESSED"
        print(f"  gate {key:16s} committed {old:6.2f}x  now {new:6.2f}x "
              f"(floor {floor:.2f}x)  {status}")
        if new < floor:
            failures.append(key)
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (non-blocking slow job)")
    ap.add_argument("--methods", default=None)
    ap.add_argument("--clients", default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--run-epochs", type=int, default=3,
                    help="whole-run column: epochs per Strategy.run call")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--train-per-client", type=int, default=None)
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--check-against", default=None,
                    help="committed BENCH_engine.json to gate speedups "
                         "against (fail on >20%% regression); also gates "
                         "telemetry overhead")
    ap.add_argument("--max-telemetry-overhead", type=float, default=0.05,
                    help="fail when an observed compiled run's steady-"
                         "state steps/s falls more than this fraction "
                         "below the unobserved run's")
    ap.add_argument("--participation", action="store_true",
                    help="also time fl with Participation(N, k=10) at "
                         f"N in {PART_CLIENTS} (compiled whole-run; "
                         "steps/s should stay ~flat in N)")
    ap.add_argument("--participation-only", action="store_true",
                    help="run ONLY the participation column and merge "
                         "its rows/keys into an existing --out JSON")
    ap.add_argument("--shard", action="store_true",
                    help="also time the compiled engine with shard=True "
                         "(hospital axis on the hosp device mesh; run "
                         "under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N or on a multi-device host); "
                         "recorded as ':shard' speedup keys")
    args = ap.parse_args()

    epochs_po = args.epochs or (1 if args.smoke else 2)
    if args.participation_only:
        rows, part, scale = run_participation_grid(args.batch,
                                                   args.run_epochs,
                                                   epochs_po)
        out = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                out = json.load(f)
        out.setdefault("results", [])
        out["results"] = [r for r in out["results"]
                          if "part" not in r.get("mode", "")] + rows
        out["participation"] = part
        out.setdefault("speedup", {})[
            f"fl@{PART_CLIENTS[-1]}:part_scale"] = scale
        out_dir = os.path.dirname(args.out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"merged participation column into {args.out}")
        return

    methods = (args.methods.split(",") if args.methods
               else (["fl"] if args.smoke else DEFAULT_METHODS))
    clients_grid = ([int(x) for x in args.clients.split(",")]
                    if args.clients else ([3] if args.smoke
                                          else DEFAULT_CLIENTS))
    epochs = args.epochs or (1 if args.smoke else 2)
    tpc = args.train_per_client or (16 if args.smoke else 128)

    # shard=True times the compiled engine with the hospital axis on the
    # hosp mesh; the stepwise baseline never shards, so the ':shard'
    # speedup key gates the SHARDED compiled path against the same oracle
    shard_grid = [False] + ([True] if args.shard else [])
    results, speedup, overhead = [], {}, {}
    for n in clients_grid:
        clients, adapter = build_setup(n, tpc, image_size=8)
        # telemetry overhead needs enough steps per run to amortize the
        # fixed metric-stack readback (a realistic run has hundreds of
        # steps; the smoke grid's ~10 would gate host-transfer latency,
        # not the taps' marginal cost)
        tel_tpc = max(tpc, 96)
        tel_clients, tel_adapter = ((clients, adapter) if tel_tpc == tpc
                                    else build_setup(n, tel_tpc,
                                                     image_size=8))
        for method in methods:
            # telemetry overhead: compiled whole-run with and without the
            # full tap spec — same program shape, extra scan outputs only
            plain = time_whole_run(method, "compiled", tel_clients,
                                   tel_adapter, args.batch,
                                   args.run_epochs, epochs)
            obs = time_whole_run(method, "compiled", tel_clients,
                                 tel_adapter, args.batch, args.run_epochs,
                                 epochs, observe=True)
            results.append(obs)     # the plain run3 row rides the grid below
            ov = plain["steps_per_sec"] / max(obs["steps_per_sec"],
                                              1e-9) - 1.0
            overhead[f"{method}@{n}"] = round(max(ov, 0.0), 4)
            print(f"{method:10s} n={n:3d} telemetry "
                  f"{obs['steps_per_sec']:9.1f} vs "
                  f"{plain['steps_per_sec']:9.1f} steps/s "
                  f"({max(ov, 0.0) * 100:5.2f}% overhead, "
                  f"{obs['dispatches_per_run']} dispatch/run)")
            for mode_fn, tag in (
                    (lambda m, e, sh: time_engine(m, e, clients, adapter,
                                                  args.batch, epochs,
                                                  shard=sh), ""),
                    (lambda m, e, sh: time_whole_run(m, e, clients, adapter,
                                                     args.batch,
                                                     args.run_epochs,
                                                     epochs, shard=sh),
                     f":run{args.run_epochs}")):
                base = mode_fn(method, "stepwise", False)
                results.append(base)
                print(f"{method:10s} n={n:3d} {'stepwise':15s} "
                      f"{base['mode']:6s} {base['steps_per_sec']:9.1f} "
                      f"steps/s ({base['epoch_seconds'] * 1e3:8.1f} ms)")
                for sh in shard_grid:
                    r = mode_fn(method, "compiled", sh)
                    results.append(r)
                    name = "compiled" + (":shard" if sh else "")
                    print(f"{method:10s} n={n:3d} {name:15s} "
                          f"{r['mode']:6s} {r['steps_per_sec']:9.1f} "
                          f"steps/s ({r['epoch_seconds'] * 1e3:8.1f} ms)")
                    sp = r["steps_per_sec"] / base["steps_per_sec"]
                    key = f"{method}@{n}{tag}" + (":shard" if sh else "")
                    speedup[key] = round(sp, 2)
                    print(f"{method:10s} n={n:3d} speedup {name:8s}"
                          f" {sp:7.2f}x")

        # raw-speed grid: fused cut-layer kernel and bf16 compute vs the
        # fp32-UNFUSED oracle, compiled whole-run only.  Non-split methods
        # have no cut layer, so their grid is precision-only; when the
        # method list has no split member one is added so the fused kernel
        # is always gated.  ':fused' / ':bf16' speedup keys feed the same
        # --check-against floor as the engine speedups.  Skipped under
        # --shard: virtual-device hosts split the thread pool and measure
        # scheduler noise, not the kernel — the unsharded smoke job owns
        # this gate.
        if args.shard:
            continue
        raw_methods = list(methods)
        if all(m in ("fl", "centralized") for m in raw_methods):
            raw_methods.append("sl_am")
        raw_reps = max(epochs, 5)
        for method in raw_methods:
            split = method not in ("fl", "centralized")
            variants = ([("fp32", False), ("fp32", True), ("bf16", True)]
                        if split else [("fp32", None), ("bf16", None)])
            rows = time_raw_grid(method, tel_clients, tel_adapter,
                                 args.batch, args.run_epochs, raw_reps,
                                 variants)
            base = rows[0]
            for r in rows:
                results.append(r)
                name = r["precision"] + {None: "", True: "+fused",
                                         False: "+unfused"}[r["fused"]]
                hbm = (r.get("memory") or {}).get("temp_size_in_bytes")
                print(f"{method:10s} n={n:3d} {name:15s} "
                      f"run{args.run_epochs:<3d} "
                      f"{r['steps_per_sec']:9.1f} steps/s"
                      + (f" (temp HBM {hbm / 1e3:.1f} kB)" if hbm else ""))
                if r is base:
                    continue
                tagv = "bf16" if r["precision"] == "bf16" else "fused"
                sp = r["steps_per_sec"] / base["steps_per_sec"]
                speedup[f"{method}@{n}:{tagv}"] = round(sp, 2)
                print(f"{method:10s} n={n:3d} speedup {tagv:8s}"
                      f" {sp:7.2f}x  (vs fp32"
                      + (":unfused)" if split else ")"))

    out = {"device": jax.devices()[0].device_kind,
           "n_devices": jax.device_count(),
           "batch_size": args.batch, "train_per_client": tpc,
           "epochs_timed": epochs, "run_epochs": args.run_epochs,
           "results": results, "speedup": speedup,
           "telemetry_overhead": overhead}
    if args.participation:
        rows, part, scale = run_participation_grid(args.batch,
                                                   args.run_epochs, epochs)
        out["results"] += rows
        out["participation"] = part
        speedup[f"fl@{PART_CLIENTS[-1]}:part_scale"] = scale
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")

    if args.check_against:
        failures = check_against(args.check_against, speedup)
        if failures:
            print(f"FAIL: speedup regressed >20% vs committed baseline "
                  f"for {failures}")
            sys.exit(1)
        print("speedup gate OK (within 20% of committed baseline)")
        tel_failures = check_telemetry_overhead(overhead,
                                                args.max_telemetry_overhead)
        if tel_failures:
            print(f"FAIL: telemetry overhead above "
                  f"{args.max_telemetry_overhead * 100:.0f}% for "
                  f"{tel_failures}")
            sys.exit(1)
        print("telemetry overhead gate OK "
              f"(<={args.max_telemetry_overhead * 100:.0f}%)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
