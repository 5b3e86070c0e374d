"""Sweep codecs x network scenarios x methods through the wire simulator.

For every method of the paper's comparison, replay one epoch's cut-layer
transfer DAG through each (codec, scenario) pair and report bytes-on-wire,
simulated wall-clock, straggler sensitivity and per-client idle fractions.
The identity codec's byte total is cross-checked against the analytic
profile of ``repro.core.comm`` (paper Table 4) — the run fails loudly if
they disagree by more than 1%.  A second gate feeds a ``Transport`` the
exact per-epoch accounting the (default) compiled engine emits and checks
``timeline_from_accounting`` replays it to the same wall-clock and
per-tag bytes as ``simulate`` — so the sweep's numbers are valid
whichever engine trained.

Writes ``benchmarks/results/wire_sweep.json`` + ``.md``.

  PYTHONPATH=src python -m benchmarks.wire_sweep [--quick]
      [--methods sl_ac,...] [--codecs identity,...] [--scenarios lan,...]
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.core.comm import client_batch_counts, comm_per_epoch
from repro.core.partition import cnn_adapter
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.wire import (Transport, make_codec, make_network, simulate,
                        straggler_sensitivity, timeline_from_accounting)

DEFAULT_METHODS = ["fl", "sl_ac", "sl_am", "sflv2_ac", "sflv3_ac"]
DEFAULT_CODECS = ["identity", "bf16", "int8", "topk:0.1"]
DEFAULT_SCENARIOS = ["lan", "hospital_wan", "cellular"]

# the paper's 5 hospitals with very different data volumes (3772 vs 880
# samples in the TB task), scaled down so the sweep stays CPU-fast
N_TRAIN = [472, 236, 110, 472, 236]
N_VAL = [118, 59, 28, 118, 59]
BATCH = 32


def build_setup(quick: bool):
    cfg = (DenseNetConfig(growth=8, blocks=(2, 4), stem_ch=8, cut_layer=1)
           if quick else
           DenseNetConfig(growth=12, blocks=(4, 8, 6), stem_ch=16,
                          cut_layer=2))
    size = 16 if quick else 32
    adapter = cnn_adapter(build_densenet(cfg))
    example = {"image": np.zeros((BATCH, size, size, 1), np.float32),
               "label": np.zeros((BATCH,), np.float32)}
    n_tr = [n // 4 for n in N_TRAIN] if quick else N_TRAIN
    n_va = [max(n // 4, BATCH) for n in N_VAL] if quick else N_VAL
    return adapter, example, n_tr, n_va


def check_identity_matches_analytic(adapter, example, n_tr, n_va) -> list:
    """Acceptance gate: identity-codec sim bytes == comm.py within 1%."""
    rows = []
    for method in DEFAULT_METHODS:
        analytic = comm_per_epoch(method, adapter, example, n_tr, n_va,
                                  BATCH).bytes_per_epoch
        sim = simulate(method, adapter, example, n_tr, n_va, BATCH,
                       "identity", "lan", keep_events=False).bytes_on_wire
        rel = abs(sim - analytic) / max(analytic, 1.0)
        rows.append({"method": method, "analytic": analytic, "sim": sim,
                     "rel_err": rel})
        if rel > 0.01:
            raise AssertionError(
                f"{method}: simulated bytes {sim:.0f} vs analytic "
                f"{analytic:.0f} differ by {rel:.2%} (> 1%)")
    return rows


def check_accounting_bridge(adapter, example, n_tr, n_va) -> list:
    """Acceptance gate: a Transport fed the compiled engine's analytic
    per-epoch accounting must replay (``timeline_from_accounting``) to the
    same wall-clock and per-tag bytes as the from-scratch ``simulate``."""
    rows = []
    tr_counts, _ = client_batch_counts(n_tr, n_va, BATCH)
    for method in ("sl_ac", "sl_am", "sflv2_ac", "sflv3_ac"):
        kind, _, schedule = method.partition("_")
        tp = Transport("identity")
        # exactly what the compiled engine's _account_compiled emits
        tp.record_epoch(adapter, example, kind, schedule, tr_counts)
        for nb in tr_counts:
            tp.account(adapter, example, count=nb)
        sim = simulate(method, adapter, example, n_tr, n_va, BATCH,
                       "identity", "lan", seed=0, keep_events=False)
        acc = timeline_from_accounting(tp, n_val=n_va, batch_size=BATCH,
                                       network="lan", seed=0,
                                       keep_events=False)
        if (acc.wall_clock_s != sim.wall_clock_s
                or acc.breakdown != sim.breakdown):
            raise AssertionError(
                f"{method}: accounting-fed timeline diverges from "
                f"simulate ({acc.wall_clock_s} vs {sim.wall_clock_s} s)")
        rows.append({"method": method, "wall_clock_s": acc.wall_clock_s,
                     "bytes_on_wire": acc.bytes_on_wire})
    return rows


def sweep(adapter, example, n_tr, n_va, methods, codecs, scenarios,
          seed=0) -> list:
    rows = []
    for scenario in scenarios:
        net = make_network(scenario)
        for codec_name in codecs:
            codec = make_codec(codec_name)
            for method in methods:
                if method == "fl" and codec_name != "identity":
                    continue           # FL has no cut layer to compress
                r = simulate(method, adapter, example, n_tr, n_va, BATCH,
                             codec, net, seed=seed, keep_events=False)
                sens = straggler_sensitivity(
                    method, adapter, example, n_tr, n_va, BATCH, codec,
                    net, seed=seed) if net.straggler_frac > 0 else 1.0
                idle = np.mean([pc["idle_frac"]
                                for pc in r.per_client.values()])
                rows.append({
                    "scenario": scenario, "codec": codec.name,
                    "method": method,
                    "bytes_on_wire": r.bytes_on_wire,
                    "bytes_raw": r.bytes_raw,
                    "compression_ratio": r.compression_ratio,
                    "wall_clock_s": r.wall_clock_s,
                    "straggler_sensitivity": sens,
                    "mean_client_idle_frac": float(idle),
                })
                print(f"  {scenario:12s} {codec.name:9s} {method:9s} "
                      f"{r.bytes_on_wire / 1e6:9.2f} MB "
                      f"{r.wall_clock_s:9.2f} s  sens={sens:5.2f}")
    return rows


def markdown_report(check_rows, rows) -> str:
    out = ["# Wire sweep — codecs x network scenarios x methods", ""]
    out.append("## Identity codec vs analytic comm profile (Table 4 gate)")
    out.append("")
    out.append("| method | analytic MB | simulated MB | rel err |")
    out.append("|---|---|---|---|")
    for r in check_rows:
        out.append(f"| {r['method']} | {r['analytic'] / 1e6:.3f} | "
                   f"{r['sim'] / 1e6:.3f} | {r['rel_err']:.2e} |")
    out.append("")
    out.append("## Sweep (one epoch, 5 hospitals)")
    out.append("")
    out.append("| scenario | codec | method | wire MB | ratio | wall s | "
               "straggler x | idle |")
    out.append("|---|---|---|---|---|---|---|---|")
    for r in rows:
        out.append(
            f"| {r['scenario']} | {r['codec']} | {r['method']} | "
            f"{r['bytes_on_wire'] / 1e6:.2f} | "
            f"{r['compression_ratio']:.2f} | {r['wall_clock_s']:.2f} | "
            f"{r['straggler_sensitivity']:.2f} | "
            f"{r['mean_client_idle_frac']:.2f} |")
    out.append("")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--methods", default=",".join(DEFAULT_METHODS))
    ap.add_argument("--codecs", default=",".join(DEFAULT_CODECS))
    ap.add_argument("--scenarios", default=",".join(DEFAULT_SCENARIOS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="benchmarks/results")
    args = ap.parse_args(argv)

    adapter, example, n_tr, n_va = build_setup(args.quick)
    print("cross-checking identity codec vs repro.core.comm ...")
    check_rows = check_identity_matches_analytic(adapter, example, n_tr,
                                                 n_va)
    for r in check_rows:
        print(f"  {r['method']:9s} rel_err={r['rel_err']:.2e}  OK")

    print("cross-checking analytic-accounting timelines vs simulate ...")
    bridge_rows = check_accounting_bridge(adapter, example, n_tr, n_va)
    for r in bridge_rows:
        print(f"  {r['method']:9s} wall={r['wall_clock_s']:.2f}s  OK")

    print("sweeping ...")
    rows = sweep(adapter, example, n_tr, n_va, args.methods.split(","),
                 args.codecs.split(","), args.scenarios.split(","),
                 args.seed)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "wire_sweep.json"), "w") as f:
        json.dump({"check": check_rows, "bridge_check": bridge_rows,
                   "sweep": rows}, f, indent=1)
    md = markdown_report(check_rows, rows)
    with open(os.path.join(args.out, "wire_sweep.md"), "w") as f:
        f.write(md)
    print(f"\nwrote {args.out}/wire_sweep.json and wire_sweep.md")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
