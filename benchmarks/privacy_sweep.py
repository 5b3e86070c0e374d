"""Privacy sweep — noise multiplier x strategy -> (eps, AUROC, leakage,
bytes): the (eps, delta)-vs-utility frontier the paper's comparison was
missing.

For every method of the paper's grid (FL / SL / SFLv2 / SFLv3) and every
DP-SGD noise multiplier, train on the synthetic 5-hospital CXR task and
report:

  * accountant epsilon at delta=1e-5 — PER HOSPITAL (unequal data volumes
    give unequal guarantees), plus the worst case;
  * pooled test AUROC;
  * cut-layer leakage: distance correlation of the smashed activations
    against raw inputs, measured on exactly what crosses the wire (through
    the identity Transport; FL has no cut layer so its column reports the
    HYPOTHETICAL leakage of the shared model's front segment — what an SL
    deployment of the same weights would expose);
  * on-wire bytes: metered cut-layer traffic for the split family, model
    up/down (+ secure-aggregation handshake) for FL.

Writes ``benchmarks/results/privacy_sweep.json`` + ``.md``.

  PYTHONPATH=src python -m benchmarks.privacy_sweep [--quick]
      [--methods fl,sl_ac,...] [--noise 0,0.5,1,2] [--epochs N]
"""

from __future__ import annotations

import argparse
import json
import math
import os

import jax
import numpy as np

from repro import optim as O
from repro.core.comm import comm_per_epoch
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.privacy import PrivacyConfig, measure_leakage
from repro.wire import Transport

DEFAULT_METHODS = ["fl", "sl_ac", "sflv2_ac", "sflv3_ac"]
DEFAULT_NOISE = [0.0, 0.5, 1.0, 2.0]
CLIP_NORM = 1.0
DELTA = 1e-5


def build_setup(quick: bool):
    n_tr = [24, 48, 24, 48, 24] if quick else [96, 192, 48, 96, 48]
    clients = make_cxr_clients(seed=0, train_per_client=n_tr,
                               val_per_client=16 if quick else 32,
                               test_per_client=24 if quick else 48,
                               image_size=16 if quick else 32)
    cfg = (DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)
           if quick else
           DenseNetConfig(growth=8, blocks=(2, 2), stem_ch=8, cut_layer=1))
    adapter = cnn_adapter(build_densenet(cfg))
    return adapter, clients


def fl_bytes(adapter, clients, batch_size, epochs, strat) -> float:
    """FL on-wire bytes: model down/up per round (+ secagg handshake)."""
    n_tr = [len(c.train["label"]) for c in clients]
    n_va = [len(c.val["label"]) for c in clients]
    example = {k: v[:batch_size] for k, v in clients[0].train.items()}
    per_round = comm_per_epoch("fl", adapter, example, n_tr, n_va,
                               batch_size).bytes_per_epoch
    extra = (strat.secagg.handshake_bytes() * strat.secagg.rounds
             if hasattr(strat, "secagg") else 0)
    return per_round * epochs + extra


def run_cell(method, sigma, adapter, clients, epochs, batch_size,
             secagg_fl=True, seed=0) -> dict:
    privacy = PrivacyConfig(noise_multiplier=sigma, clip_norm=CLIP_NORM,
                            delta=DELTA, seed=seed,
                            secagg=secagg_fl and method == "fl")
    transport = Transport("identity") if method != "fl" else None
    strat = make_strategy(method, adapter, lambda: O.adam(1e-3),
                          len(clients), transport=transport,
                          privacy=privacy)
    state = strat.setup(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    log = None
    for _ in range(epochs):
        state, log = strat.run_epoch(state, [c.train for c in clients],
                                     rng, batch_size)
    metrics = strat.evaluate(state, clients, "test", batch_size=32)

    report = strat.privacy_report()
    eps = [r["epsilon"] for r in report]

    # leakage of what actually ships, hospital 0's front segment
    params = strat.params_for_eval(state, 0)
    probe_batch = {k: v[:64] for k, v in clients[0].test.items()}
    leak = measure_leakage(adapter, params, probe_batch,
                           transport=transport, seed=seed)

    if method == "fl":
        wire = fl_bytes(adapter, clients, batch_size, epochs, strat)
    else:
        wire = transport.bytes_on_wire

    return {
        "method": method, "noise_multiplier": sigma,
        "clip_norm": CLIP_NORM, "delta": DELTA,
        "epsilon_per_hospital": eps,
        "epsilon_max": max(eps) if eps else math.inf,
        "auroc": metrics["auroc"], "auprc": metrics["auprc"],
        "sensitivity": metrics["sensitivity"],
        "specificity": metrics["specificity"], "ece": metrics["ece"],
        "dcor_input": leak["dcor_input"],
        "probe_r2": leak["probe"]["r2"],
        "label_probe_auc": leak.get("label_probe_auc", float("nan")),
        "bytes_on_wire": float(wire),
        "mean_train_loss": log.mean_loss,
    }


def _fmt_eps(e: float) -> str:
    return "inf" if math.isinf(e) else f"{e:.2f}"


def markdown_report(rows) -> str:
    out = ["# Privacy sweep — DP-SGD noise multiplier x strategy", "",
           f"Per-example clip C={CLIP_NORM}, delta={DELTA}; epsilon is the "
           "WORST hospital (per-hospital list in the JSON).  FL leakage "
           "columns are the hypothetical exposure of the shared front "
           "segment.", "",
           "| method | sigma | eps (max) | AUROC | dCor(input) | probe R2 "
           "| label AUC | wire MB |", "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['method']} | {r['noise_multiplier']:g} | "
            f"{_fmt_eps(r['epsilon_max'])} | {r['auroc']:.3f} | "
            f"{r['dcor_input']:.3f} | {r['probe_r2']:.3f} | "
            f"{r['label_probe_auc']:.3f} | "
            f"{r['bytes_on_wire'] / 1e6:.2f} |")
    out.append("")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--methods", default=",".join(DEFAULT_METHODS))
    ap.add_argument("--noise", default=",".join(str(s) for s in
                                                DEFAULT_NOISE))
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="benchmarks/results")
    args = ap.parse_args(argv)

    adapter, clients = build_setup(args.quick)
    epochs = args.epochs if args.epochs is not None else (1 if args.quick
                                                          else 2)
    rows = []
    for method in args.methods.split(","):
        for sigma in [float(s) for s in args.noise.split(",")]:
            r = run_cell(method, sigma, adapter, clients, epochs,
                         args.batch_size, seed=args.seed)
            rows.append(r)
            print(f"  {method:9s} sigma={sigma:4g} "
                  f"eps={_fmt_eps(r['epsilon_max']):>7s} "
                  f"auroc={r['auroc']:.3f} dcor={r['dcor_input']:.3f} "
                  f"wire={r['bytes_on_wire'] / 1e6:8.2f} MB")

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "privacy_sweep.json"), "w") as f:
        json.dump({"clip_norm": CLIP_NORM, "delta": DELTA,
                   "epochs": epochs, "sweep": rows}, f, indent=1,
                  default=float)
    with open(os.path.join(args.out, "privacy_sweep.md"), "w") as f:
        f.write(markdown_report(rows))
    print(f"\nwrote {args.out}/privacy_sweep.json and privacy_sweep.md")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
