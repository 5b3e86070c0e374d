"""Faithful reproduction of the paper's comparison (Tables 2/3/4/5/6) on the
synthetic 5-hospital non-IID CXR task (see DESIGN.md §1 data gate).

Runs the full 10-method grid of Table 2 for both model families
(DenseNet-mini, U-Net-mini), with best-validation-loss model selection as in
§3.2, and records per-epoch wall time (Table 3), analytic communication
(Table 4) and XLA-counted FLOPs (Tables 5/6).

  PYTHONPATH=src python -m benchmarks.repro_tables [--quick] [--arch X]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import optim as O
from repro.configs.paper_models import DENSENET_MINI, UNET_MINI
from repro.core.comm import comm_per_epoch
from repro.core.flops import flops_per_epoch, segment_fwd_flops

_SEG_FWD_CACHE: dict = {}
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import build_densenet, build_unet

# the paper's Table-2 method grid (label, strategy key, nls?)
ROWS = [
    ("Centralized",  "centralized", False),
    ("FL",           "fl",          False),
    ("SL_LS_AC",     "sl_ac",       False),
    ("SL_LS_AM",     "sl_am",       False),
    ("SL_NLS_AC",    "sl_ac",       True),
    ("SL_NLS_AM",    "sl_am",       True),
    ("SFLv2_LS_AC",  "sflv2_ac",    False),
    ("SFLv2_NLS_AC", "sflv2_ac",    True),
    ("SFLv3_LS_AC",  "sflv3_ac",    False),
    ("SFLv3_NLS_AC", "sflv3_ac",    True),
    ("SFLv1_LS_AC",  "sflv1_ac",    False),   # bonus (paper excluded SFLv1)
]


def build_model(arch: str, nls: bool):
    if arch == "densenet-mini":
        return cnn_adapter(build_densenet(DENSENET_MINI, nls=nls))
    if arch == "unet-mini":
        return cnn_adapter(build_unet(UNET_MINI, nls=nls))
    raise KeyError(arch)


_STRAT_CACHE: dict = {}


def run_method(label, method, nls, arch, clients, epochs, batch_size, lr,
               seed=0):
    # reuse the strategy (and its jitted steps) across seeds — compile once
    skey = (label, arch, batch_size)
    if skey not in _STRAT_CACHE:
        adapter = build_model(arch, nls)
        _STRAT_CACHE[skey] = make_strategy(method, adapter,
                                           lambda: O.adam(lr), len(clients))
    strat = _STRAT_CACHE[skey]
    adapter = strat.adapter
    state = strat.setup(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    data = [c.train for c in clients]

    best = {"val_loss": float("inf"), "state": None}
    epoch_times = []
    for ep in range(epochs):
        t0 = time.time()
        state, log = strat.run_epoch(state, data, rng, batch_size)
        epoch_times.append(time.time() - t0)
        vl = strat.val_loss(state, clients, batch_size)
        if vl < best["val_loss"]:
            best = {"val_loss": vl,
                    "state": jax.tree.map(lambda x: x, state)}
        print(f"    ep{ep} loss={log.mean_loss:.3f} val={vl:.3f} "
              f"t={epoch_times[-1]:.1f}s", flush=True)

    metrics = strat.evaluate(best["state"], clients, "test", batch_size)

    n_train = [len(d["label"]) for d in data]
    n_val = [len(c.val["label"]) for c in clients]
    eb = {k: v[:batch_size] for k, v in data[0].items()}
    comm = comm_per_epoch(method if method != "centralized" else
                          "centralized", adapter, eb, n_train, n_val,
                          batch_size)
    key = (arch, nls, batch_size)
    if key not in _SEG_FWD_CACHE:
        _SEG_FWD_CACHE[key] = segment_fwd_flops(adapter, eb)
    fl = flops_per_epoch(method, adapter, eb, n_train, batch_size,
                         seg_fwd=_SEG_FWD_CACHE[key])
    return {
        "label": label, "method": method, "nls": nls, "arch": arch,
        **{k: round(float(v), 4) for k, v in metrics.items()},
        "best_val_loss": round(float(best["val_loss"]), 4),
        # first epoch includes jit compile; steady state = median of rest
        "epoch_time_s": round(float(np.median(epoch_times[1:])
                                    if len(epoch_times) > 1
                                    else epoch_times[0]), 2),
        "comm_gb": round(comm.gb, 6),
        "comm_breakdown": {k: int(v) for k, v in comm.breakdown.items()},
        "server_tflops": round(fl.server_tflops, 6),
        "avg_client_tflops": round(fl.avg_client_tflops, 6),
        "averaging_mflops": round(fl.averaging_mflops, 6),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--arch", default=None,
                    choices=[None, "densenet-mini", "unet-mini"])
    ap.add_argument("--out", default="benchmarks/results")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    size = 32
    # unequal hospital volumes, like the paper's 3772/1150/1816/880/1090
    sizes = [40, 16, 24, 16, 24] if args.quick else [160, 80, 120, 64, 96]
    clients = make_cxr_clients(seed=0, train_per_client=sizes,
                               val_per_client=60, test_per_client=60,
                               image_size=size)
    plans = {
        "densenet-mini": {"epochs": 2 if args.quick else 10,
                          "batch": 8, "lr": 3e-4},
        "unet-mini": {"epochs": 2 if args.quick else 5,
                      "batch": 8, "lr": 3e-4},
    }
    archs = [args.arch] if args.arch else list(plans)
    for arch in archs:
        plan = plans[arch]
        out_path = os.path.join(args.out, f"repro_{arch}.json")
        results = []
        if os.path.exists(out_path):            # resume partial runs
            results = json.load(open(out_path))
        done = {(r["label"], r.get("seed", 0)) for r in results}
        n_seeds = 1 if args.quick else args.seeds
        for label, method, nls in ROWS:
            for seed in range(n_seeds):
                if (label, seed) in done:
                    continue
                print(f"== {arch} {label} seed{seed}", flush=True)
                t0 = time.time()
                rec = run_method(label, method, nls, arch, clients,
                                 plan["epochs"], plan["batch"], plan["lr"],
                                 seed=seed)
                rec["seed"] = seed
                rec["wall_s"] = round(time.time() - t0, 1)
                results.append(rec)
                print(f"   -> auroc={rec['auroc']} auprc={rec['auprc']} "
                      f"f1={rec['f1']} kappa={rec['kappa']} "
                      f"comm={rec['comm_gb']}GB", flush=True)
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
