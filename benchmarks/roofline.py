"""Roofline analysis (deliverable g) — two sources, one three-term model:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / ICI link bw

``--mode dryrun`` (the original table) reads the transformer dry-run JSON
records and emits the per-(arch × shape) table for EXPERIMENTS.md
§Roofline.  MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); the
usefulness ratio MODEL_FLOPS/HLO_FLOPs flags remat/redundancy waste
(values > ~0.5 are good for a remat-everything policy; tiny values
indicate structural waste).

``--mode strategies`` (the default) builds the five healthcare strategy
WHOLE-RUN programs (fl, sl_am, sflv2_ac, sflv3_ac, sflv1_ac — compiled
engine, int8 cut-layer transport on the split family), runs each once so
``obs.profile.hlo_cost`` can re-lower the exact donated program, and
writes the per-strategy compute/memory/collective cost table to
``benchmarks/results/BENCH_roofline.json``.

Hardware peaks come from ``launch.dryrun.HW_TABLE``, keyed by device kind;
``--hw`` selects the row (default: the first device's kind, which must be
in the table — a CPU run names the nominal row with ``--hw cpu_host``).
"""

from __future__ import annotations

import glob
import json
import os

from repro.launch.dryrun import HW_TABLE, default_hw, roofline_terms


def param_counts(cfg):
    """(total_params, active_params) — analytic, no allocation."""
    import jax
    from repro.models.transformer import TransformerLM, layer_kinds
    model = TransformerLM.build(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    import numpy as np
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    active = total
    if cfg.n_experts:
        # per MoE layer only top_k (+shared) experts are active
        per_expert = 3 * cfg.d_model * cfg.d_ff
        n_moe = sum(1 for k in layer_kinds(cfg) if k == "moe")
        inactive = n_moe * (cfg.n_experts - cfg.top_k) * per_expert
        active = total - inactive
    return total, active


def model_flops(arch_id: str, shape_name: str) -> float:
    """6·N_active·D for a train step (fwd+bwd); 2·N_active·D per decode/
    prefill token."""
    from repro.configs.base import INPUT_SHAPES
    from repro.configs.registry import REGISTRY
    cfg = REGISTRY[arch_id].config
    sh = INPUT_SHAPES[shape_name]
    _, active = param_counts(cfg)
    if sh["kind"] == "train":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 6.0 * active * tokens
    if sh["kind"] == "prefill":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 2.0 * active * tokens
    return 2.0 * active * sh["global_batch"]          # decode: 1 token/seq


def load_records(results_dir="benchmarks/results", mesh="single"):
    recs = {}
    for f in glob.glob(os.path.join(results_dir, f"dryrun_*_{mesh}.json")):
        r = json.load(open(f))
        recs[(r["arch"], r["shape"])] = r
    return recs


def roofline_table(results_dir="benchmarks/results", mesh="single",
                   chips=256):
    from repro.configs.base import INPUT_SHAPES
    from repro.configs.registry import REGISTRY
    rows = []
    recs = load_records(results_dir, mesh)
    for aid in REGISTRY:
        for shape in INPUT_SHAPES:
            r = recs.get((aid, shape))
            if r is None or r["status"] != "ok":
                rows.append({"arch": aid, "shape": shape,
                             "status": (r or {}).get("status", "missing"),
                             "notes": (r or {}).get("notes", "")})
                continue
            rf = r["roofline"]
            mf = model_flops(aid, shape)
            hlo_total = r["hlo_flops"] * chips   # cost_analysis is per device
            rows.append({
                "arch": aid, "shape": shape, "status": "ok",
                "t_compute_s": rf["t_compute"],
                "t_memory_s": rf["t_memory"],
                "t_collective_s": rf["t_collective"],
                "dominant": rf["dominant"],
                "model_flops": mf,
                "useful_ratio": mf / hlo_total if hlo_total else float("nan"),
                "collective_gb": rf["collective_bytes"] / 1e9,
            })
    return rows


# ---------------------------------------------------------------------------
# per-strategy roofline — the five healthcare run programs
# ---------------------------------------------------------------------------

STRATEGY_METHODS = ["fl", "sl_am", "sflv2_ac", "sflv3_ac", "sflv1_ac"]
STRAT_OUT = os.path.join(os.path.dirname(__file__), "results",
                         "BENCH_roofline.json")


def _strategy_cost(method: str, n_clients: int, train_per_client: int,
                   batch_size: int, run_epochs: int, precision: str,
                   fused: bool):
    """Train one compiled whole-run program and return its HLO cost."""
    import jax
    import numpy as np
    from repro import optim as O
    from repro.core.partition import cnn_adapter
    from repro.core.strategies import make_strategy
    from repro.data.synthetic import make_cxr_clients
    from repro.models.cnn import DenseNetConfig, build_densenet
    from repro.obs.profile import hlo_cost
    from repro.wire import Transport

    clients = make_cxr_clients(seed=0, n_clients=n_clients,
                               train_per_client=train_per_client,
                               val_per_client=8, test_per_client=8,
                               image_size=8)
    cfg = DenseNetConfig(growth=2, blocks=(1, 1), stem_ch=4, cut_layer=1)
    adapter = cnn_adapter(build_densenet(cfg))
    transport = (Transport("int8", fuse=fused)
                 if method not in ("fl", "centralized") else None)
    strat = make_strategy(method, adapter, lambda: O.adam(1e-3), n_clients,
                          transport=transport, precision=precision)
    state = strat.setup(jax.random.key(0))
    state, logs = strat.run(state, [c.train for c in clients],
                            np.random.default_rng(0), batch_size,
                            run_epochs)
    steps = sum(l.steps for l in logs)
    return steps, hlo_cost(strat)


def strategy_roofline(methods=None, n_clients=3, train_per_client=16,
                      batch_size=4, run_epochs=2, hw=None,
                      precision="fp32", fused=True) -> dict:
    """Per-strategy compute/memory/collective cost table.

    Each row is the strategy's WHOLE-RUN compiled program (``run_epochs``
    epochs, every round's trip count folded in by
    ``launch.hlo_analysis``), divided by the ``hw`` peaks into the three
    roofline terms.  Memory figures are the compiler's buffer-assignment
    view (``memory_analysis``) — ``alias_size_in_bytes`` is what the
    donated carries save off peak.
    """
    hw_name = hw or default_hw()
    peaks = HW_TABLE[hw_name]
    rows = []
    for method in (methods or STRATEGY_METHODS):
        steps, cost = _strategy_cost(method, n_clients, train_per_client,
                                     batch_size, run_epochs, precision,
                                     fused)
        if cost is None:                       # degenerate run: no program
            rows.append({"strategy": method, "status": "no_compiled_run"})
            continue
        rf = roofline_terms({"hlo_flops": cost["flops"],
                             "hlo_bytes": cost["hbm_bytes"],
                             "collectives": cost["collective_bytes"]},
                            mesh_chips=1, hw=peaks)
        row = {"strategy": method, "status": "ok", "steps": steps,
               "flops": cost["flops"], "hbm_bytes": cost["hbm_bytes"],
               "collective_bytes": cost["collective_total"],
               "t_compute_s": rf["t_compute"], "t_memory_s": rf["t_memory"],
               "t_collective_s": rf["t_collective"],
               "dominant": rf["dominant"],
               "compile_seconds": cost["compile_seconds"],
               "memory": cost["memory"]}
        rows.append(row)
    return {"hw": hw_name, "peaks": peaks, "n_clients": n_clients,
            "train_per_client": train_per_client, "batch_size": batch_size,
            "run_epochs": run_epochs, "precision": precision,
            "fused": fused, "rows": rows}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="strategies",
                    choices=["strategies", "dryrun"])
    ap.add_argument("--hw", default=None, choices=list(HW_TABLE),
                    help="hardware peaks row (default: the device kind; "
                         "CPU runs pass cpu_host)")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--train-per-client", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--run-epochs", type=int, default=2)
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "bf16"])
    ap.add_argument("--unfused", action="store_true",
                    help="force the unfused cut-layer reference path")
    ap.add_argument("--out", default=STRAT_OUT)
    args = ap.parse_args()

    if args.mode == "dryrun":
        rows = roofline_table()
        print("arch,shape,dominant,t_compute_s,t_memory_s,t_collective_s,"
              "useful_ratio,collective_gb_per_dev")
        for r in rows:
            if r.get("status") != "ok":
                print(f"{r['arch']},{r['shape']},{r['status']},,,,,")
                continue
            print(f"{r['arch']},{r['shape']},{r['dominant']},"
                  f"{r['t_compute_s']:.3e},{r['t_memory_s']:.3e},"
                  f"{r['t_collective_s']:.3e},{r['useful_ratio']:.3f},"
                  f"{r['collective_gb']:.3f}")
        return

    table = strategy_roofline(
        n_clients=args.clients, train_per_client=args.train_per_client,
        batch_size=args.batch, run_epochs=args.run_epochs, hw=args.hw,
        precision=args.precision, fused=not args.unfused)
    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(table, f, indent=1)
    print(f"hw={table['hw']}  "
          "strategy,dominant,t_compute_s,t_memory_s,t_collective_s,"
          "compile_s")
    for r in table["rows"]:
        if r.get("status") != "ok":
            print(f"{r['strategy']},{r['status']},,,,")
            continue
        print(f"{r['strategy']},{r['dominant']},{r['t_compute_s']:.3e},"
              f"{r['t_memory_s']:.3e},{r['t_collective_s']:.3e},"
              f"{r['compile_seconds']:.2f}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
