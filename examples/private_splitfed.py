"""SplitFedv3 under differential privacy (repro.privacy).

Trains the paper's proposed SFLv3 on the synthetic 5-hospital CXR task
three ways —

  * non-private (the paper's regime),
  * DP-SGD: per-example clip + Gaussian noise via the fused Pallas kernel,
    with the RDP accountant reporting per-hospital (eps, delta),
  * cut-layer noise: Gaussian noise on the smashed activations only
    (Li et al.'s mitigation; no gradient accounting, but directly attacks
    the No-Peek server-inference channel)

— and reports AUROC next to what an honest-but-curious server can still
extract from the cut layer: distance correlation with the raw inputs and a
linear reconstruction probe's held-out R^2, measured on exactly what
crosses the wire.

  PYTHONPATH=src python examples/private_splitfed.py [--epochs N]
"""

import argparse
import math

import jax
import numpy as np

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.privacy import PrivacyConfig, measure_leakage


def train(adapter, clients, epochs, privacy, batch_size=16, seed=0):
    strat = make_strategy("sflv3_ac", adapter, lambda: O.adam(3e-4),
                          len(clients), privacy=privacy)
    state = strat.setup(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    log = None
    for _ in range(epochs):
        state, log = strat.run_epoch(state, [c.train for c in clients],
                                     rng, batch_size)
    metrics = strat.evaluate(state, clients, "test", 32)
    return strat, state, metrics, log


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--cut-noise", type=float, default=0.5)
    args = ap.parse_args(argv)

    clients = make_cxr_clients(seed=0, train_per_client=[96, 192, 48, 96,
                                                         48],
                               val_per_client=32, test_per_client=48,
                               image_size=32)
    cfg = DenseNetConfig(growth=8, blocks=(2, 4), stem_ch=16, cut_layer=2)
    adapter = cnn_adapter(build_densenet(cfg))

    regimes = [
        ("non-private", None),
        (f"dp-sgd s={args.sigma:g} C={args.clip:g}",
         PrivacyConfig(noise_multiplier=args.sigma, clip_norm=args.clip)),
        (f"cut-noise std={args.cut_noise:g}",
         PrivacyConfig(cut_noise_std=args.cut_noise)),
    ]

    print(f"sflv3_ac on 5 synthetic hospitals, {args.epochs} epochs\n")
    dp_strat = None
    for label, privacy in regimes:
        strat, state, m, log = train(adapter, clients, args.epochs, privacy)
        if privacy is not None and privacy.dp_enabled:
            dp_strat = strat
        params = strat.params_for_eval(state, 0)
        probe_batch = {k: v[:64] for k, v in clients[0].test.items()}
        leak = measure_leakage(adapter, params, probe_batch,
                               privacy=privacy)
        report = strat.privacy_report()
        if report:
            eps = max(r["epsilon"] for r in report)
            eps_s = ("inf" if math.isinf(eps)
                     else f"{eps:.2f} (delta={report[0]['delta']:g})")
        else:
            eps_s = "-"
        print(f"  {label:24s} loss={log.mean_loss:.4f} "
              f"auroc={m['auroc']:.3f} sens={m['sensitivity']:.2f} "
              f"spec={m['specificity']:.2f}")
        print(f"  {'':24s} eps={eps_s}  "
              f"cut-layer dCor={leak['dcor_input']:.3f} "
              f"probe R2={leak['probe']['r2']:.3f}\n")

    if dp_strat is not None:
        print("per-hospital accountants (unequal data => unequal eps):")
        for i, r in enumerate(dp_strat.privacy_report()):
            print(f"  DT{i + 1}: eps={r['epsilon']:.2f} "
                  f"steps={r['steps']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
