"""Federated learning on the synthetic CXR task with the int8 cut-layer link
compressor ablation (beyond-paper; repro.kernels.act_compress).

Shows the paper's headline trade-off directly: FL moves model-sized bytes
per round; SL-family methods move activation-sized bytes per batch; the int8
compressor cuts the SL link bytes ~4x at negligible metric cost.

  PYTHONPATH=src python examples/federated_cxr.py
"""

import jax
import numpy as np

from repro import optim as O
from repro.core.comm import comm_per_epoch
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet


def main():
    clients = make_cxr_clients(seed=0, train_per_client=64,
                               val_per_client=32, test_per_client=32,
                               image_size=32)
    cfg = DenseNetConfig(growth=8, blocks=(2, 4), stem_ch=16, cut_layer=2)
    adapter = cnn_adapter(build_densenet(cfg))
    eb = {k: v[:16] for k, v in clients[0].train.items()}
    n_tr = [len(c.train["label"]) for c in clients]
    n_va = [len(c.val["label"]) for c in clients]

    print("per-epoch communication (analytic, paper Table 4 analogue):")
    profiles = {}
    for method in ["fl", "sl_ac", "sflv3_ac"]:
        c = profiles[method] = comm_per_epoch(method, adapter, eb, n_tr,
                                              n_va, 16)
        print(f"  {method:10s} {c.gb * 1e3:8.2f} MB   {c.breakdown}")
    from repro.wire import make_codec
    raw = profiles["sl_ac"]
    c8 = comm_per_epoch("sl_ac", adapter, eb, n_tr, n_va, 16,
                        codec=make_codec("int8"))
    print(f"  sl_ac+int8 {c8.gb * 1e3:8.2f} MB   "
          f"(cut-layer tensors int8+row-scale via repro.wire, "
          f"{raw.bytes_per_epoch / c8.bytes_per_epoch:.2f}x)")

    print("\ntraining FL for 4 rounds:")
    strat = make_strategy("fl", adapter, lambda: O.adam(3e-4), len(clients))
    state = strat.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    for r in range(4):
        state, log = strat.run_epoch(state, [c.train for c in clients],
                                     rng, 16)
        m = strat.evaluate(state, clients, "val", 32)
        print(f"  round {r}: loss={log.mean_loss:.4f} "
              f"val_auroc={m['auroc']:.3f}")
    print("test:", strat.evaluate(state, clients, "test", 32))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
