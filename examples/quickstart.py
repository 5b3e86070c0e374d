"""Quickstart: train a SplitFedv3 model (the paper's method) across five
virtual hospitals on the synthetic chest-X-ray task, then compare with plain
split learning — all on CPU in ~2 minutes.

  PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import numpy as np

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.obs import Telemetry


def main():
    # five hospitals, non-IID scanners (see repro/data/synthetic.py)
    clients = make_cxr_clients(seed=0, train_per_client=64,
                               val_per_client=32, test_per_client=32,
                               image_size=32)
    cfg = DenseNetConfig(growth=8, blocks=(2, 4), stem_ch=16, cut_layer=2)

    for method in ["sflv3_ac", "sl_ac"]:
        adapter = cnn_adapter(build_densenet(cfg))
        # the default compiled engine lowers the WHOLE 4-epoch run into
        # one XLA program via strat.run (engine="stepwise" is the legacy
        # per-batch host loop; both train identically).  observe= taps
        # per-round telemetry (grad/update norms, cut-layer activation
        # stats) inside that one program — params stay bit-identical.
        # For cross-device federations, participation=Participation(
        # n_global=N, k=K) trains a K-hospital cohort per round (still
        # one program; compute scales with K, not N) — see DESIGN.md §14.
        strat = make_strategy(method, adapter, lambda: O.adam(3e-4),
                              n_clients=len(clients),
                              observe=Telemetry())
        state = strat.setup(jax.random.key(0))
        rng = np.random.default_rng(0)
        t0 = time.time()
        state, logs = strat.run(state, [c.train for c in clients], rng,
                                batch_size=16, n_epochs=4)
        for epoch, log in enumerate(logs):
            print(f"[{method}] epoch {epoch}: loss={log.mean_loss:.4f}")
        print(f"[{method}] per-round telemetry "
              "(hospital means; see repro.obs):")
        print(strat.last_run_telemetry.table())
        metrics = strat.evaluate(state, clients, "test", batch_size=32)
        print(f"[{method}] test {metrics}  ({time.time() - t0:.0f}s)\n")

    # serve the result: export hospital 0's deployable model (its own
    # front + the shared server, stitched at the cut) into a batched
    # screening service — pre-lowered bucket ladder, so steady-state
    # requests never compile; scores are bit-identical to strat.scores.
    # See DESIGN.md §15 and examples/train_and_serve.py for hot-swapping
    # each round's export into a live service.
    from repro.serving import ScreeningService
    servable = strat.export(state, client_idx=0)
    with ScreeningService(servable, image_shape=(32, 32, 1),
                          max_wait_s=0.002) as svc:
        score = svc.score_one({"image": clients[0].test["image"][0]})
        print(f"[serve] {servable.family} export v{svc.version}: "
              f"first test image scores {score:.4f} "
              f"(p50 {svc.stats()['total_p50_ms']:.2f} ms)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
