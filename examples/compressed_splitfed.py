"""SplitFedv3 with a compressed cut-layer link (repro.wire Transport).

Trains the paper's proposed SFLv3 on the synthetic 5-hospital CXR task
twice — once over an uncompressed link and once with the int8 Pallas codec
roundtripping every cut-layer tensor in-graph — and reports AUROC next to
the achieved on-wire compression ratio, plus the simulated epoch wall-clock
over the hospital WAN for each codec.  This is the operational form of the
paper's Table-4 finding: the SL family's bytes are activations, so codecs
on the cut layer buy back wall-clock that FL can only get from smaller
models.

  PYTHONPATH=src python examples/compressed_splitfed.py [--epochs N]
"""

import argparse

import jax
import numpy as np

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.wire import Transport, boundary_error, simulate


def train(method, adapter, clients, epochs, codec=None, seed=0):
    transport = Transport(codec) if codec else None
    strat = make_strategy(method, adapter, lambda: O.adam(3e-4),
                          len(clients), transport=transport)
    state = strat.setup(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    log = None
    for _ in range(epochs):
        state, log = strat.run_epoch(state, [c.train for c in clients],
                                     rng, 16)
    metrics = strat.evaluate(state, clients, "test", 32)
    return state, strat, metrics, log, transport


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--method", default="sflv3_ac")
    args = ap.parse_args(argv)

    clients = make_cxr_clients(seed=0, train_per_client=96,
                               val_per_client=32, test_per_client=48,
                               image_size=32)
    cfg = DenseNetConfig(growth=8, blocks=(2, 4), stem_ch=16, cut_layer=2)
    adapter = cnn_adapter(build_densenet(cfg))

    print(f"{args.method} on 5 synthetic hospitals, {args.epochs} epochs\n")
    rows = []
    for codec in (None, "int8"):
        label = codec or "identity"
        state, strat, m, log, tp = train(args.method, adapter, clients,
                                         args.epochs, codec)
        ratio = tp.compression_ratio if tp else 1.0
        wire_mb = tp.bytes_on_wire / 1e6 if tp else float("nan")
        rows.append((label, m["auroc"], m["auprc"], ratio))
        print(f"  codec={label:8s} loss={log.mean_loss:.4f} "
              f"test_auroc={m['auroc']:.3f} test_auprc={m['auprc']:.3f} "
              f"compression={ratio:.2f}x"
              + (f" wire={wire_mb:.1f} MB" if tp else ""))
        if tp:
            params = strat.params_for_eval(state, 0)
            batch = {k: v[:16] for k, v in clients[0].train.items()}
            errs = boundary_error(tp, adapter, params, batch)
            rel = [e["rel_l2"] for v in errs.values() for e in v]
            print(f"           cut-layer rel-L2 reconstruction error: "
                  f"{max(rel):.4f}")

    base, comp = rows[0], rows[1]
    print(f"\n  AUROC delta (int8 - identity): {comp[1] - base[1]:+.4f} "
          f"at {comp[3]:.2f}x fewer bytes on the wire")

    eb = {k: v[:16] for k, v in clients[0].train.items()}
    n_tr = [len(c.train["label"]) for c in clients]
    n_va = [len(c.val["label"]) for c in clients]
    print("\nsimulated epoch wall-clock over hospital_wan:")
    for codec in ("identity", "bf16", "int8", "topk:0.1"):
        r = simulate(args.method, adapter, eb, n_tr, n_va, 16, codec,
                     "hospital_wan", keep_events=False)
        print(f"  {codec:9s} {r.bytes_on_wire / 1e6:8.2f} MB  "
              f"{r.wall_clock_s:6.2f} s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
