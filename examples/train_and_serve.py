"""Close the train→deploy loop: a live screening service absorbing each
federated round's model via zero-downtime hot-swap.

A ``ScreeningService`` starts serving after round 1 and keeps answering
single-image requests while FL training continues; after every round the
fresh ``Strategy.export`` is swapped in behind the in-flight-safe
``ModelSlot``.  At each round the script scores the pooled test set BOTH
ways — training-side ``Strategy.scores_all`` and request-by-request
through the live service — and asserts the AUROCs match BIT-exactly:
the service serves exactly the model training just produced, never a
stale or torn one.

The coda round-trips a SplitFedv3 export (client front stitched with the
shared server at the cut) through the on-disk checkpoint format and
re-serves it — the multi-hospital strategies deploy through the same
path as FL.

  PYTHONPATH=src python examples/train_and_serve.py
"""

import concurrent.futures as cf
import tempfile

import jax
import numpy as np

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.serving import ScreeningService, load_servable, save_servable
from repro.train.metrics import auroc

ROUNDS = 4


def pooled_test(clients):
    return (np.concatenate([c.test["image"] for c in clients]),
            np.concatenate([c.test["label"] for c in clients]))


def serve_auroc(svc, images, labels):
    """Score the pooled test set one request at a time through the live
    queue (8 concurrent clients), like screening traffic would."""
    with cf.ThreadPoolExecutor(8) as ex:
        scores = list(ex.map(
            lambda im: svc.score_one({"image": im}), images))
    return auroc(labels, np.asarray(scores, np.float32))


def main():
    clients = make_cxr_clients(seed=0, train_per_client=64,
                               val_per_client=16, test_per_client=32,
                               image_size=32)
    cfg = DenseNetConfig(growth=8, blocks=(2, 2), stem_ch=16, cut_layer=2)
    adapter = cnn_adapter(build_densenet(cfg))
    strat = make_strategy("fl", adapter, lambda: O.adam(3e-4),
                          n_clients=len(clients))
    state = strat.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    images, labels = pooled_test(clients)

    svc = None
    try:
        for rnd in range(ROUNDS):
            state, logs = strat.run(state, [c.train for c in clients], rng,
                                    batch_size=16, n_epochs=1)
            servable = strat.export(state, meta={"round": rnd})
            if svc is None:
                svc = ScreeningService(servable,
                                       image_shape=images.shape[1:],
                                       max_wait_s=0.002)
            else:
                svc.swap(servable)           # zero downtime: in-flight
                                             # requests finish on the old
                                             # tree, new ones see round rnd
            # training-side eval (FL: same global model for every client)
            train_scores = np.concatenate(
                strat.scores_all(state, [c.test for c in clients]))
            train_auroc = auroc(labels, train_scores)
            live_auroc = serve_auroc(svc, images, labels)
            assert live_auroc == train_auroc, (
                f"round {rnd}: served AUROC {live_auroc} != training eval "
                f"{train_auroc} — the service is not bit-exact")
            st = svc.stats()
            print(f"round {rnd}: loss={logs[-1].mean_loss:.4f}  "
                  f"AUROC train={train_auroc:.4f} "
                  f"serve={live_auroc:.4f} (bit-exact, v{svc.version})  "
                  f"p50={st['total_p50_ms']:.2f}ms "
                  f"p99={st['total_p99_ms']:.2f}ms")
    finally:
        if svc is not None:
            svc.close()

    # -- the split family deploys through the same path -------------------
    sfl = make_strategy("sflv3_ac", adapter, lambda: O.adam(3e-4),
                        n_clients=len(clients))
    sstate = sfl.setup(jax.random.key(1))
    sstate, _ = sfl.run(sstate, [c.train for c in clients], rng,
                        batch_size=16, n_epochs=1)
    ref = np.asarray(sfl.scores(sstate, 2, clients[2].test))
    with tempfile.NamedTemporaryFile(suffix=".msgpack") as f:
        save_servable(f.name, sfl.export(sstate, client_idx=2,
                                         meta={"round": 0}))
        servable = load_servable(f.name, adapter)
    with ScreeningService(servable, image_shape=images.shape[1:]) as svc2:
        got = np.asarray([svc2.score_one({"image": im})
                          for im in clients[2].test["image"]], np.float32)
    assert np.array_equal(got, ref.ravel())
    print(f"sflv3 export (hospital 2 front + shared server) round-tripped "
          f"through {servable.family!r} checkpoint and re-served "
          f"bit-exactly (AUROC {auroc(clients[2].test['label'], got):.4f})")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
