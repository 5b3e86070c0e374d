"""Strategy registry: method grid of the paper's Table 2."""

from repro.core.strategies.base import Strategy
from repro.core.strategies.centralized import Centralized
from repro.core.strategies.federated import FedAvg
from repro.core.strategies.split import SplitLearning
from repro.core.strategies.splitfed import SplitFedV1, SplitFedV2, SplitFedV3


def make_strategy(method: str, adapter, opt_factory, n_clients,
                  transport=None, privacy=None, engine="compiled",
                  drop_remainder=True, shard=False, observe=None,
                  precision="fp32", participation=None, aggregator=None):
    """method: centralized | fl | sl_{ac,am} | sflv{1,2,3}_{ac,am}.

    ``transport`` (repro.wire.Transport) compresses the cut-layer link of
    the SL/SFL family; centralized/FL have no cut layer to compress.
    ``privacy`` (repro.privacy.PrivacyConfig) turns on DP-SGD for any
    method, cut-layer noise for the SL/SFL family, and pairwise-mask
    secure aggregation for FL.

    ``engine`` selects the execution path: ``"compiled"`` (the default;
    repro.core.strategies.engine: a whole multi-epoch ``Strategy.run`` as
    ONE XLA program, scan-over-rounds / scan-over-batches /
    vmap-over-hospitals; ``run_epoch`` is a one-round run, and only FL's
    host-side aggregations keep a compiled program per round) or
    ``"stepwise"`` (legacy, one jitted dispatch per mini-batch — kept as
    the parity oracle).
    Both are numerically equivalent to 1e-5 (tests/test_engine.py);
    transport byte accounting and simulated wire timelines are identical
    under either engine (``wire.simulator.timeline_from_accounting``).
    ``drop_remainder=False`` keeps the final short batch of each hospital
    (pad-and-mask on the compiled path).  ``shard=True`` places the
    hospital axis of every compiled program across the local devices on a
    ``("hosp",)`` mesh (``repro.core.placement``): hospital counts that do
    not divide the device count are padded with zero-weight phantom
    hospitals, so any ``n_clients`` runs on any device count with results
    identical to ``shard=False`` (≤1e-5; no-op on one device or under the
    stepwise oracle).

    ``observe`` (repro.obs.Telemetry, or True for the default spec) taps
    per-round x per-hospital metrics — train loss, grad/update norms,
    FedAvg update cosine, cut-layer activation stats, DP clip fraction —
    inside the compiled programs as extra scan outputs: the whole run
    stays ONE dispatch and params are bit-identical to ``observe=None``.
    Results land on ``strategy.last_run_telemetry``.

    ``precision`` (``"fp32"`` | ``"bf16"``) selects the training compute
    dtype via ``core.partition.cast_adapter``: bf16 forward/backward with
    fp32 master params and fp32 optimizer/aggregation accumulation.
    Evaluation always runs full precision.  bf16 is parity-gated against
    fp32 in tests/test_precision.py (AUROC tolerance, not bitwise — see
    DESIGN.md §13).

    ``participation`` (repro.core.participation.Participation) samples K
    of the N enrolled hospitals each round (fixed-size, Poisson, or an
    explicit schedule) — the compiled whole-run program packs each
    round's cohort into a fixed slot axis (compute scales with K, not N)
    and the RDP accountant composes at the amplified rate.  Compiled
    engine only; ``shard`` and secure aggregation are unsupported with
    it, centralized has no cohort to sample, and the split family
    supports fixed-size cohorts without ``observe``.
    ``Participation(n_global=N, k=N)`` is bit-identical to
    ``participation=None``.

    ``aggregator`` (FL only) replaces the data-size-weighted FedAvg mean
    with a registered ``repro.core.aggregate`` rule — a name
    (``"trimmed_mean"``, ``"coordinate_median"``,
    ``"staleness_discounted"``, ``"hierarchical"``...) or an
    ``Aggregator`` instance; ``None`` keeps the (bit-identical) default.
    """
    from repro.core.partition import cast_adapter
    adapter = cast_adapter(adapter, precision)
    if participation is not None and method == "centralized":
        raise ValueError("centralized pools all hospitals; there is no "
                         "per-round cohort to sample")
    if aggregator is not None and method != "fl":
        raise ValueError("aggregator= selects the FedAvg aggregation rule "
                         f"and applies to fl only, not {method}")
    kw = dict(privacy=privacy, engine=engine,
              drop_remainder=drop_remainder, shard=shard, observe=observe,
              participation=participation)
    if method in ("centralized", "fl"):
        if transport is not None:
            raise ValueError(f"{method} has no cut-layer link for a "
                             "transport codec")
        if privacy is not None and privacy.cut_noise_std > 0:
            raise ValueError(f"{method} has no cut layer to noise")
        if privacy is not None and privacy.secagg and method != "fl":
            raise ValueError("secure aggregation needs federated uploads")
        if method == "centralized":
            kw.pop("participation")
            return Centralized(adapter, opt_factory, n_clients, **kw)
        return FedAvg(adapter, opt_factory, n_clients,
                      aggregator=aggregator, **kw)
    if privacy is not None and privacy.secagg:
        raise ValueError("secure aggregation applies to FL model uploads; "
                         f"{method} ships activations, not updates")
    kind, schedule = method.rsplit("_", 1)
    cls = {"sl": SplitLearning, "sflv1": SplitFedV1,
           "sflv2": SplitFedV2, "sflv3": SplitFedV3}[kind]
    return cls(adapter, opt_factory, n_clients, schedule,
               transport=transport, **kw)


METHODS = ["centralized", "fl", "sl_ac", "sl_am",
           "sflv2_ac", "sflv3_ac", "sflv1_ac"]

__all__ = ["Strategy", "Centralized", "FedAvg", "SplitLearning",
           "SplitFedV1", "SplitFedV2", "SplitFedV3", "make_strategy",
           "METHODS"]
