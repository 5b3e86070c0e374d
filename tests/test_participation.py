"""repro.core.participation: per-round client subsampling of N hospitals.

Acceptance gates (ISSUE 9):
  * ``Participation(n_global=N, k=N)`` is BIT-identical to
    ``participation=None`` for FL; the split family matches at the
    engine-parity tolerance 1e-5 (gather/scatter by slot id shifts XLA
    fusion boundaries by ~1 ulp — DESIGN.md §14);
  * a participating multi-epoch compiled run stays ONE dispatch;
  * a hospital's round is co-sample independent (keys and batches depend
    on (round, hospital), never on who else was drawn) and phantom slots
    never perturb the sampled rows;
  * an empty Poisson round keeps the previous globals (S1 guard);
  * the RDP accountant composes at the amplified rate: eps(K<N) is
    STRICTLY below eps(K=N);
  * wire accounting sees only sampled clients (EpochSchedule.client_set);
  * telemetry un-pads slot metrics to global columns with NaN rows.
"""

import jax
import numpy as np
import pytest

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.participation import Participation, as_participation
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.privacy import PrivacyConfig

DP = PrivacyConfig(noise_multiplier=1.1, clip_norm=1.0)
SPLIT_METHODS = ["sl_ac", "sl_am", "sflv2_ac", "sflv3_ac", "sflv1_ac"]


@pytest.fixture(scope="module")
def tiny_setup():
    clients = make_cxr_clients(seed=0, train_per_client=[17, 12, 9],
                               val_per_client=6, test_per_client=7,
                               image_size=16, n_clients=3)
    cfg = DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)
    return clients, cnn_adapter(build_densenet(cfg))


def _train(method, clients, adapter, participation, privacy=None,
           epochs=2, batch=4, transport=None, observe=None,
           engine="compiled"):
    st = make_strategy(method, adapter, lambda: O.adam(1e-3), len(clients),
                       privacy=privacy, engine=engine, transport=transport,
                       observe=observe, participation=participation)
    state = st.setup(jax.random.key(0))
    state, logs = st.run(state, [c.train for c in clients],
                         np.random.default_rng(0), batch, epochs)
    return st, state, logs


def _leaves(st, state, client=0):
    return [np.asarray(x) for x in
            jax.tree.leaves(st.params_for_eval(state, client))]


# ---------------------------------------------------------------------------
# the frozen spec
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError, match="exactly one"):
        Participation(n_global=5)
    with pytest.raises(ValueError, match="exactly one"):
        Participation(n_global=5, k=2, q=0.5)
    with pytest.raises(ValueError):
        Participation(n_global=5, k=0)
    with pytest.raises(ValueError):
        Participation(n_global=5, k=6)
    with pytest.raises(ValueError):
        Participation(n_global=5, q=0.0)
    with pytest.raises(ValueError):
        Participation(n_global=5, q=1.5)
    with pytest.raises(ValueError, match="ids must be in"):
        Participation(n_global=3, schedule=((0, 5),))
    with pytest.raises(ValueError, match="repeat"):
        Participation(n_global=3, schedule=((1, 1),))
    with pytest.raises(ValueError, match="slots"):
        Participation(n_global=5, k=2, slots=0)
    with pytest.raises(TypeError):
        as_participation("k=2")
    assert as_participation(None) is None


def test_spec_kinds_slots_rate():
    p = Participation(n_global=10, k=3)
    assert (p.kind, p.n_slots, p.rate) == ("fixed", 3, 0.3)
    p = Participation(n_global=10, q=0.25)
    assert (p.kind, p.n_slots, p.rate) == ("poisson", 10, 0.25)
    p = Participation(n_global=10, q=0.25, slots=4)
    assert p.n_slots == 4
    p = Participation(n_global=10, schedule=((1, 0), (2,)))
    assert (p.kind, p.n_slots, p.rate) == ("schedule", 2, 1.0)
    assert p.schedule[0] == (0, 1)            # normalized sorted


def test_round_ids_deterministic_and_bounded():
    p = Participation(n_global=50, k=7, seed=3)
    a, b = p.round_ids(5), p.round_ids(5)
    assert np.array_equal(a, b)
    assert len(a) == 7 and len(set(a.tolist())) == 7
    assert not np.array_equal(p.round_ids(5), p.round_ids(6))
    # poisson truncation: never more than slots
    p = Participation(n_global=50, q=0.9, slots=5, seed=1)
    for r in range(6):
        assert len(p.round_ids(r)) <= 5
    # schedule replay + exhaustion
    p = Participation(n_global=4, schedule=((0, 2), (1,)))
    assert p.round_ids(1).tolist() == [1]
    with pytest.raises(ValueError, match="rounds"):
        p.round_ids(2)


# ---------------------------------------------------------------------------
# K = N parity (acceptance: FL bit-identical; split family at 1e-5)
# ---------------------------------------------------------------------------

def test_fl_k_equals_n_bit_identical(tiny_setup):
    clients, adapter = tiny_setup
    st0, s0, l0 = _train("fl", clients, adapter, None, privacy=DP)
    st1, s1, l1 = _train("fl", clients, adapter,
                         Participation(n_global=3, k=3), privacy=DP)
    for a, b in zip(_leaves(st0, s0), _leaves(st1, s1)):
        assert np.array_equal(a, b)           # BIT-identical
    la = np.sort(np.concatenate([np.asarray(l.losses) for l in l0]))
    lb = np.sort(np.concatenate([np.asarray(l.losses) for l in l1]))
    np.testing.assert_array_equal(la, lb)
    # accountant: same steps, same epsilon (rate is K/N = 1)
    ra, rb = st0.privacy_report(), st1.privacy_report()
    for x, y in zip(ra, rb):
        assert x["steps"] == y["steps"]
        assert abs(x["epsilon"] - y["epsilon"]) < 1e-9


@pytest.mark.parametrize("method", SPLIT_METHODS)
def test_split_k_equals_n_parity(method, tiny_setup):
    clients, adapter = tiny_setup
    st0, s0, _ = _train(method, clients, adapter, None)
    st1, s1, _ = _train(method, clients, adapter,
                        Participation(n_global=3, k=3))
    for c in range(3):
        for a, b in zip(_leaves(st0, s0, c), _leaves(st1, s1, c)):
            np.testing.assert_allclose(a, b, atol=1e-5)


# ---------------------------------------------------------------------------
# one dispatch + privacy amplification
# ---------------------------------------------------------------------------

def test_fl_participation_one_dispatch_and_amplification(tiny_setup):
    clients, adapter = tiny_setup
    st2, _, _ = _train("fl", clients, adapter,
                       Participation(n_global=3, k=2), privacy=DP,
                       epochs=3)
    assert st2._dispatches == 1
    st3, _, _ = _train("fl", clients, adapter,
                       Participation(n_global=3, k=3), privacy=DP,
                       epochs=3)
    eps2 = max(r["epsilon"] for r in st2.privacy_report())
    eps3 = max(r["epsilon"] for r in st3.privacy_report())
    assert eps2 < eps3                        # STRICT amplification


@pytest.mark.parametrize("method", ["sl_ac", "sflv3_ac"])
def test_split_participation_one_dispatch(method, tiny_setup):
    clients, adapter = tiny_setup
    st, state, _ = _train(method, clients, adapter,
                          Participation(n_global=3, k=2), privacy=DP,
                          epochs=2)
    assert st._dispatches == 1
    for a in _leaves(st, state, 0):
        assert np.all(np.isfinite(a))


# ---------------------------------------------------------------------------
# co-sample independence + phantom invariance
# ---------------------------------------------------------------------------

def test_cosample_independence(tiny_setup):
    """Hospital 0's round-0 training is identical whether its cohort
    partner is hospital 1 or hospital 2 (batches and noise keys are
    keyed by GLOBAL row, not slot context)."""
    clients, adapter = tiny_setup
    _, _, la = _train("fl", clients, adapter,
                      Participation(n_global=3, schedule=((0, 1),)),
                      privacy=DP, epochs=1)
    _, _, lb = _train("fl", clients, adapter,
                      Participation(n_global=3, schedule=((0, 2),)),
                      privacy=DP, epochs=1)
    nb0 = 17 // 4                              # hospital 0's batches
    a = np.asarray(la[0].losses if isinstance(la, list) else la.losses)
    b = np.asarray(lb[0].losses if isinstance(lb, list) else lb.losses)
    np.testing.assert_array_equal(a[:nb0], b[:nb0])


def test_phantom_invariance(tiny_setup):
    """Widening the slot axis beyond the cohort (phantom rows) must not
    perturb the sampled hospitals' results."""
    clients, adapter = tiny_setup
    sched = ((0, 1), (1, 2))
    st2, s2, _ = _train("fl", clients, adapter,
                        Participation(n_global=3, schedule=sched, slots=2))
    st3, s3, _ = _train("fl", clients, adapter,
                        Participation(n_global=3, schedule=sched, slots=3))
    for a, b in zip(_leaves(st2, s2), _leaves(st3, s3)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_empty_poisson_round_keeps_params(tiny_setup):
    """S1 end-to-end: a Poisson round that samples nobody is a no-op on
    the globals — never a divide-by-zero NaN."""
    clients, adapter = tiny_setup
    seed = next(s for s in range(1000)
                if len(Participation(n_global=3, q=0.05,
                                     seed=s).round_ids(0)) == 0)
    st = make_strategy("fl", adapter, lambda: O.adam(1e-3), 3,
                       participation=Participation(n_global=3, q=0.05,
                                                   seed=seed))
    state = st.setup(jax.random.key(0))
    before = _leaves(st, state)
    state, _ = st.run(state, [c.train for c in clients],
                      np.random.default_rng(0), 4, 1)
    after = _leaves(st, state)
    for a, b in zip(before, after):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# scope validation
# ---------------------------------------------------------------------------

def test_unsupported_combinations_raise(tiny_setup):
    clients, adapter = tiny_setup
    part = Participation(n_global=3, k=2)
    mk = lambda method, **kw: make_strategy(  # noqa: E731
        method, adapter, lambda: O.adam(1e-3), 3, **kw)
    with pytest.raises(ValueError, match="centralized"):
        mk("centralized", participation=part)
    with pytest.raises(ValueError, match="compiled"):
        mk("fl", participation=part, engine="stepwise")
    with pytest.raises(ValueError, match="shard"):
        mk("fl", participation=part, shard=True)
    with pytest.raises(ValueError, match="secagg"):
        mk("fl", participation=part,
           privacy=PrivacyConfig(secagg=True))
    from repro.core.aggregate import SecAggregator
    with pytest.raises(ValueError, match="scan_compatible"):
        mk("fl", participation=part, aggregator=SecAggregator(None))
    with pytest.raises(ValueError, match="n_global"):
        mk("fl", participation=Participation(n_global=5, k=2))
    # split family: fixed-size cohorts only, no observe
    with pytest.raises(ValueError, match="fixed"):
        mk("sl_ac", participation=Participation(n_global=3, q=0.5))
    from repro.obs import Telemetry
    with pytest.raises(ValueError, match="observe"):
        mk("sl_ac", participation=part, observe=Telemetry())


@pytest.mark.parametrize("method", ["fl", "sl_am", "sflv3_ac"])
def test_run_epoch_refuses_participation(method, tiny_setup):
    """Each ``run_epoch`` call would be round 0 again (the same cohort,
    accounted at the sampled rate), so a participating strategy refuses
    it before it draws from ``rng`` or dispatches; ``run`` numbers its
    rounds."""
    clients, adapter = tiny_setup
    st = make_strategy(method, adapter, lambda: O.adam(1e-3), 3,
                       participation=Participation(n_global=3, k=2))
    state = st.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match=r"run\(n_epochs"):
        st.run_epoch(state, [c.train for c in clients], rng, 4)
    assert st._dispatches == 0
    assert rng.bit_generator.state == \
        np.random.default_rng(0).bit_generator.state


# ---------------------------------------------------------------------------
# wire accounting: only sampled clients ship bytes
# ---------------------------------------------------------------------------

def test_wire_client_set_and_sampled_bytes(tiny_setup):
    from repro.wire import Transport
    clients, adapter = tiny_setup
    tp_full = Transport("identity")
    _train("sl_ac", clients, adapter, None, transport=tp_full, epochs=2)
    tp = Transport("identity")
    _train("sl_ac", clients, adapter, Participation(n_global=3, k=2),
           transport=tp, epochs=2)
    assert 0 < tp.bytes_on_wire < tp_full.bytes_on_wire
    assert len(tp.epoch_log) == 2
    for ep in tp.epoch_log:
        assert ep.client_set is not None and len(ep.client_set) == 2
        for c in range(3):
            if c not in ep.client_set:
                assert ep.tr_counts[c] == 0
            else:
                assert ep.tr_counts[c] > 0


# ---------------------------------------------------------------------------
# telemetry: slot metrics un-pad to global columns, NaN where unsampled
# ---------------------------------------------------------------------------

def test_telemetry_participation_columns(tiny_setup):
    from repro.obs import Telemetry
    clients, adapter = tiny_setup
    sched = ((0, 1), (1, 2))
    st, _, _ = _train("fl", clients, adapter,
                      Participation(n_global=3, schedule=sched),
                      observe=Telemetry(), epochs=2)
    rt = st.last_run_telemetry
    assert rt is not None and len(rt.rounds) == 2
    for e, r in enumerate(rt.rounds):
        assert r.participation.tolist() == list(sched[e])
        out = [v for v in r.metrics.values()
               if np.asarray(v).shape == (3,)]
        assert out, "expected per-hospital metric columns"
        unsampled = [c for c in range(3) if c not in sched[e]][0]
        for v in out:
            v = np.asarray(v)
            assert np.isnan(v[unsampled])
            assert np.all(np.isfinite(v[list(sched[e])]))
        assert "participation" in r.to_json()
