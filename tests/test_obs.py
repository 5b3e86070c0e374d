"""Observability tests (repro.obs): telemetry taps riding the compiled
scans, span tracing, profiling summaries, and run reports.

The load-bearing acceptance properties:

  * telemetry-ON params are BIT-identical to telemetry-OFF — observation
    never draws keys or reorders math;
  * an observed compiled ``Strategy.run`` is still ONE dispatch;
  * both engines reduce to the same per-round x per-hospital telemetry
    (the stepwise oracle collects the same taps per step);
  * per-hospital metric rows are un-padded (no phantom hospitals) and the
    per-round epsilon series terminates at the accountant's epsilons.
"""

import json

import jax
import numpy as np
import pytest

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.obs import Telemetry
from repro.obs.telemetry import as_telemetry
from repro.privacy import PrivacyConfig

METHODS = ["fl", "centralized", "sl_am", "sflv2_ac", "sflv3_ac",
           "sflv1_ac"]
ENGINES = ["compiled", "stepwise"]
CUT_METHODS = {"sl_am", "sflv2_ac", "sflv3_ac", "sflv1_ac"}
DP = PrivacyConfig(noise_multiplier=1.1, clip_norm=1.0)
EPOCHS = 2


@pytest.fixture(scope="module")
def tiny_setup():
    clients = make_cxr_clients(seed=0, train_per_client=[17, 12, 9],
                               val_per_client=6, test_per_client=7,
                               image_size=16, n_clients=3)
    cfg = DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)
    return clients, cnn_adapter(build_densenet(cfg))


# one (method, engine, observed, privacy) run each — shared across tests
_CACHE = {}


def _leaves(st, state):
    return [np.asarray(l) for i in range(3)
            for l in jax.tree.leaves(st.params_for_eval(state, i))]


def _run(tiny_setup, method, engine, observed, privacy=None, shard=False):
    key = (method, engine, observed, privacy is not None, shard)
    if key not in _CACHE:
        clients, adapter = tiny_setup
        st = make_strategy(method, adapter, lambda: O.adam(1e-3),
                           len(clients), privacy=privacy, engine=engine,
                           shard=shard,
                           observe=Telemetry() if observed else None)
        state = st.setup(jax.random.key(0))
        state, logs = st.run(state, [c.train for c in clients],
                             np.random.default_rng(0), 4, EPOCHS)
        _CACHE[key] = {"st": st, "leaves": _leaves(st, state),
                       "logs": logs, "rt": st.last_run_telemetry,
                       "dispatches": st._dispatches}
    return _CACHE[key]


# ---------------------------------------------------------------------------
# acceptance: bit-identical params, one dispatch, correct taps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("method", METHODS)
def test_observed_params_bit_identical(method, engine, tiny_setup):
    """Enabling telemetry changes NOTHING about the training math — every
    parameter leaf is bit-for-bit the unobserved run's."""
    off = _run(tiny_setup, method, engine, observed=False)
    on = _run(tiny_setup, method, engine, observed=True)
    assert len(off["leaves"]) == len(on["leaves"])
    for a, b in zip(off["leaves"], on["leaves"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("method", METHODS)
def test_observed_compiled_run_is_one_dispatch(method, tiny_setup):
    """The metric taps ride the whole-run scan as extra outputs: an
    observed multi-epoch compiled run is still ONE program invocation."""
    on = _run(tiny_setup, method, "compiled", observed=True)
    assert on["dispatches"] == 1
    assert getattr(on["st"], "_run_calls", 0) == 1


@pytest.mark.parametrize("method", METHODS)
def test_round_telemetry_content(method, tiny_setup):
    """Per-round telemetry: one RoundTelemetry per epoch, the right tap
    set for the method family, un-padded [n_hospitals] rows."""
    rt = _run(tiny_setup, method, "compiled", observed=True)["rt"]
    assert rt is not None and rt.strategy != ""
    assert len(rt.rounds) == EPOCHS
    n_rows = 1 if method == "centralized" else 3   # pooled vs per-hospital
    for i, r in enumerate(rt.rounds):
        assert r.round_index == i
        keys = set(r.metrics)
        assert {"loss", "grad_norm", "update_norm"} <= keys
        assert ("update_cosine" in keys) == (method == "fl")
        assert ({"cut_mean", "cut_std", "cut_absmax"} <= keys) == (
            method in CUT_METHODS)
        assert "clip_frac" not in keys            # no DP in this run
        assert r.epsilon is None
        for k, v in r.metrics.items():
            v = np.asarray(v)
            assert v.shape == (n_rows,), (k, v.shape)
            assert np.isfinite(v).all(), (k, v)
    # logs carry the same objects
    logs = _run(tiny_setup, method, "compiled", observed=True)["logs"]
    assert [l.telemetry for l in logs] == rt.rounds


@pytest.mark.parametrize("method", METHODS)
def test_telemetry_engine_parity(method, tiny_setup):
    """Both engines reduce to the same per-round x per-hospital values
    (the stepwise oracle taps the same intermediates per step)."""
    rc = _run(tiny_setup, method, "compiled", observed=True)["rt"]
    rs = _run(tiny_setup, method, "stepwise", observed=True)["rt"]
    assert len(rc.rounds) == len(rs.rounds)
    for a, b in zip(rc.rounds, rs.rounds):
        assert set(a.metrics) == set(b.metrics)
        for k in a.metrics:
            np.testing.assert_allclose(a.metrics[k], b.metrics[k],
                                       atol=1e-4, err_msg=k)


def test_fl_update_cosine_bounds(tiny_setup):
    """The FedAvg update cosine is a true cosine: in [-1, 1], and with
    one hospital-weighted mean over three hospitals, not all 1."""
    rt = _run(tiny_setup, "fl", "compiled", observed=True)["rt"]
    cos = rt.metric("update_cosine")
    assert cos.shape == (EPOCHS, 3)
    assert (np.abs(cos) <= 1.0 + 1e-6).all()


# ---------------------------------------------------------------------------
# DP runs: clip fractions + per-round epsilon series
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["fl", "sl_am"])
def test_observed_dp_run(method, tiny_setup):
    off = _run(tiny_setup, method, "compiled", observed=False, privacy=DP)
    on = _run(tiny_setup, method, "compiled", observed=True, privacy=DP)
    for a, b in zip(off["leaves"], on["leaves"]):
        np.testing.assert_array_equal(a, b)
    rt = on["rt"]
    eps_prev = np.zeros(3)
    for r in rt.rounds:
        cf = np.asarray(r.metrics["clip_frac"])
        assert ((cf >= 0) & (cf <= 1)).all()
        assert r.epsilon is not None and r.epsilon.shape == (3,)
        assert (r.epsilon > eps_prev).all()       # cumulative composition
        eps_prev = r.epsilon
    # the series terminates at exactly the real accountant's epsilons
    report = on["st"].privacy_report()
    np.testing.assert_allclose(
        rt.rounds[-1].epsilon, [r["epsilon"] for r in report], rtol=1e-9)


def test_observed_dp_telemetry_engine_parity(tiny_setup):
    rc = _run(tiny_setup, "fl", "compiled", observed=True, privacy=DP)["rt"]
    rs = _run(tiny_setup, "fl", "stepwise", observed=True, privacy=DP)["rt"]
    for a, b in zip(rc.rounds, rs.rounds):
        for k in a.metrics:
            np.testing.assert_allclose(a.metrics[k], b.metrics[k],
                                       atol=1e-4, err_msg=k)
        np.testing.assert_allclose(a.epsilon, b.epsilon, rtol=1e-9)


# ---------------------------------------------------------------------------
# placement: telemetry rides the hosp mesh, phantom hospitals un-padded
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["fl", "sflv3_ac"])
def test_observed_sharded_run(method, tiny_setup):
    """shard=True: metric stacks ride the "hosp" mesh next to the losses;
    the reduced telemetry is identical to the unsharded run and params
    match the unsharded observed run (exercised for real on the
    8-virtual-device CI job; a no-op mesh on one device)."""
    plain = _run(tiny_setup, method, "compiled", observed=True)
    sharded = _run(tiny_setup, method, "compiled", observed=True,
                   shard=True)
    assert sharded["dispatches"] == 1
    for a, b in zip(plain["leaves"], sharded["leaves"]):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for ra, rb in zip(plain["rt"].rounds, sharded["rt"].rounds):
        assert set(ra.metrics) == set(rb.metrics)
        for k in ra.metrics:
            assert np.asarray(rb.metrics[k]).shape == (3,)   # un-padded
            np.testing.assert_allclose(ra.metrics[k], rb.metrics[k],
                                       atol=1e-4, err_msg=k)


# ---------------------------------------------------------------------------
# spec plumbing: make_strategy(observe=), run(observe=), as_telemetry
# ---------------------------------------------------------------------------

def test_as_telemetry_normalization():
    assert as_telemetry(None) is None
    assert as_telemetry(False) is None
    assert as_telemetry(True) == Telemetry()
    t = Telemetry(cut_stats=False)
    assert as_telemetry(t) is t
    off = Telemetry(loss=False, norms=False, update_cosine=False,
                    cut_stats=False, clip_fraction=False, epsilon=False)
    assert as_telemetry(off) is None              # all-off spec == off
    with pytest.raises(TypeError):
        as_telemetry("yes")


def test_run_observe_override(tiny_setup):
    """run(observe=) overrides the constructor spec per run: False
    disables, a Telemetry enables, None inherits."""
    clients, adapter = tiny_setup
    data = [c.train for c in clients]
    st = make_strategy("fl", adapter, lambda: O.adam(1e-3), 3,
                       observe=Telemetry())
    state = st.setup(jax.random.key(0))
    state, logs = st.run(state, data, np.random.default_rng(0), 4, 1,
                         observe=False)
    assert st.last_run_telemetry is None
    assert logs[0].telemetry is None
    state, logs = st.run(state, data, np.random.default_rng(0), 4, 1)
    assert st.last_run_telemetry is not None      # inherits constructor


def test_telemetry_flag_subsets(tiny_setup):
    """Disabled taps are absent — the step metric key set is static per
    spec, so a norms-off run never computes norms."""
    clients, adapter = tiny_setup
    st = make_strategy("sl_am", adapter, lambda: O.adam(1e-3), 3)
    state = st.setup(jax.random.key(0))
    spec = Telemetry(norms=False, cut_stats=False)
    st.run(state, [c.train for c in clients], np.random.default_rng(0),
           4, 1, observe=spec)
    r = st.last_run_telemetry.rounds[0]
    assert set(r.metrics) == {"loss"}
    assert spec.step_keys(dp=False, cut=True) == ()


def test_step_keys_static_sets():
    t = Telemetry()
    assert t.step_keys(dp=False, cut=False) == ("grad_norm", "update_norm")
    assert t.step_keys(dp=True, cut=True) == (
        "grad_norm", "update_norm", "cut_mean", "cut_std", "cut_absmax",
        "clip_frac")


# ---------------------------------------------------------------------------
# trace.py: span tree, wire lanes, merged JSON
# ---------------------------------------------------------------------------

def test_tracer_span_tree():
    from repro.obs.trace import Tracer
    tr = Tracer()
    with tr.span("run", strategy="fl"):
        with tr.span("pack"):
            pass
        with tr.span("enqueue"):
            pass
    names = [e["name"] for e in tr.events]
    assert names == ["pack", "enqueue", "run"]   # children close first
    run = tr.find("run")
    disp = tr.find("enqueue")
    assert run["args"]["strategy"] == "fl" and run["args"]["depth"] == 0
    assert disp["args"]["depth"] == 1
    # the spans of one run share its ordinal and name their parent
    assert run["args"]["run"] == disp["args"]["run"] == 1
    assert disp["args"]["parent"] == "run" and run["args"]["parent"] is None
    # children nest inside the parent span's interval
    assert run["ts"] <= disp["ts"]
    assert disp["ts"] + disp["dur"] <= run["ts"] + run["dur"] + 1.0
    assert any(e["ph"] == "M" for e in tr.trace_events())


def test_strategy_records_spans(tiny_setup):
    from repro.obs.trace import Tracer
    clients, adapter = tiny_setup
    st = make_strategy("fl", adapter, lambda: O.adam(1e-3), 3,
                       observe=Telemetry())
    tr = st.attach_tracer(Tracer())
    state = st.setup(jax.random.key(0))
    st.run(state, [c.train for c in clients], np.random.default_rng(0),
           4, 2)
    assert tr.find("run") is not None
    assert tr.find("pack") is not None
    assert tr.find("enqueue") is not None


def test_wire_events_and_merge(tmp_path):
    from repro.obs.trace import (PID_WIRE, merge_events, wire_events,
                                 write_chrome_trace)
    from repro.wire.simulator import simulate
    clients = make_cxr_clients(seed=0, train_per_client=[8, 8],
                               val_per_client=4, test_per_client=4,
                               image_size=8, n_clients=2)
    cfg = DenseNetConfig(growth=2, blocks=(1, 1), stem_ch=4, cut_layer=1)
    adapter = cnn_adapter(build_densenet(cfg))
    sim = simulate("sl_ac", adapter, {k: v[:1] for k, v in
                                      clients[0].train.items()},
                   [8, 8], [4, 4], 4)
    evs = wire_events(sim)
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs and all(e["pid"] == PID_WIRE for e in xs)
    assert sum(e["args"]["bytes"] for e in xs) == int(sim.bytes_on_wire)
    merged = merge_events(evs, pid_offset=10)
    assert all(e["pid"] == PID_WIRE + 10 for e in merged
               if e["ph"] == "X")
    path = write_chrome_trace(merged, tmp_path / "trace.json")
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert len(doc["traceEvents"]) == len(merged)


# ---------------------------------------------------------------------------
# profile.py + report.py
# ---------------------------------------------------------------------------

def test_cost_summary_and_hlo(tiny_setup):
    from repro.obs.profile import cost_summary, hlo_cost
    on = _run(tiny_setup, "fl", "compiled", observed=True)
    cost = hlo_cost(on["st"])
    assert cost is not None
    assert cost["compile_seconds"] > 0
    assert cost["flops"] > 0
    summary = cost_summary(on["st"], wall_seconds=2.0, total_steps=22)
    assert summary["strategy"] == "fl"
    assert summary["dispatches"] == 1
    assert summary["steps_per_s"] == pytest.approx(11.0)


def test_hlo_cost_requires_a_compiled_run(tiny_setup):
    from repro.obs.profile import hlo_cost
    clients, adapter = tiny_setup
    st = make_strategy("fl", adapter, lambda: O.adam(1e-3), 3)
    assert hlo_cost(st) is None                  # nothing dispatched yet


def test_runlog_and_report(tiny_setup, tmp_path):
    from repro.obs.report import (render_markdown, write_report,
                                  write_runlog)
    on = _run(tiny_setup, "fl", "compiled", observed=True)
    rt = on["rt"]
    path = write_runlog(tmp_path, "fl", telemetry=rt,
                        cost={"dispatches": 1}, extra={"note": "test"})
    with open(path) as f:
        doc = json.load(f)
    assert doc["telemetry"]["strategy"] == "fl"
    assert len(doc["telemetry"]["rounds"]) == EPOCHS
    assert doc["cost"]["dispatches"] == 1 and doc["note"] == "test"
    md = render_markdown(rt, cost={"dispatches": 1})
    assert "| round |" in md and "loss" in md
    rpath = write_report(tmp_path, "fl", rt)
    assert "| round |" in open(rpath).read()
    # table renders one row per round with finite scalars
    lines = rt.table().splitlines()
    assert len(lines) == 2 + EPOCHS


def test_jax_profile_context(tmp_path):
    from repro.obs.profile import jax_profile
    with jax_profile(tmp_path / "jaxtrace"):
        jax.block_until_ready(jax.numpy.ones((4,)) * 2)
    # the profiler really ran: its xplane dump is on disk
    assert list((tmp_path / "jaxtrace").rglob("*.xplane.pb"))
