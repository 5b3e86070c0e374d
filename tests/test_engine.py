"""Compiled-engine tests: pad-and-mask packing, schedule arrays, and the
stepwise-vs-compiled parity suite (params, losses, accountant)."""

import jax
import numpy as np
import pytest

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.schedule import SCHEDULES, schedule_array
from repro.core.strategies import make_strategy
from repro.core.strategies.base import EpochLog, np_batches
from repro.core.strategies.engine import pack_epoch
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet
from repro.privacy import PrivacyConfig

METHODS = ["fl", "sl_ac", "sl_am", "sflv2_ac", "sflv3_ac"]
DP = PrivacyConfig(noise_multiplier=1.1, clip_norm=1.0)
CUT = PrivacyConfig(cut_noise_std=0.5)


@pytest.fixture(scope="module")
def tiny_setup():
    # uneven hospitals (17/12/9 @ batch 4) => masked steps + remainders
    clients = make_cxr_clients(seed=0, train_per_client=[17, 12, 9],
                               val_per_client=6, test_per_client=7,
                               image_size=16, n_clients=3)
    cfg = DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)
    return clients, cnn_adapter(build_densenet(cfg))


def _run(method, engine, clients, adapter, privacy=None, epochs=1,
         drop_remainder=True, batch=4):
    st = make_strategy(method, adapter, lambda: O.adam(1e-3), len(clients),
                       privacy=privacy, engine=engine,
                       drop_remainder=drop_remainder)
    state = st.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    log = None
    for _ in range(epochs):
        state, log = st.run_epoch(state, [c.train for c in clients], rng,
                                  batch)
    return st, state, log


def _assert_parity(method, clients, adapter, privacy=None, epochs=1,
                   drop_remainder=True, atol=1e-5):
    st_a, sa, la = _run(method, "stepwise", clients, adapter, privacy,
                        epochs, drop_remainder)
    st_b, sb, lb = _run(method, "compiled", clients, adapter, privacy,
                        epochs, drop_remainder)
    assert len(la.losses) == len(lb.losses)
    np.testing.assert_allclose(la.losses, lb.losses, atol=atol)
    assert abs(la.mean_loss - lb.mean_loss) < atol
    assert la.client_steps == lb.client_steps
    for i in range(len(clients)):
        pa, pb = st_a.params_for_eval(sa, i), st_b.params_for_eval(sb, i)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=atol)
    ra, rb = st_a.privacy_report(), st_b.privacy_report()
    assert len(ra) == len(rb)
    for x, y in zip(ra, rb):
        assert x["steps"] == y["steps"]
        assert abs(x["epsilon"] - y["epsilon"]) < 1e-9
        assert x["delta"] == y["delta"]


# ---------------------------------------------------------------------------
# parity suite (acceptance: <= 1e-5 on params/losses, privacy on and off)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_parity_plain(method, tiny_setup):
    clients, adapter = tiny_setup
    _assert_parity(method, clients, adapter)


def test_parity_multi_epoch_and_centralized(tiny_setup):
    clients, adapter = tiny_setup
    _assert_parity("fl", clients, adapter, epochs=2)
    _assert_parity("centralized", clients, adapter, epochs=2)


@pytest.mark.parametrize("method", ["fl", "sl_am", "sflv3_ac"])
def test_parity_dp(method, tiny_setup):
    """DP-SGD draws are key-indexed fold-ins: bit-identical across engines,
    and the analytic accountant composition matches step-by-step counts."""
    clients, adapter = tiny_setup
    _assert_parity(method, clients, adapter, privacy=DP)


@pytest.mark.slow
@pytest.mark.parametrize("method", ["sl_ac", "sflv2_ac"])
def test_parity_dp_full_grid(method, tiny_setup):
    clients, adapter = tiny_setup
    _assert_parity(method, clients, adapter, privacy=DP, epochs=2)


def test_parity_cut_noise(tiny_setup):
    clients, adapter = tiny_setup
    _assert_parity("sl_ac", clients, adapter, privacy=CUT)


@pytest.mark.parametrize("method", ["sl_am", "sflv2_ac"])
def test_parity_cut_noise_keep_remainder(method, tiny_setup):
    """Cut-layer noise WITHOUT DP + drop_remainder=False: noise draws are
    per-example (fold_in by row index), so the compiled pad-and-mask rows
    draw exactly what the stepwise short batch draws — padded rows get
    zero noise and zero loss weight."""
    clients, adapter = tiny_setup
    _assert_parity(method, clients, adapter, privacy=CUT,
                   drop_remainder=False)


def test_parity_fl_secagg(tiny_setup):
    """secagg keeps the host-side masked aggregation on the compiled path
    (per-client uploads must exist to be masked)."""
    clients, adapter = tiny_setup
    _assert_parity("fl", clients, adapter,
                   privacy=PrivacyConfig(secagg=True))


@pytest.mark.parametrize("method", ["fl", "sl_am"])
def test_parity_keep_remainder(method, tiny_setup):
    """drop_remainder=False: stepwise short batches == compiled
    pad-and-mask per-example weights."""
    clients, adapter = tiny_setup
    _assert_parity(method, clients, adapter, drop_remainder=False)


@pytest.mark.parametrize("method", ["fl", "sl_am"])
def test_parity_dp_keep_remainder(method, tiny_setup):
    """DP + drop_remainder=False on the compiled engine: weighted
    per-example clipping makes padded rows exact no-ops, so the stepwise
    short-batch DP step (the parity oracle) is matched — losses, params,
    AND accountant epsilon."""
    clients, adapter = tiny_setup
    _assert_parity(method, clients, adapter, privacy=DP,
                   drop_remainder=False)


# ---------------------------------------------------------------------------
# whole-run programs: Strategy.run(n_epochs) as ONE XLA call
# ---------------------------------------------------------------------------

RUN_METHODS = ["centralized", "fl", "sl_am", "sflv2_ac", "sflv3_ac"]


def _whole_run(method, engine, clients, adapter, privacy=None, epochs=3,
               batch=4, drop_remainder=True):
    st = make_strategy(method, adapter, lambda: O.adam(1e-3), len(clients),
                       privacy=privacy, engine=engine,
                       drop_remainder=drop_remainder)
    state = st.setup(jax.random.key(0))
    state, logs = st.run(state, [c.train for c in clients],
                         np.random.default_rng(0), batch, epochs)
    return st, state, logs


def _assert_run_parity(method, clients, adapter, privacy=None, epochs=3,
                       atol=1e-5):
    st_a, sa, la = _whole_run(method, "stepwise", clients, adapter, privacy,
                              epochs)
    st_b, sb, lb = _whole_run(method, "compiled", clients, adapter, privacy,
                              epochs)
    assert len(la) == len(lb) == epochs
    for ea, eb in zip(la, lb):
        assert len(ea.losses) == len(eb.losses)
        np.testing.assert_allclose(ea.losses, eb.losses, atol=atol)
        assert ea.client_steps == eb.client_steps
        assert ea.weights == eb.weights
    for i in range(len(clients)):
        pa, pb = st_a.params_for_eval(sa, i), st_b.params_for_eval(sb, i)
        for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=atol)
    ra, rb = st_a.privacy_report(), st_b.privacy_report()
    assert len(ra) == len(rb)
    for x, y in zip(ra, rb):
        assert x["steps"] == y["steps"]
        assert abs(x["epsilon"] - y["epsilon"]) < 1e-9


@pytest.mark.parametrize("method", RUN_METHODS)
def test_run_parity_plain(method, tiny_setup):
    """3-epoch whole-run program == stepwise epoch loop (<= 1e-5)."""
    clients, adapter = tiny_setup
    _assert_run_parity(method, clients, adapter)


@pytest.mark.parametrize("method", ["fl", "sl_am", "sflv3_ac"])
def test_run_parity_dp(method, tiny_setup):
    clients, adapter = tiny_setup
    _assert_run_parity(method, clients, adapter, privacy=DP, epochs=2)


@pytest.mark.slow
@pytest.mark.parametrize("method", ["centralized", "sl_ac", "sflv2_ac"])
def test_run_parity_dp_full_grid(method, tiny_setup):
    clients, adapter = tiny_setup
    _assert_run_parity(method, clients, adapter, privacy=DP, epochs=2)


def test_run_parity_cut_noise(tiny_setup):
    clients, adapter = tiny_setup
    _assert_run_parity("sl_ac", clients, adapter, privacy=CUT, epochs=2)


def _grid_run(st, state, data, rng, batch, epochs):
    """SFLv3/v1 whole run on ``pack_run``'s host-packed batch grid: the
    unchanged synchronous-step body scanned over epochs, each step
    indexing the grid — the layout the device gather replaces."""
    from repro.core.aggregate import mean_sync
    from repro.core.strategies.engine import _sflv3_epoch_body, pack_run
    batches, packed = pack_run(data, batch, rng, epochs)
    steps = packed.nb_max
    epoch = _sflv3_epoch_body(st.adapter, st._opt_c, st._opt_s,
                              st.n_clients, st.transport, st.privacy)
    b_idx = np.stack([[s % nb for nb in packed.n_batches]
                      for s in range(steps)]).astype(np.int32)
    key_idx = np.stack([
        st._take_key_indices(steps) if st._keyed
        else np.zeros((steps,), np.uint32) for _ in range(epochs)])

    def run(carry, batches, key_idx, base_key):
        def round_body(carry, xs):
            b_e, ki_e = xs
            out = epoch(*carry, b_e, b_idx, ki_e, base_key)
            sc = mean_sync(out[0]) if st._sync_stacked else out[0]
            return (sc, *out[1:4]), out[4]
        return jax.lax.scan(round_body, carry, (batches, key_idx))

    carry = (state["stacked_clients"], state["server"], state["c_opt"],
             state["s_opt"])
    return jax.jit(run)(carry, batches, key_idx, st._privacy_base_key())


@pytest.mark.parametrize("privacy", [None, DP, CUT], ids=["plain", "dp",
                                                           "cut_noise"])
@pytest.mark.parametrize("method", ["sflv3_ac", "sflv1_ac"])
def test_device_gather_matches_the_packed_grid(method, privacy, tiny_setup):
    """The unsharded SFLv3/v1 run gathers its batches on the device from
    the hospitals' arrays and an index grid: same losses and state, bit
    for bit, as the host-packed grid, and the same host rng draws."""
    clients, adapter = tiny_setup
    data = [c.train for c in clients]

    def strategy():
        st = make_strategy(method, adapter, lambda: O.adam(1e-3),
                           len(clients), privacy=privacy)
        return st, st.setup(jax.random.key(0))

    st, state = strategy()
    rng = np.random.default_rng(0)
    state, logs = st.run(state, data, rng, 4, 2)
    _, args = st._last_run_invocation        # the hospitals' own arrays
    assert [a.shape[0] for a in args[4]["label"]] == [
        len(d["label"]) for d in data]
    ref, ref_state = strategy()
    ref_rng = np.random.default_rng(0)
    carry, losses = _grid_run(ref, ref_state, data, ref_rng, 4, 2)
    assert rng.bit_generator.state == ref_rng.bit_generator.state
    assert [lg.losses for lg in logs] == [
        np.asarray(l).reshape(-1).tolist() for l in losses]
    got = (state["stacked_clients"], state["server"], state["c_opt"],
           state["s_opt"])
    assert jax.tree.structure(got) == jax.tree.structure(carry)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _interleaved_grid_run(st, state, data, rng, batch, epochs):
    """SL/SFLv2 whole run on ``pack_run``'s host-packed batch grid: the
    unchanged schedule-interleave body scanned over epochs, each step
    indexing the grid — the layout the device gather replaces."""
    import jax.numpy as jnp
    from repro.core.aggregate import mean_sync
    from repro.core.strategies.engine import (_interleaved_epoch_body,
                                              pack_run)
    place = st.placement
    batches, packed = pack_run(data, batch, rng, epochs,
                               pad_clients=place.n_pad)
    sched = schedule_array(st.schedule, packed.n_batches)
    epoch = _interleaved_epoch_body(st.adapter, st._opt_c, st._opt_s,
                                    st.transport, st.privacy)
    key_idx = np.stack([
        st._take_key_indices(len(sched)) if st._keyed
        else np.zeros((len(sched),), np.uint32) for _ in range(epochs)])
    w = jnp.asarray(place.client_weights()) if place.padded else None

    def run(carry, batches, key_idx, base_key):
        def round_body(carry, xs):
            b_e, ki_e = xs
            out = epoch(*carry, b_e, None, sched, ki_e, base_key)
            sc = mean_sync(out[0], w) if st._sync_stacked else out[0]
            return (sc, *out[1:4]), out[4]
        return jax.lax.scan(round_body, carry, (batches, key_idx))

    st._ensure_stacked(state)
    carry = (state["stacked_clients"], state["server"],
             state["stacked_c_opts"], state["s_opt"])
    return jax.jit(run)(carry, batches, key_idx, st._privacy_base_key())


def _assert_split_gather(method, adapter, data, privacy=None, pad=0,
                         epochs=2):
    """An SL/SFLv2 ``Strategy.run`` equals the host-packed grid run bit
    for bit, from the same host rng draws, and the program received the
    hospitals' own arrays."""
    from repro.core.placement import Placement

    def strategy():
        st = make_strategy(method, adapter, lambda: O.adam(1e-3),
                           len(data), privacy=privacy)
        st.placement = Placement(len(data), len(data) + pad)
        return st, st.setup(jax.random.key(0))

    st, state = strategy()
    rng = np.random.default_rng(0)
    state, logs = st.run(state, data, rng, 4, epochs)
    _, args = st._last_run_invocation        # the hospitals' own arrays
    assert [a.shape[0] for a in args[4]["label"]] == [
        len(d["label"]) for d in data]
    assert args[5].shape[:2] == (epochs, len(data) + pad)
    ref, ref_state = strategy()
    ref_rng = np.random.default_rng(0)
    carry, losses = _interleaved_grid_run(ref, ref_state, data, ref_rng, 4,
                                          epochs)
    assert rng.bit_generator.state == ref_rng.bit_generator.state
    assert [lg.losses for lg in logs] == np.asarray(losses).tolist()
    got = (state["stacked_clients"], state["server"],
           state["stacked_c_opts"], state["s_opt"])
    assert jax.tree.structure(got) == jax.tree.structure(carry)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(carry)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("pad", [0, 1], ids=["real", "phantom"])
@pytest.mark.parametrize("privacy", [None, DP], ids=["plain", "dp"])
@pytest.mark.parametrize("method", ["sl_am", "sl_ac", "sflv2_ac"])
def test_split_device_gather_matches_the_packed_grid(method, privacy, pad,
                                                     tiny_setup):
    """The unsharded SL/SFLv2 run gathers each step's batch on the device
    from its hospital's own arrays and an index grid: same losses and
    state, bit for bit, as the host-packed grid; ``pad`` phantom
    hospitals (a placement's padding without a mesh) ride along
    unscheduled."""
    clients, adapter = tiny_setup
    _assert_split_gather(method, adapter, [c.train for c in clients],
                         privacy, pad)


def test_split_device_gather_skips_a_hospital_without_rows(tiny_setup):
    """A hospital that holds no rows is never scheduled, and its branch
    of the gather reads nothing."""
    clients, adapter = tiny_setup
    data = [c.train for c in clients]
    data[1] = {k: v[:0] for k, v in data[1].items()}
    _assert_split_gather("sl_am", adapter, data, epochs=1)


@pytest.mark.parametrize("method", RUN_METHODS)
def test_run_is_one_program(method, tiny_setup):
    """A 3-epoch compiled run is ONE XLA dispatch: the whole-run program
    is invoked exactly once, traced/compiled exactly once, and no other
    training program is dispatched."""
    clients, adapter = tiny_setup
    st, _, logs = _whole_run(method, "compiled", clients, adapter)
    assert len(logs) == 3
    assert getattr(st, "_run_calls", 0) == 1
    assert st._dispatches == 1, "a program other than the run was dispatched"
    run_fn = getattr(st, "_run_c", None) or getattr(st, "_run3_c", None)
    assert run_fn is not None
    if hasattr(run_fn, "_cache_size"):
        assert run_fn._cache_size() == 1
    # a second run reuses the same compiled program (no retrace)
    st.run(st.setup(jax.random.key(1)), [c.train for c in clients],
           np.random.default_rng(1), 4, 3)
    assert st._run_calls == st._dispatches == 2
    if hasattr(run_fn, "_cache_size"):
        assert run_fn._cache_size() == 1


@pytest.mark.parametrize("method", RUN_METHODS)
def test_run_epoch_is_a_one_round_run(method, tiny_setup):
    """Compiled ``run_epoch`` is ``run(n_epochs=1)``: one whole-run
    dispatch of the same program, and from the same state and rng seed
    the same state, log and rng draws, bit for bit."""
    clients, adapter = tiny_setup
    data = [c.train for c in clients]
    st = make_strategy(method, adapter, lambda: O.adam(1e-3), len(clients))
    rng_a = np.random.default_rng(0)
    state, log = st.run_epoch(st.setup(jax.random.key(0)), data, rng_a, 4)
    assert st._run_calls == st._dispatches == 1
    got = [np.asarray(l) for i in range(len(clients))
           for l in jax.tree.leaves(st.params_for_eval(state, i))]
    rng_b = np.random.default_rng(0)
    state, logs = st.run(st.setup(jax.random.key(0)), data, rng_b, 4, 1)
    assert st._run_calls == st._dispatches == 2
    run_fn = getattr(st, "_run_c", None) or getattr(st, "_run3_c", None)
    if hasattr(run_fn, "_cache_size"):
        assert run_fn._cache_size() == 1        # one program for both
    assert logs == [log]
    assert rng_a.bit_generator.state == rng_b.bit_generator.state
    want = [np.asarray(l) for i in range(len(clients))
            for l in jax.tree.leaves(st.params_for_eval(state, i))]
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_abstract_args_copies_nothing_to_the_device(monkeypatch):
    """The stash's skeleton reads a host leaf's shape and dtype (as jit
    gives it) without a device copy; a committed array keeps its
    sharding."""
    import jax.numpy as jnp
    from repro.core.strategies.engine import abstract_args

    def no_copy(*a, **k):
        raise AssertionError("abstract_args copied a leaf to the device")

    host = {"image": [np.zeros((5, 8, 8, 1), np.float32),
                      np.zeros((3, 8, 8, 1), np.float32)],
            "idx": np.zeros((2, 3, 4), np.int32),
            "wide": np.zeros((2,), np.int64), "scalar": 1.5}
    committed = jax.device_put(np.ones((3,), np.float32), jax.devices()[0])
    n_live = len(jax.live_arrays())
    with monkeypatch.context() as m:
        for mod, name in ((jnp, "asarray"), (jnp, "array"),
                          (jax, "device_put")):
            m.setattr(mod, name, no_copy)
        got = abstract_args((host, committed))
    assert len(jax.live_arrays()) == n_live
    skel, placed = got
    assert [(s.shape, s.dtype) for s in skel["image"]] == [
        ((5, 8, 8, 1), np.float32), ((3, 8, 8, 1), np.float32)]
    assert (skel["idx"].shape, skel["idx"].dtype) == ((2, 3, 4), np.int32)
    assert skel["wide"].dtype == jnp.asarray(host["wide"]).dtype
    assert (skel["scalar"].shape, skel["scalar"].dtype) == ((), np.float32)
    assert all(s.sharding is None for s in jax.tree.leaves(skel))
    assert placed.sharding == committed.sharding
    assert placed.shape == (3,) and placed.dtype == np.float32


def test_run_secagg_falls_back_to_per_round(tiny_setup):
    """Secagg's masked uploads are host-side: run() keeps per-epoch
    dispatch but still matches the stepwise reference."""
    clients, adapter = tiny_setup
    priv = PrivacyConfig(secagg=True)
    _assert_run_parity("fl", clients, adapter, privacy=priv, epochs=2)
    st, _, _ = _whole_run("fl", "compiled", clients, adapter, privacy=priv,
                          epochs=2)
    assert getattr(st, "_run_calls", 0) == 0     # whole-run path not taken


def test_run_empty_epochs(tiny_setup):
    """Zero epochs train nothing.  So does a compiled run in which no
    hospital has a full batch, under ``run`` and ``run_epoch`` alike: it
    compiles nothing, draws the shuffles a pack would, and logs one empty
    epoch per epoch (SFLv2 still averages its client segments); SFLv3
    refuses it, and a participating run returns no epochs."""
    from repro.core.participation import Participation
    clients, adapter = tiny_setup
    st = make_strategy("fl", adapter, lambda: O.adam(1e-3), len(clients))
    state = st.setup(jax.random.key(0))
    state, logs = st.run(state, [c.train for c in clients],
                         np.random.default_rng(0), 4, 0)
    assert logs == []

    # 3 rows a hospital, 9 pooled: no batch of 10 anywhere
    short = [{k: v[:3] for k, v in c.train.items()} for c in clients]
    n = len(short)
    empty = {"fl": EpochLog([], 0, client_steps=[0] * n),
             "centralized": EpochLog([], 0),
             "sl_am": EpochLog([], 0, client_steps=[0] * n),
             "sflv2_ac": EpochLog([], 0, client_steps=[0] * n)}
    for method in RUN_METHODS:
        for epochs in (2, None):               # run(2), then run_epoch
            st = make_strategy(method, adapter, lambda: O.adam(1e-3), n)
            state = st.setup(jax.random.key(0))
            rng = np.random.default_rng(0)
            if method == "sflv3_ac":
                with pytest.raises(ValueError, match="fewer than batch"):
                    if epochs is None:
                        st.run_epoch(state, short, rng, 10)
                    else:
                        st.run(state, short, rng, 10, epochs)
                continue
            if epochs is None:
                state, log = st.run_epoch(state, short, rng, 10)
                logs = [log]
            else:
                state, logs = st.run(state, short, rng, 10, epochs)
            assert logs == [empty[method]] * (epochs or 1)
            assert st._dispatches == 0
            ref = np.random.default_rng(0)
            pools = [9] if method == "centralized" else [3] * n
            for _ in range(epochs or 1):
                for rows in pools:
                    ref.shuffle(np.arange(rows))
            assert rng.bit_generator.state == ref.bit_generator.state
            if method == "sflv2_ac":            # the end-of-epoch sync ran
                for i in range(1, n):
                    for a, b in zip(
                            jax.tree.leaves(st.params_for_eval(state, 0)),
                            jax.tree.leaves(st.params_for_eval(state, i))):
                        np.testing.assert_array_equal(np.asarray(a),
                                                      np.asarray(b))
    st = make_strategy("fl", adapter, lambda: O.adam(1e-3), n,
                       participation=Participation(n_global=n, k=2))
    state = st.setup(jax.random.key(0))
    assert st.run(state, short, np.random.default_rng(0), 10, 2)[1] == []


@pytest.mark.parametrize("method", ["fl", "sl_am"])
def test_run_keep_remainder_parity(method, tiny_setup):
    """drop_remainder=False whole-run: stepwise short batches == compiled
    pad-and-mask per-example weights, across every round."""
    clients, adapter = tiny_setup
    st_a, sa, la = _whole_run(method, "stepwise", clients, adapter,
                              drop_remainder=False)
    st_b, sb, lb = _whole_run(method, "compiled", clients, adapter,
                              drop_remainder=False)
    for ea, eb in zip(la, lb):
        np.testing.assert_allclose(ea.losses, eb.losses, atol=1e-5)
        assert ea.weights == eb.weights
    for i in range(len(clients)):
        for a, b in zip(jax.tree.leaves(st_a.params_for_eval(sa, i)),
                        jax.tree.leaves(st_b.params_for_eval(sb, i))):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# np_batches remainder handling (satellite)
# ---------------------------------------------------------------------------

def test_np_batches_remainder():
    data = {"x": np.arange(11)[:, None], "label": np.arange(11)}
    dropped = np_batches(data, 4, None)
    assert [len(b["label"]) for b in dropped] == [4, 4]      # 3 lost
    kept = np_batches(data, 4, None, drop_remainder=False)
    assert [len(b["label"]) for b in kept] == [4, 4, 3]
    assert sorted(np.concatenate([b["label"] for b in kept])) == list(
        range(11))
    # shuffles must be identical across the two modes
    a = np_batches(data, 4, np.random.default_rng(7))
    b = np_batches(data, 4, np.random.default_rng(7),
                   drop_remainder=False)
    np.testing.assert_array_equal(a[0]["label"], b[0]["label"])


def test_pack_epoch_matches_np_batches():
    data = [{"x": np.arange(10, dtype=np.float32)[:, None],
             "label": np.arange(10)},
            {"x": np.arange(5, dtype=np.float32)[:, None],
             "label": np.arange(5)}]
    packed = pack_epoch(data, 2, np.random.default_rng(3))
    assert packed.mask.shape == (2, 5)
    assert packed.n_batches == [5, 2]
    assert packed.mask[1].tolist() == [True, True, False, False, False]
    # same rng stream => identical batch contents as the stepwise path
    # (ONE generator consumed in hospital order, as strategies do)
    rng = np.random.default_rng(3)
    stepwise = [np_batches(d, 2, rng) for d in data]
    for c, bs in enumerate(stepwise):
        for j, b in enumerate(bs):
            np.testing.assert_array_equal(
                packed.batches["label"][c, j], b["label"])
    # padding rows are flagged invalid, remainder kept under pad-and-mask
    kept = pack_epoch(data, 3, np.random.default_rng(0),
                      drop_remainder=False)
    assert kept.n_batches == [4, 2]
    assert kept.ex_weights[0, 3].tolist() == [1.0, 0.0, 0.0]
    assert kept.step_examples[0] == [3, 3, 3, 1]


@pytest.mark.parametrize("drop_remainder", [True, False])
def test_pack_run_index_matches_pack_run(drop_remainder):
    """Gathering the index grid from the hospitals' rows laid end to end
    gives ``pack_run``'s grid wherever it holds data, from the same rng
    draws, with the same meta; the arrays are the caller's own."""
    from repro.core.strategies.engine import pack_run, pack_run_index
    data = [{"x": np.arange(n, dtype=np.float32)[:, None] + 100 * c,
             "label": np.arange(n) + 100 * c}
            for c, n in enumerate([10, 5, 7])]
    rng_a, rng_b = np.random.default_rng(3), np.random.default_rng(3)
    grid, meta = pack_run(data, 3, rng_a, 2, drop_remainder, pad_clients=1)
    got, idx, meta_i = pack_run_index(data, 3, rng_b, 2, drop_remainder,
                                      pad_clients=1)
    assert rng_a.bit_generator.state == rng_b.bit_generator.state
    assert idx.dtype == np.int32 and idx.shape == grid["x"].shape[:4]
    assert all(a is d[k] for k in got for a, d in zip(got[k], data))
    for k in ("n_batches", "step_examples", "n_samples", "batch_size"):
        assert getattr(meta_i, k) == getattr(meta, k)
    np.testing.assert_array_equal(meta_i.mask, meta.mask)
    if drop_remainder:
        assert meta_i.ex_weights is None
        real = np.broadcast_to(meta.mask[None, :, :, None], idx.shape)
    else:
        np.testing.assert_array_equal(meta_i.ex_weights, meta.ex_weights)
        real = np.broadcast_to(meta.ex_weights[None] > 0, idx.shape)
    for k in data[0]:
        gathered = np.concatenate(got[k])[idx]
        np.testing.assert_array_equal(gathered[real], grid[k][real])
    assert not idx[~real].any()                   # padding points at row 0


def test_schedule_array_matches_schedules():
    nb = [3, 1, 2]
    for name in ("ac", "am"):
        arr = schedule_array(name, nb)
        assert arr.dtype == np.int32 and arr.shape == (6, 2)
        assert [tuple(r) for r in arr] == SCHEDULES[name](nb)


# ---------------------------------------------------------------------------
# EpochLog statistics (satellite)
# ---------------------------------------------------------------------------

def test_epochlog_mask_aware_mean():
    log = EpochLog([1.0, 3.0], 2)
    assert log.mean_loss == 2.0
    weighted = EpochLog([1.0, 3.0], 2, weights=[3, 1])
    assert weighted.mean_loss == pytest.approx(1.5)
    assert EpochLog([], 0).mean_loss != EpochLog([], 0).mean_loss  # nan


def test_epochlog_stats_identical_across_engines(tiny_setup):
    clients, adapter = tiny_setup
    _, _, la = _run("fl", "stepwise", clients, adapter,
                    drop_remainder=False)
    _, _, lb = _run("fl", "compiled", clients, adapter,
                    drop_remainder=False)
    assert la.weights == lb.weights
    assert la.client_steps == lb.client_steps == [5, 3, 3]
    assert abs(la.mean_loss - lb.mean_loss) < 1e-6


# ---------------------------------------------------------------------------
# engine guards
# ---------------------------------------------------------------------------

def test_engine_guards(tiny_setup):
    clients, adapter = tiny_setup
    with pytest.raises(ValueError):
        make_strategy("fl", adapter, lambda: O.adam(1e-3), 3,
                      engine="warp")
    # cut-layer noise draws are per-example (batch-length independent), so
    # both privacy modes accept kept remainder batches
    make_strategy("sl_ac", adapter, lambda: O.adam(1e-3), 3,
                  privacy=CUT, engine="compiled", drop_remainder=False)
    make_strategy("fl", adapter, lambda: O.adam(1e-3), 3, privacy=DP,
                  engine="compiled", drop_remainder=False)
    with pytest.raises(ValueError):                 # batch-synchronous v3
        make_strategy("sflv3_ac", adapter, lambda: O.adam(1e-3), 3,
                      drop_remainder=False)


# ---------------------------------------------------------------------------
# batched eval (satellite): one dispatch for every hospital
# ---------------------------------------------------------------------------

def test_scores_all_matches_per_hospital(tiny_setup):
    clients, adapter = tiny_setup
    st, state, _ = _run("sl_ac", "compiled", clients, adapter)
    datas = [c.test for c in clients]
    batched = st.scores_all(state, datas, batch_size=4)
    for i, d in enumerate(datas):
        assert len(batched[i]) == len(d["label"]) == 7   # partial kept
        single = st.scores(state, i, d, batch_size=4)
        np.testing.assert_allclose(batched[i], single, atol=1e-6)
    m = st.evaluate(state, clients, "test", batch_size=4)
    assert 0.0 <= m["auroc"] <= 1.0


@pytest.mark.parametrize("drop_remainder", [True, False])
def test_transport_accounting_compiled_matches_stepwise(drop_remainder,
                                                        tiny_setup):
    """Byte accounting is engine-independent — including kept remainder
    batches, which the compiled path must meter at their TRUE short shape
    rather than the padded full-batch shape."""
    from repro.wire import Transport
    clients, adapter = tiny_setup
    byt = {}
    for engine in ("stepwise", "compiled"):
        tp = Transport("identity")
        st = make_strategy("sl_am", adapter, lambda: O.adam(1e-3),
                           len(clients), transport=tp, engine=engine,
                           drop_remainder=drop_remainder)
        state = st.setup(jax.random.key(0))
        st.run_epoch(state, [c.train for c in clients],
                     np.random.default_rng(0), 4)
        byt[engine] = (tp.steps, tp.bytes_on_wire)
    assert byt["stepwise"] == byt["compiled"]
