"""End-to-end behaviour tests for the distributed-learning system."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim as O
from repro.core.partition import cnn_adapter, lm_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import DenseNetConfig, build_densenet


@pytest.fixture(scope="module")
def tiny_setup():
    clients = make_cxr_clients(seed=0, train_per_client=32,
                               val_per_client=16, test_per_client=16,
                               image_size=16)
    cfg = DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)
    return clients, cfg


def _run(method, nls, clients, cfg, epochs=1):
    ad = cnn_adapter(build_densenet(cfg, nls=nls))
    st = make_strategy(method, ad, lambda: O.adam(1e-3), len(clients))
    state = st.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    for _ in range(epochs):
        state, log = st.run_epoch(state, [c.train for c in clients], rng, 8)
    return st, state, log


@pytest.mark.parametrize("method", ["centralized", "fl", "sl_ac", "sl_am",
                                    "sflv2_ac", "sflv3_ac", "sflv1_ac"])
@pytest.mark.parametrize("nls", [False, True])
def test_method_runs_and_evaluates(method, nls, tiny_setup):
    clients, cfg = tiny_setup
    if method in ("centralized", "fl") and nls:
        pytest.skip("nls split irrelevant for non-split methods")
    st, state, log = _run(method, nls, clients, cfg)
    assert np.isfinite(log.mean_loss)
    m = st.evaluate(state, clients, "test", batch_size=16)
    assert 0.0 <= m["auroc"] <= 1.0


def _client_trees(state, first_last=(0, -1)):
    """Client segment trees under either engine layout (stepwise keeps a
    list, the default compiled engine keeps the hospital axis stacked)."""
    if "stacked_clients" in state:
        from repro.core.partition import tree_take
        return [tree_take(state["stacked_clients"], i) for i in first_last]
    return [state["clients"][i] for i in first_last]


def test_sflv2_synchronizes_clients(tiny_setup):
    clients, cfg = tiny_setup
    st, state, _ = _run("sflv2_ac", False, clients, cfg)
    c0, c1 = _client_trees(state)
    for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sl_keeps_clients_unique(tiny_setup):
    clients, cfg = tiny_setup
    st, state, _ = _run("sl_ac", False, clients, cfg)
    c0, c1 = _client_trees(state)
    diffs = [float(jnp.abs(a - b).max())
             for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1))]
    assert max(diffs) > 0


def test_sflv3_keeps_clients_unique_and_one_server(tiny_setup):
    clients, cfg = tiny_setup
    st, state, _ = _run("sflv3_ac", False, clients, cfg)
    sc = state["stacked_clients"]
    leaf = jax.tree.leaves(sc)[0]
    assert leaf.shape[0] == len(clients)
    # at least one client pair differs
    assert any(float(jnp.abs(l[0] - l[-1]).max()) > 0
               for l in jax.tree.leaves(sc))


def test_sflv1_averages_clients(tiny_setup):
    clients, cfg = tiny_setup
    st, state, _ = _run("sflv1_ac", False, clients, cfg)
    for l in jax.tree.leaves(state["stacked_clients"]):
        np.testing.assert_allclose(np.asarray(l[0]), np.asarray(l[-1]),
                                   rtol=1e-6, atol=1e-7)


def test_fedavg_single_client_identity(tiny_setup):
    """FedAvg with one client == that client's local training."""
    clients, cfg = tiny_setup
    ad = cnn_adapter(build_densenet(cfg))
    st = make_strategy("fl", ad, lambda: O.adam(1e-3), 1)
    state = st.setup(jax.random.key(0))
    # a copy: the epoch's program donates the state's buffers
    p0 = jax.tree.map(jnp.copy, state["params"])
    state, _ = st.run_epoch(state, [clients[0].train],
                            np.random.default_rng(0), 8)

    from repro.core.strategies.base import make_full_step, np_batches
    opt = O.adam(1e-3)
    step = make_full_step(ad, opt)
    p, s = p0, opt.init(p0)
    for b in np_batches(clients[0].train, 8, np.random.default_rng(0)):
        p, s, _ = step(p, s, b)
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_split_equals_full_forward(tiny_setup):
    """front o middle (o tail) must equal the unsplit forward."""
    clients, cfg = tiny_setup
    for nls in (False, True):
        ad = cnn_adapter(build_densenet(cfg, nls=nls))
        params = ad.init(jax.random.key(1))
        batch = {k: v[:4] for k, v in clients[0].train.items()}
        x = ad.inputs(batch)
        for seg in ad.seg_names:
            x = ad.apply_seg(seg, params[seg], x, batch, False)
        full = ad.full_scores(params, batch)
        np.testing.assert_allclose(
            np.asarray(jax.nn.sigmoid(x.reshape(-1).astype(jnp.float32))),
            np.asarray(full), rtol=1e-6)


def test_lm_split_equals_full_forward():
    from repro.models.transformer import ModelConfig, TransformerLM
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                      cut_layer=2, remat=False,
                      compute_dtype=jnp.float32)
    for nls in (False, True):
        model = TransformerLM.build(cfg, nls=nls)
        ad = lm_adapter(model)
        params = ad.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 12), 0, 97)
        batch = {"tokens": toks}
        x = ad.inputs(batch)
        for seg in ad.seg_names:
            x = ad.apply_seg(seg, params[seg], x, batch, False)
        direct, _, _ = model.apply(params, toks[:, :-1])
        np.testing.assert_allclose(np.asarray(x), np.asarray(direct),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ksize", [1, 3, 5])
def test_depthwise_conv_matches_the_grouped_convolution(ksize):
    """``layers.depthwise_conv`` (shifted multiply-adds, hand-written
    backward pass) against XLA's grouped convolution at HIGHEST, values
    and both gradients.  rtol 1e-5: float32 sums of at most 25 products
    per output (and of 2*9*7 per kernel tap) in another order."""
    from repro.models import layers as L
    kx, kw, kg = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (2, 9, 7, 5), jnp.float32)
    w = jax.random.normal(kw, (ksize, ksize, 1, 5), jnp.float32)
    g = jax.random.normal(kg, (2, 9, 7, 5), jnp.float32)

    def grouped(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1],
            precision=jax.lax.Precision.HIGHEST)

    want, want_vjp = jax.vjp(grouped, x, w)
    got, got_vjp = jax.vjp(L.depthwise_conv, x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
