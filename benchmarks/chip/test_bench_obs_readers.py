"""The readers of the program's own measurement (``repro.obs``): pack
counters, compile log and segment scopes, each on a record built around
a real span log, and each silent when its input is absent."""

import time

import jax
import numpy as np
import pytest

from chip import harness


def reader(name):
    return harness.Library().module("metrics", name).read


def rec(span, images=100, ops=None):
    return {"span": span, "images": images, "host_spans": [],
            "trace": {"busy_s": 1.0, "window_s": 2.0, "kernels": {},
                      "ops": ops or {}}}


def test_pack_bytes_per_image_sums_the_window_pack_counters():
    from repro.obs.trace import Tracer
    tr = Tracer()
    with tr.span("pack") as sp:              # before the window
        sp.set(bytes_image=10**6)
    t0 = time.perf_counter()
    for _ in range(2):
        with tr.span("run"):
            with tr.span("pack") as sp:
                sp.set(bytes_image=4000, bytes_label=40, bytes_mask=4000,
                       batch_slots=5, real_batches=3)
    t1 = time.perf_counter()
    assert reader("pack_bytes_per_image.train")(rec((t0, t1), images=20)) \
        == pytest.approx(2 * 8040 / 20)


def test_pack_bytes_per_image_is_silent_without_counters():
    from repro.obs.trace import Tracer
    read = reader("pack_bytes_per_image.train")
    t0 = time.perf_counter()
    with Tracer().span("pack"):
        pass
    t1 = time.perf_counter()
    assert read(rec((t0, t1))) is None
    assert read(rec((t1 + 10.0, t1 + 20.0))) is None


def bench_window_program(x):
    return jax.numpy.sin(x) * 2.0


def test_trace_lower_s_reads_the_set_up_compiles_of_the_window_program():
    from repro.obs import compile_log
    from repro.obs.trace import Tracer
    t_setup = time.perf_counter()
    jax.jit(bench_window_program)(np.float32(1.0))
    logged = [e for e in compile_log.entries(since=t_setup)
              if e.fun_name == "bench_window_program"]
    assert {e.event for e in logged} >= {"trace", "lower", "backend"}
    t0 = time.perf_counter()
    with Tracer().span("enqueue") as sp:
        sp.set(program="bench_window_program")
    t1 = time.perf_counter()
    want = sum(e.duration for e in logged if e.event in ("trace", "lower"))
    assert reader("trace_lower_s.setup")(rec((t0, t1))) == pytest.approx(
        want)
    # a window that names no program, or one never compiled, reads nothing
    assert reader("trace_lower_s.setup")(rec((t1 + 1, t1 + 2))) is None
    with Tracer().span("enqueue") as sp:
        sp.set(program="never_compiled")
    assert reader("trace_lower_s.setup")(
        rec((t1, time.perf_counter()))) is None


@pytest.fixture(scope="module")
def scoped_window():
    """A tiny traced SFLv3 run and the compiled program's scopes."""
    from repro import optim as O
    from repro.core.partition import cnn_adapter
    from repro.core.strategies import make_strategy
    from repro.data.synthetic import make_cxr_clients
    from repro.models.cnn import DenseNetConfig, build_densenet
    from repro.obs import scopes
    from repro.obs.trace import Tracer
    clients = make_cxr_clients(seed=0, train_per_client=[12, 8],
                               val_per_client=4, test_per_client=4,
                               image_size=16, n_clients=2)
    adapter = cnn_adapter(build_densenet(DenseNetConfig(
        growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)))
    st = make_strategy("sflv3_ac", adapter, lambda: O.adam(1e-3), 2)
    st.attach_tracer(Tracer())
    state = st.setup(jax.random.key(0))
    t0 = time.perf_counter()
    st.run(state, [c.train for c in clients], np.random.default_rng(0), 4,
           1)
    t1 = time.perf_counter()
    fn, args = st._last_run_invocation
    return (t0, t1), scopes.op_scopes(fn.lower(*args).compile().as_text())


@pytest.mark.parametrize("seg", ["front", "middle"])
def test_segment_us_per_image_sums_the_scoped_ops(scoped_window, seg):
    span, op_scope = scoped_window
    ops = {name: 1e-6 for name in op_scope}
    ops["outside.1"] = 5.0                   # an op of no scope
    n = sum(1 for s in op_scope.values() if s == seg)
    assert n > 0
    got = reader(f"{seg}_us_per_image.train")(rec(span, images=10, ops=ops))
    assert got == pytest.approx(n * 1e-6 * 1e6 / 10)


@pytest.mark.parametrize("seg", ["front", "middle"])
def test_segment_us_per_image_is_silent_without_scopes(scoped_window, seg):
    span, _ = scoped_window
    read = reader(f"{seg}_us_per_image.train")
    assert read(rec(span, ops={"fusion.1": 1.0})) is None
    assert read(rec((span[1] + 10.0, span[1] + 20.0),
                    ops={"fusion.1": 1.0})) is None
