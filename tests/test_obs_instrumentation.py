"""The run's own measurement (repro.obs): named scopes in the compiled
programs, the spans and counters of one run, their profiler annotations,
and the compile log.

  * every convolution and dot of a compiled SFLv3, FL and SL run carries
    a segment, ``cut`` or ``update`` scope, backward included, and the
    scopes leave params and losses bit-identical;
  * the spans of one compiled run tile it: pack, enqueue, wait, account;
  * the ``pack`` span's byte counters are the ``nbytes`` of what the run
    hands the program: the packed grid, or for an unsharded split-family
    run the hospitals' arrays and the index grid the program gathers
    from;
  * the ``account`` span's wire counters are the transport's metering of
    the run, and what ``jax.checkpoint`` recomputes is scope ``remat``;
  * a profiler trace holds the spans as prefixed annotations with stats;
  * the compile log names each compile by function, and logs nothing
    when a compiled program runs again at the same shapes.
"""

import contextlib
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim as O
from repro.core.participation import Participation
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import (DenseNetConfig, UNetConfig, build_densenet,
                              build_unet)
from repro.obs import compile_log, scopes
from repro.obs.trace import ANNOTATION_PREFIX, Tracer, recent_spans
from repro.wire import Transport

N = 3
BATCH = 4
EPOCHS = 2
RUN_PHASES = ["pack", "enqueue", "wait", "account"]


@pytest.fixture(scope="module")
def tiny():
    clients = make_cxr_clients(seed=0, train_per_client=[17, 12, 9],
                               val_per_client=6, test_per_client=7,
                               image_size=16, n_clients=N)
    cfg = DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=1)
    return [c.train for c in clients], cnn_adapter(build_densenet(cfg))


def _train(tiny, method, tracer=None, runs=1, **kw):
    data, adapter = tiny
    split = method.startswith(("sl", "sfl"))
    st = make_strategy(method, adapter, lambda: O.adam(1e-3), N,
                       transport=Transport("int8") if split else None, **kw)
    if tracer is not None:
        st.attach_tracer(tracer)
    state = st.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    logs = []
    for _ in range(runs):
        state, run_logs = st.run(state, data, rng, BATCH, EPOCHS)
        logs += run_logs
    return st, state, logs


def _compiled_text(st) -> str:
    fn, args = st._last_run_invocation
    return fn.lower(*args).compile().as_text()


_CONV_DOT = re.compile(r"=\s+\S+\s+(convolution|dot)\(.*?"
                       r'op_name="([^"]*)"')


@pytest.mark.parametrize("method", ["sflv3_ac", "fl", "sl_am"])
def test_every_conv_and_dot_carries_a_scope(tiny, method):
    st, _, _ = _train(tiny, method)
    found = [m.groups() for m in map(_CONV_DOT.search,
                                     _compiled_text(st).splitlines()) if m]
    assert found
    unscoped = [name for _, name in found if scopes.scope_of(name) is None]
    assert unscoped == []
    # the backward pass counts to its segment
    backward = {scopes.scope_of(name) for _, name in found
                if "transpose(" in name}
    assert {"front", "middle"} <= backward


@pytest.mark.parametrize("method", ["sflv3_ac", "fl", "sl_am"])
def test_scopes_leave_the_run_bit_identical(tiny, method, monkeypatch):
    st, state, logs = _train(tiny, method)
    assert set(scopes.op_scopes(_compiled_text(st)).values()) >= {
        "front", "middle", "update"}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    st0, state0, logs0 = _train(tiny, method)
    assert scopes.op_scopes(_compiled_text(st0)) == {}
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [lg.losses for lg in logs] == [lg.losses for lg in logs0]


TILED = [("fl", None), ("centralized", None), ("sl_am", None),
         ("sflv2_ac", None), ("sflv3_ac", None), ("sflv1_ac", None),
         ("fl", 2), ("sl_am", 2), ("sflv3_ac", 2)]


@pytest.mark.parametrize("method,k", TILED,
                         ids=[f"{m}-k{k}" if k else m for m, k in TILED])
def test_run_spans_tile_the_run(tiny, method, k):
    tr = Tracer()
    part = None if k is None else Participation(n_global=N, k=k)
    _train(tiny, method, tracer=tr, runs=2, participation=part)
    runs = [e for e in tr.events if e["name"] == "run"]
    assert [e["args"]["run"] for e in runs] == [1, 2]
    for run in runs:
        kids = sorted((e for e in tr.events
                       if e["args"].get("parent") == "run"
                       and e["args"].get("run") == run["args"]["run"]),
                      key=lambda e: e["ts"])
        assert [e["name"] for e in kids] == RUN_PHASES
        end = run["ts"]
        for e in kids:                      # in order, none overlapping
            assert e["ts"] >= end - 1.0
            end = e["ts"] + e["dur"]
        assert end <= run["ts"] + run["dur"] + 1.0
        uncovered = run["dur"] - sum(e["dur"] for e in kids)
        assert uncovered < 0.05 * run["dur"] + 2000.0      # microseconds
        assert run["args"]["images"] > 0
    gathers = [e for e in tr.events if e["name"] in ("gather", "stack")]
    assert gathers and all(e["args"]["parent"] == "pack" for e in gathers)


PACKS = [("sflv3_ac", None), ("sflv2_ac", None), ("sl_am", None),
         ("fl", None), ("sl_am", 2)]


@pytest.mark.parametrize("method,k", PACKS,
                         ids=[f"{m}-k{k}" if k else m for m, k in PACKS])
def test_pack_counters_are_the_packed_bytes(tiny, method, k, monkeypatch):
    """An unsharded split-family run (SFLv3, SFLv2, SL) hands the program
    the hospitals' arrays and an index grid (``pack_run_index``) and
    gathers every batch slot on the device; a run that still packs (FL's
    ``pack_run``, a participating run's slot grid) counts the grid the
    host packed and gathers none."""
    from repro.core.strategies import engine
    pack_run = engine.pack_run
    seen = {}

    def spy(name):
        fn = getattr(engine, name)

        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            seen.setdefault(name, out)
            return out
        return wrapped

    for name in ("pack_run", "pack_run_index", "pack_participation_run"):
        monkeypatch.setattr(engine, name, spy(name))
    tr = Tracer()
    part = None if k is None else Participation(n_global=N, k=k)
    _train(tiny, method, tracer=tr, participation=part)
    pack = tr.find("pack")["args"]
    enqueue = tr.find("enqueue")["args"]
    children = [e["name"] for e in tr.events
                if e["args"].get("parent") == "pack"]
    if method != "fl" and k is None:
        assert set(seen) == {"pack_run_index"}
        data, idx, packed = seen["pack_run_index"]
        assert set(data) == {"image", "label", "mask"}
        assert {key: pack[f"bytes_{key}"] for key in data} == {
            key: sum(a.nbytes for a in v) for key, v in data.items()}
        assert pack["bytes_index"] == idx.nbytes
        assert children == ["gather"]                    # nothing stacked
        e, c, nb = idx.shape[:3]
        assert pack["device_gather"] == pack["batch_slots"] == e * c * nb
        # the grid the host packed for the same run before
        old_grid = sum(a.nbytes for a in pack_run(
            tiny[0], BATCH, np.random.default_rng(0), EPOCHS)[0].values())
        assert enqueue["program"] == ("sflv3_run" if method == "sflv3_ac"
                                      else "interleaved_run")
        assert enqueue["bytes_host"] >= (sum(pack[f"bytes_{key}"] for key in data)
                                         + idx.nbytes)
        assert enqueue["bytes_host"] < old_grid
        # SFLv3 trains every hospital's full batch at every step
        per_epoch = (nb * N if method == "sflv3_ac"
                     else sum(packed.n_batches))
        assert tr.find("run")["args"]["images"] == EPOCHS * per_epoch * BATCH
        assert pack["real_batches"] == e * sum(packed.n_batches) < e * c * nb
    else:
        name = "pack_run" if k is None else "pack_participation_run"
        assert set(seen) == {name}
        batches, packed = seen[name]
        assert {key: pack[f"bytes_{key}"] for key in batches} == {
            key: v.nbytes for key, v in batches.items()}
        assert set(batches) == {"image", "label", "mask"}
        e, c, nb = batches["label"].shape[:3]
        assert pack["batch_slots"] == e * c * nb
        assert pack["device_gather"] == 0 and "bytes_index" not in pack
        assert children == (["gather"] * EPOCHS + ["stack"] if k is None
                            else ["gather"])
        assert enqueue["program"] == ("fl_run" if k is None
                                      else "interleaved_run_participation")
        assert enqueue["bytes_host"] >= sum(v.nbytes
                                            for v in batches.values())
        assert pack["real_batches"] == packed.mask.sum() * (
            e if k is None else 1) < e * c * nb
    assert enqueue["bytes_in"] > enqueue["bytes_host"]


def _tiny_unet(remat=True, widths=(4, 8, 8, 16, 16)):
    """A U-Net whose cut carries the bottleneck and every skip."""
    return cnn_adapter(build_unet(UNetConfig(
        widths=widths, cut_layer=len(widths) + 1, remat=remat)))


WIRE = [(m, a) for m in ("sl_am", "sflv3_ac") for a in ("unet", "densenet")]


@pytest.mark.parametrize("method,arch", WIRE,
                         ids=[f"{m}-{a}" for m, a in WIRE])
def test_account_counters_are_the_runs_wire_traffic(tiny, method, arch):
    """Each run's ``account`` span carries the bytes the transport metered
    for that run, on the wire and raw, and the arrays crossing the cut:
    the U-Net's bottleneck and four skips, the DenseNet's one array."""
    data, adapter = tiny
    if arch == "unet":
        adapter = _tiny_unet()
    tr = Tracer()
    st, _, _ = _train((data, adapter), method, tracer=tr, runs=2)
    accounts = [e["args"] for e in tr.events if e["name"] == "account"]
    assert len(accounts) == 2
    t = st.transport
    assert sum(a["wire_bytes"] for a in accounts) == t.bytes_on_wire > 0
    assert sum(a["wire_bytes_raw"] for a in accounts) == t.bytes_raw
    assert accounts[0]["wire_bytes"] == accounts[1]["wire_bytes"]
    assert accounts[0]["wire_bytes_raw"] > accounts[0]["wire_bytes"]
    assert {a["cut_arrays"] for a in accounts} == {
        5 if arch == "unet" else 1}
    # a run without a transport sets none
    st0 = make_strategy(method, adapter, lambda: O.adam(1e-3), N)
    tr0 = st0.attach_tracer(Tracer())
    st0.run(st0.setup(jax.random.key(0)), data, np.random.default_rng(0),
            BATCH, 1)
    assert "wire_bytes" not in tr0.find("account")["args"]


def test_remat_matches_the_plain_model_and_names_its_recomputation(tiny):
    """A compiled SL run of the U-Net with its blocks checkpointed (one
    image at a time) against the plain model: losses and params within
    float32 round-off, and operations in the ``remat`` scope only with
    checkpointing on.  SGD, no int8 link and three levels (a 4x4
    bottleneck), so that the gradients' round-off stays round-off: Adam
    moves a coordinate whose gradient is round-off by about its learning
    rate, the link can round an activation to the next int8 step, and
    group norm over a 1x1 bottleneck divides round-off by round-off.
    Tolerance 1e-5 relative: the batch's gradient is summed image by
    image, in another order."""
    data, _ = tiny
    runs = {}
    for remat in (True, False):
        st = make_strategy("sl_am", _tiny_unet(remat, (4, 8, 8)),
                           lambda: O.sgd(0.01), N)
        state, logs = st.run(st.setup(jax.random.key(0)), data,
                             np.random.default_rng(0), BATCH, EPOCHS)
        named = scopes.op_scopes(_compiled_text(st))
        runs[remat] = (state, [x for lg in logs for x in lg.losses],
                       sum(s == "remat" for s in named.values()))
    assert runs[True][2] > 0 and runs[False][2] == 0
    np.testing.assert_allclose(runs[True][1], runs[False][1], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(runs[True][0]),
                    jax.tree.leaves(runs[False][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_profiler_trace_holds_the_annotated_spans(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("run"):
            with tr.span("pack") as sp:
                jax.block_until_ready(jnp.ones((4,)) * 2)
                sp.set(bytes_image=123)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {e.name: dict(e.stats)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith(ANNOTATION_PREFIX)}
    assert events[ANNOTATION_PREFIX + "run"]["run"] == 1
    pack = events[ANNOTATION_PREFIX + "pack"]
    assert pack["bytes_image"] == 123 and pack["parent"] == "run"
    assert pack["run"] == 1


def test_compile_log_names_each_compile_once(tiny):
    data, adapter = tiny
    st = make_strategy("sflv3_ac", adapter, lambda: O.adam(1e-3), N,
                       transport=Transport("int8"))
    state = st.setup(jax.random.key(0))
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    state, _ = st.run(state, data, rng, BATCH, EPOCHS)
    first = compile_log.entries(since=t0)
    names = {(e.event, e.fun_name) for e in first}
    assert {("trace", "sflv3_run"), ("lower", "sflv3_run"),
            ("backend", "sflv3_run")} <= names
    run = [e for e in first if e.fun_name == "sflv3_run"]
    assert [e.event for e in run] == ["trace", "lower", "backend"]
    assert all(e.duration > 0 and e.end > e.start >= t0 for e in run)
    # the jitted jax.numpy helpers traced inside the program are not logged
    assert not {"add", "multiply", "less"} & {e.fun_name for e in first}
    t1 = time.perf_counter()
    st.run(state, data, rng, BATCH, EPOCHS)
    assert compile_log.entries(since=t1) == []


def test_compiles_land_in_the_attached_tracer_only(tiny):
    tr = Tracer()
    st, _, _ = _train(tiny, "fl", tracer=tr)
    compiles = [e for e in tr.events
                if e["name"].startswith("compile.")
                and e["args"]["fun_name"] == "fl_run"]
    assert [e["name"] for e in compiles] == [
        "compile.trace", "compile.lower", "compile.backend"]
    assert all(e["args"]["parent"] == "enqueue" for e in compiles)
    st.attach_tracer(None)
    n = len(tr.events)
    jax.jit(lambda x: x + 3.0)(1.0)
    assert len(tr.events) == n


def test_scope_of_strips_autodiff_and_vmap_wrappers():
    assert scopes.scope_of(
        "jit(sflv3_run)/while/body/transpose(jvp(vmap(front)))/conv") \
        == "front"
    assert scopes.scope_of("jit(f)/jvp(vmap(cut))/jit(fused)/mul") == "cut"
    assert scopes.scope_of("jit(f)/update/middle/add") == "middle"
    assert scopes.scope_of("jit(f)/while/body/add") is None
    # a checkpoint's recomputation is remat, whatever segment holds it
    assert scopes.scope_of("jit(f)/transpose(jvp(middle))/jvp(middle)/"
                           "checkpoint/rematted_computation/conv") == "remat"
    assert scopes.scope_of("jit(f)/jvp(middle)/checkpoint/conv") == "middle"
    text = "\n".join([
        '  %fusion.7 = f32[2]{0} fusion(%a), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(r)/transpose(jvp(middle))/mul"}',
        '  ROOT %copy.1 = f32[2]{0} copy(%b), '
        'metadata={op_name="jit(r)/update/add" source_file="x.py"}',
        '  %add.3 = f32[2]{0} add(%a, %b), metadata={op_name="jit(r)/add"}',
        '  %param.0 = f32[2]{0} parameter(0)'])
    assert scopes.op_scopes(text) == {"fusion.7": "middle",
                                      "copy.1": "update"}
    assert scopes.scope_seconds({"fusion.7": 2.0, "copy.1": 1.0,
                                 "add.3": 0.5}, scopes.op_scopes(text)) == {
        "front": 0.0, "middle": 2.0, "tail": 0.0, "cut": 0.0,
        "update": 1.0, "remat": 0.0, None: 0.5}


def test_span_log_keeps_finished_spans_on_the_host_clock():
    tr = Tracer()
    t0 = time.perf_counter()
    with tr.span("run"):
        with tr.span("pack") as sp:
            sp.set(bytes_label=8)
    t1 = time.perf_counter()
    got = recent_spans(t0, t1)
    assert [s.name for s in got] == ["pack", "run"]
    assert got[0].args == {"bytes_label": 8} and got[0].parent == "run"
    assert t0 <= got[1].start <= got[0].start <= got[0].end <= got[1].end
    assert recent_spans(t1 + 1.0) == []
