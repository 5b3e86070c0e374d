"""The U-Net split-learning cell (``unet768.sl_am_int8``) on the CPU at a
tiny size: its own tiny twin, whose cut still carries the skips (widths
8-16-24, the cut after the encoder: the bottleneck and two skips cross
it, on 32x32 images), runs past the device gate and is correct; its two
faults and the ``high3`` control are refused; and the operation and cut
counts at full width and 768x768 from shapes.

The twin is held to the cell's own limits (``limits/unet768.sl_am_int8
.json``) at every seed it reads.  Readings at the tiny size on the CPU
(seeds 2**31+3, 2**31+7, 11, 12, 3000000001, 3000000011, 7, 8):
``cut_gap``, program 4.12e-07 to 6.54e-07, ``high3`` 1.39e-05 to
2.46e-05; ``first_loss_gap``, program at most 7.75e-07, ``high3`` over
the limit at three seeds of eight; ``change_gap``, program at most
0.0337, ``high3`` 0.0227 to 0.082, half batch at least 0.224, a state
left unchanged 1.  ``cut_gap`` is what refuses ``high3`` at every seed.
Its files live in a temporary directory that the harness searches after
this one.
"""

import json

import pytest

from chip import compare, flops, harness
from chip.conftest import CPU, HERE
from chip.test_bench_cells import _half_batch

CELL = "unet768.sl_am_int8"
TINY = f"tiny_{CELL}"
SEED = 2**31 + 7
TINY_MODEL = {"widths": [8, 16, 24], "in_ch": 1, "n_classes": 1,
              "cut_layer": 4, "remat": True}
TINY_SIZE = 32
TINY_MIX = {"train_per_client": [12, 8, 4], "batch": 4}
LIMITS = json.loads((HERE / "limits" / f"{CELL}.json").read_text())
CONTROL_SEEDS = [2**31 + 3, 2**31 + 7, 11, 12, 3000000001]


@pytest.fixture(scope="module")
def tiny_unet(tmp_path_factory):
    """``(bench, library)`` with ``tiny_unet768.sl_am_int8`` beside the
    benchmark's cells."""
    d = tmp_path_factory.mktemp("tiny_unet_lib")
    for sub in ("configs", "mixes", "limits"):
        (d / sub).mkdir()
    cfg = json.loads((HERE / "configs" / "unet768.json").read_text())
    cfg.update(model=TINY_MODEL, image_size=TINY_SIZE)
    (d / "configs" / "tiny_unet768.json").write_text(json.dumps(cfg))
    mix = json.loads((HERE / "mixes" / "sl_am_int8.json").read_text())
    mix.update(TINY_MIX)
    (d / "mixes" / "tiny_sl_am_int8.json").write_text(json.dumps(mix))
    (d / "limits" / f"{TINY}.json").write_text(json.dumps(LIMITS))
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": TINY, "config": "tiny_unet768",
                               "traffic": "tiny_sl_am_int8", "chips": 1})
    for m in bench["end_to_end"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY)
    return bench, harness.Library([HERE, d])


def run(tiny_unet, seed=SEED):
    bench, lib = tiny_unet
    return harness.run_cell(bench, TINY, seed, 0.5, False, lib=lib,
                            device=dict(CPU))


@pytest.mark.parametrize("seed", [SEED, 3000000001])
def test_cell_runs_and_is_correct(tiny_unet, seed):
    out = run(tiny_unet, seed)
    assert out["correct"] is True, out["compared"]
    assert list(out["compared"]) == ["first_loss_gap", "cut_gap",
                                     "change_gap", "run_loss_gap",
                                     "moment_gap"]
    assert {k: v["limit"] for k, v in out["compared"].items()
            if v["limit"] is not None} == LIMITS
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_images_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def _keep_state(monkeypatch):
    """The split-learning whole-run program returns the state it was
    given."""
    import jax
    import jax.numpy as jnp
    from repro.core.strategies import engine
    make = engine.make_interleaved_run

    def made(*a, **k):
        fn = make(*a, **k)

        def interleaved_run(*args):
            kept = jax.tree.map(jnp.copy, args[:4])
            return (*kept, *fn(*args)[4:])
        return interleaved_run

    monkeypatch.setattr(engine, "make_interleaved_run", made)


@pytest.mark.parametrize("fault", [_keep_state, _half_batch],
                         ids=["keep_state", "half_batch"])
def test_a_broken_timed_path_is_not_correct(tiny_unet, fault, monkeypatch):
    fault(monkeypatch)
    out = run(tiny_unet)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
def test_control_is_not_correct(tiny_unet, seed):
    """The reference in ``high3`` put in the program's place fails the
    cell's limits, where the reference against itself passes them."""
    import jax
    import numpy as np
    from chip import gen, weights
    bench, lib = tiny_unet
    cell = {w["name"]: w for w in bench["workloads"]}[TINY]
    ctx = harness.Context(bench, cell, lib, seed, 0.5, False)
    assert ctx.limits == LIMITS
    driver = lib.module("drivers", ctx.mix["driver"])
    model = ctx.cfg["model"]
    data = [d["train"] for d in gen.cxr_clients(
        ctx.seed, ctx.mix["train_per_client"], ctx.cfg["image_size"])]
    fronts, server = weights.make(ctx.family, model, ctx.seed, len(data))
    init = {"fronts": jax.tree.map(np.asarray, fronts),
            "server": jax.tree.map(np.asarray, server)}
    runs = {p: driver.reference_run(ctx.family, model, ctx.mix, data, init,
                                    ctx.seed, p)
            for p in ("highest", "high3")}
    sound = driver.numbers(runs["highest"], runs["highest"], init)
    assert compare.verdict(sound, ctx.limits)[0] is True
    numbers = driver.numbers(runs["high3"], runs["highest"], init)
    ok, lines = compare.verdict(numbers, ctx.limits)
    assert ok is False, lines


def test_the_schedule_takes_turns_by_mini_batch():
    from chip.ref_split import schedule
    assert schedule([3, 1, 2]) == [(0, 0), (1, 0), (2, 0), (0, 1), (2, 1),
                                   (0, 2)]
    assert len(schedule([12, 8, 5, 4, 3])) == 32


def test_forward_flops_and_cut_elements_at_full_width():
    lib = harness.Library()
    cfg = lib.json("configs", "unet768")
    fam = lib.module("families", cfg["family"])
    model, size = cfg["model"], cfg["image_size"]
    assert (model["widths"], size, cfg["reduced"]) == (
        [64, 128, 256, 512, 728], 768, [])
    assert flops.model_flops_per_image(fam, model, size) == 112_082_153_472
    # the 48x48x728 bottleneck and the skips 768^2 x 64 ... 96^2 x 512
    assert flops.cut_elements(fam, model, size) == 72_456_192 == (
        48 * 48 * 728 + sum((768 >> i) ** 2 * w
                            for i, w in enumerate([64, 128, 256, 512])))


def test_reference_params_are_the_programs():
    """Names and shapes of the reference's parameters are the program's,
    at full width (shapes only)."""
    import jax
    from repro.core.partition import cnn_adapter
    from repro.models.cnn import UNetConfig, build_unet
    cfg = harness.Library().json("configs", "unet768")
    fam = harness.Library().module("families", "unet")
    model = cfg["model"]
    prog = cnn_adapter(build_unet(UNetConfig(
        **{k: tuple(v) if isinstance(v, list) else v
           for k, v in model.items()})))

    def shapes(f):
        return jax.tree.map(lambda a: a.shape,
                            jax.eval_shape(f, jax.random.key(0)))

    assert shapes(prog.init) == shapes(lambda k: fam.init(k, model))


def test_wire_bytes_reader_reads_the_account_counters():
    """``wire_bytes_per_image.train`` is the window's ``account`` counters
    over its images, and silent where the runs set no such counter (a
    program without them, or a run without a transport)."""
    import time

    import jax
    import numpy as np
    from repro import optim as O
    from repro.core.partition import cnn_adapter
    from repro.core.strategies import make_strategy
    from repro.models.cnn import UNetConfig, build_unet
    from repro.obs.trace import Tracer
    from repro.wire import Transport
    read = harness.Library().module("metrics",
                                    "wire_bytes_per_image.train").read
    adapter = cnn_adapter(build_unet(UNetConfig(widths=(4, 8), cut_layer=3)))
    rng = np.random.default_rng(0)
    data = [{"image": rng.normal(size=(n, 8, 8, 1)).astype(np.float32),
             "label": (rng.uniform(size=n) > 0.5).astype(np.float32)}
            for n in (8, 4)]
    for transport in (Transport("int8"), None):
        st = make_strategy("sl_am", adapter, lambda: O.adam(1e-3), 2,
                           transport=transport)
        st.attach_tracer(Tracer())
        state = st.setup(jax.random.key(0))
        t0 = time.perf_counter()
        state, logs = st.run(state, data, rng, 4, 2)
        t1 = time.perf_counter()
        images = sum(sum(lg.client_steps) for lg in logs) * 4
        got = read({"span": (t0, t1), "images": images})
        if transport is None:
            assert got is None
        else:
            assert got == transport.bytes_on_wire / images > 0


def test_remat_reader_is_silent_without_the_scope(monkeypatch):
    from repro.obs import scopes
    read = harness.Library().module("metrics",
                                    "remat_us_per_image.train").read
    rec = {"trace": {"ops": {}}, "span": (0.0, 1.0), "images": 4}
    for seconds, want in (({"front": 1.0, None: 0.5}, None),
                          ({"front": 1.0, "remat": 0.0}, None),
                          ({"front": 1.0, "remat": 2.0}, 5e5), (None, None)):
        monkeypatch.setattr(scopes, "traced_scope_seconds",
                            lambda ops, t0, t1, s=seconds: s)
        assert read(rec) == want
