#!/usr/bin/env python3
"""Bring-up smoke: the paper's training and screening paths on a TPU.

Drives DenseNet-121 at the paper's width (``DENSENET121_PAPER``: 224x224,
first 4 units at the client) through the entry points a user calls:
``make_strategy(...).run(...)`` for the paper's three techniques, then
``Strategy.export`` behind a ``ScreeningService``.  Weights are random
(seeded) and the data is the synthetic five-hospital chest-X-ray task.

  python chip_smoke.py                # one chip: train, references, serve
  python chip_smoke.py --four-chips   # shard=True vs shard=False, 4 chips

Phases (one process, no child processes; every failed check raises):

* train   — ``fl`` with DP-SGD (the ``dp_clip`` kernel), ``sl_am``, and
            ``sflv3_ac`` with the int8 transport (the ``cut_fuse``
            kernel), 2 epochs each as ONE dispatch per ``run``; prints
            compile seconds, steps, first/last loss, dispatches and the
            process's running peak of device bytes.
* refs    — ``cut_fuse`` and ``dp_clip`` against their ``ref.py`` at the
            DenseNet cut shape; the compiled ``sflv3_ac`` epoch 0 against
            ``engine="stepwise"``; ``tpu_custom_call`` in the lowered text
            of the whole-run programs.
* serve   — hospital 0's ``sflv3_ac`` export behind a (1, 2, 4, 8) bucket
            ladder answering single-image requests with no fresh compile.

``--four-chips`` runs only ``fl`` and ``sflv3_ac`` with ``shard=True``
against ``shard=False`` (5 hospitals pad to 8, two per chip), at
DenseNet-121's widths with its later dense blocks cut to one layer, and
``sflv3_ac`` on the identity link (the one-chip phases cover int8).

The last stdout line is ``{"ok": true, "device": {...}}``.  Without a TPU
(or with the Pallas kernels in interpret mode) the script says what it
found and exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
LR = 1e-3
METHODS = ("fl", "sl_am", "sflv3_ac")
PER_CLIENT = 16           # 2 batches of 8 per hospital per epoch
BATCH = 8
EPOCHS = 2
BUCKETS = (1, 2, 4, 8)
N_REQUESTS = 16
# the chip's default f32 matmul/conv precision differs from the CPU's, and
# the compiled and stepwise programs fuse differently: per-step losses of
# the same math agree to this absolute tolerance (BCE losses are ~0.7)
TOL_STEPWISE = 5e-3
TOL_SERVE = 1e-3          # |score| bucket program vs Strategy.scores
TOL_DP_REL = 1e-5         # dp_clip kernel vs ref, relative to max |ref|
TOL_QUANT_STEPS = 1.001   # int8 roundtrip: within one quantisation step
# shard=True vs shard=False is the same math in another program, so its
# losses get TOL_STEPWISE.  Its params are compared by the size of their
# difference relative to how far training moved them,
# ||θ_shard − θ_one|| / ||θ_one − θ_init||: Adam moves a coordinate by
# ~lr per update whatever its gradient's size, so an absolute bound would
# be near lr * updates for any pair of runs.  Rounding-level differences
# stay far below this bound (on the CPU a 1e-6 relative change of the init
# moves fl+DP params 2.1e-4 of their movement, sflv3_ac 7.9e-5), while a
# shard=True run that drops the cross-chip server-gradient sum lands near
# 1 (tests/test_chip_smoke.py plants that fault).  On a v5e, sflv3_ac
# read 0.11 at the default matmul/conv precision (one bf16 pass for f32
# operands) on the int8 link and on the identity link alike, and 3.4e-3
# at f32 ("highest") precision, so its parity runs at f32 precision on the
# identity link (the one-chip phases cover int8); fl reads 3.5e-3 at the
# default precision.
TOL_SHARD_REL = 1e-2
# Four chips cost four times as much per second, and the full-depth
# programs already compile and run in the one-chip phases.  The shard
# parity runs keep DenseNet-121's widths, input size and client segment
# (stem + 3 dense layers: the same cut shape and kernels) with one dense
# layer in each later block.
FOUR_CHIP_BLOCKS = (3, 1, 1, 1)
# The [C, ...] arguments of each whole-run program, by position, with the
# axis that carries the hospitals.  fl: batches, mask, example weights,
# key grid.  sflv3_ac: client segments, their Adam state, batches, batch
# index grid.
HOSPITAL_ARGS = {"fl": {1: 1, 2: 0, 3: 0, 4: 1},
                 "sflv3_ac": {0: 0, 2: 0, 4: 1, 5: 1}}
# matmul/conv precision of each method's shard parity runs (None: default)
SHARD_PRECISION = {"fl": None, "sflv3_ac": "highest"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one smoke run trains and serves.  ``model=None`` is the paper's
    DenseNet-121; the tests pass a tiny config to rehearse on the CPU."""
    model: object = None
    image: int = 224
    n_clients: int = 5

    @property
    def cfg(self):
        if self.model is not None:
            return self.model
        from repro.configs.paper_models import DENSENET121_PAPER
        return DENSENET121_PAPER


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def device_gate(n_chips=None) -> dict:
    """The TPU or nothing: platform, kernel mode and chip count."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX found platform "
                         f"{d.platform!r} ({d.device_kind}, {len(devs)} "
                         "devices); no phase run")
    from repro.kernels import compat
    if compat.INTERPRET:
        raise SystemExit("chip_smoke: repro.kernels.compat.INTERPRET is True "
                         "— the Pallas kernels would run in the interpreter")
    if n_chips is not None and len(devs) != n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds XLA spent compiling programs (or loading them from the
    persistent cache), and persistent-cache hits, since construction.
    Tracing and lowering are not in it (nested jits would count twice);
    they are in each phase's wall time."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(device):
    """``peak_bytes_in_use`` of a device: the process's running maximum,
    never reset, so not one phase's peak.  None where the backend keeps no
    allocator stats (the CPU)."""
    stats = device.memory_stats()
    return None if stats is None else stats["peak_bytes_in_use"]


def make_clients(sizes: Sizes):
    from repro.data.synthetic import make_cxr_clients
    return make_cxr_clients(seed=SEED, n_clients=sizes.n_clients,
                            train_per_client=PER_CLIENT,
                            val_per_client=BATCH,
                            test_per_client=N_REQUESTS,
                            image_size=sizes.image)


def method_options(method: str) -> dict:
    """The paper's technique per method: DP-SGD on FL, the int8 cut-layer
    link on SplitFedv3, plain split learning."""
    if method == "fl":
        from repro.privacy import PrivacyConfig
        return {"privacy": PrivacyConfig(noise_multiplier=1.1,
                                         clip_norm=1.0)}
    if method == "sflv3_ac":
        from repro.wire import Transport
        return {"transport": Transport("int8")}
    return {}


def train(method, clients, sizes: Sizes, clock, n_epochs=None, **kw):
    """One ``make_strategy(...).run`` from a seeded init; returns the
    strategy, its state, the epoch logs and the run's numbers."""
    import jax
    from repro import optim as O
    from repro.core.partition import cnn_adapter
    from repro.core.strategies import make_strategy
    from repro.models.cnn import build_densenet
    strat = make_strategy(method, cnn_adapter(build_densenet(sizes.cfg)),
                          lambda: O.adam(LR), n_clients=sizes.n_clients,
                          **{**method_options(method), **kw})
    state = strat.setup(jax.random.key(SEED))
    c0, h0 = clock.seconds, clock.cache_hits
    t0 = time.perf_counter()
    state, logs = strat.run(state, [c.train for c in clients],
                            np.random.default_rng(SEED), BATCH,
                            n_epochs or EPOCHS)
    jax.block_until_ready(state)
    losses = np.concatenate([np.asarray(log.losses) for log in logs])
    check(losses.size and np.isfinite(losses).all(),
          f"{method}: non-finite or empty losses {losses}")
    info = {"compile_s": clock.seconds - c0,
            "cache_hits": clock.cache_hits - h0,
            "wall_s": time.perf_counter() - t0,
            "steps": sum(log.steps for log in logs),
            "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
            "dispatches": strat._dispatches}
    return strat, state, logs, info


def custom_calls(strat) -> int:
    """``tpu_custom_call`` count in the lowered whole-run program (lowered
    again from its abstract arguments; nothing is compiled twice)."""
    fn, args = strat._last_run_invocation
    return fn.lower(*args).as_text().count("tpu_custom_call")


def train_phase(sizes: Sizes, clients, clock):
    """Each method's whole run, one dispatch each; returns the sflv3_ac
    strategy, its state and epoch logs for the later phases."""
    import jax
    kept = None
    for method in METHODS:
        strat, state, logs, info = train(method, clients, sizes, clock)
        check(info["dispatches"] == 1,
              f"{method}: {info['dispatches']} dispatches for one run")
        info["process_peak_bytes"] = peak_bytes(jax.devices()[0])
        if method != "sl_am":
            # the DP clip / int8 cut-layer kernels, not the interpreter
            n = custom_calls(strat)
            check(n > 0,
                  f"{method}: no tpu_custom_call in the run program")
            info["tpu_custom_calls"] = n
        print(f"train {method}: " + " ".join(
            f"{k}={v}" for k, v in info.items()), flush=True)
        if method == "sflv3_ac":
            kept = (strat, state, logs)
        del state
    return kept


def kernel_refs(sizes: Sizes):
    """``cut_fuse`` and ``dp_clip`` on the device against their refs at
    the DenseNet cut shape and its largest per-example gradient leaf."""
    import jax
    import jax.numpy as jnp
    from repro.core.partition import cnn_adapter
    from repro.kernels.act_compress.ref import roundtrip_ref
    from repro.kernels.cut_fuse.ops import cut_noise_roundtrip, fused_roundtrip
    from repro.kernels.cut_fuse.ref import noise_roundtrip_ref
    from repro.kernels.dp_clip.ops import clip_accumulate
    from repro.kernels.dp_clip.ref import clip_accumulate_ref
    from repro.models.cnn import build_densenet

    adapter = cnn_adapter(build_densenet(sizes.cfg))
    example = {"image": jax.ShapeDtypeStruct(
        (BATCH, sizes.image, sizes.image, 1), jnp.float32)}
    cut = adapter.boundary_specs(example)["front->middle"]
    kx, kz, kg = jax.random.split(jax.random.key(SEED), 3)
    x = jax.random.normal(kx, cut.shape, jnp.float32)
    z0 = jax.random.normal(kz, cut.shape, jnp.float32)
    w = jnp.ones((cut.shape[0],), jnp.float32).at[-1].set(0.0)
    std = 0.5
    step = (jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12)
            / 127.0)
    rt = float(jnp.max(jnp.abs(fused_roundtrip(x) - roundtrip_ref(x))
                       / step))
    nz = float(jnp.max(jnp.abs(cut_noise_roundtrip(x, std * z0, w)
                               - noise_roundtrip_ref(x, z0, std, w))
                       / step))
    check(rt <= TOL_QUANT_STEPS and nz <= TOL_QUANT_STEPS,
          f"cut_fuse off its ref: roundtrip {rt}, noise {nz} steps")

    shapes = jax.eval_shape(adapter.init, jax.random.key(SEED))
    leaves = jax.tree.leaves(shapes)
    big = max(leaves, key=lambda l: l.size)
    small = min(leaves, key=lambda l: l.size)
    scale = jnp.linspace(0.2, 3.0, BATCH).astype(jnp.float32)
    grads = {}
    for name, leaf in (("largest", big), ("smallest", small)):
        g = jax.random.normal(jax.random.fold_in(kg, leaf.size),
                              (BATCH, *leaf.shape), jnp.float32)
        grads[name] = g * (scale / np.sqrt(leaf.size)).reshape(
            (-1,) + (1,) * leaf.ndim)
    got, got_n = clip_accumulate(grads, 1.0)
    want, want_n = clip_accumulate_ref(grads, 1.0)
    rel = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
              for a, b in zip(jax.tree.leaves((got, got_n)),
                              jax.tree.leaves((want, want_n))))
    check(rel <= TOL_DP_REL, f"dp_clip off its ref: rel {rel}")
    print(f"refs kernels: cut shape {tuple(cut.shape)} "
          f"roundtrip_steps={rt} noise_steps={nz} "
          f"(tol {TOL_QUANT_STEPS}); dp_clip leaf {tuple(big.shape)} "
          f"rel={rel} (tol {TOL_DP_REL})", flush=True)


def stepwise_ref(sizes: Sizes, clients, clock, compiled_logs):
    """The compiled sflv3_ac run's epoch 0 against the stepwise engine."""
    strat, _, logs, info = train("sflv3_ac", clients, sizes, clock,
                                 n_epochs=1, engine="stepwise")
    a = np.asarray(compiled_logs[0].losses)
    b = np.asarray(logs[0].losses)
    check(a.shape == b.shape, f"step counts differ: {a.shape} {b.shape}")
    diff = float(np.max(np.abs(a - b)))
    check(diff <= TOL_STEPWISE,
          f"compiled vs stepwise losses differ by {diff}")
    print(f"refs stepwise: sflv3_ac epoch 0, {a.size} step losses, "
          f"max_abs_diff={diff} (tol {TOL_STEPWISE}) "
          f"dispatches={strat._dispatches} compile_s={info['compile_s']}",
          flush=True)


def serve_phase(clients, strat, state, clock):
    """Hospital 0's export behind the bucket ladder; single-image
    requests coalesce into buckets and never trigger a compile."""
    from repro.serving import ScreeningService
    test = clients[0].test
    images = test["image"][:N_REQUESTS]
    want = strat.scores(state, 0, {k: v[:N_REQUESTS]
                                   for k, v in test.items()},
                        batch_size=BATCH)
    servable = strat.export(state, client_idx=0)
    c0 = clock.seconds
    with ScreeningService(servable, buckets=BUCKETS,
                          image_shape=images.shape[1:]) as svc:
        warm = svc.scorer.n_compiles
        check(warm == len(BUCKETS),
              f"{warm} programs for {len(BUCKETS)} buckets")
        compile_s = clock.seconds - c0
        b = max(BUCKETS)
        got8, info = svc.score_batch({"image": images[:b]})
        check(info["buckets"] == [b], f"bucket {info['buckets']} for {b}")
        d8 = float(np.max(np.abs(got8 - want[:b])))
        reqs = [svc.submit({"image": im}) for im in images]
        for r in reqs:
            check(r.done.wait(60.0), "a request was not answered")
        served = np.asarray([r.score for r in reqs], np.float32)
        dall = float(np.max(np.abs(served - want)))
        stats = svc.stats()
        check(svc.scorer.n_compiles == warm,
              f"fresh compiles after warm-up: {svc.scorer.n_compiles}")
    check(np.isfinite(served).all(), "non-finite served scores")
    check(d8 <= TOL_SERVE and dall <= TOL_SERVE,
          f"served scores off Strategy.scores: bucket {d8}, requests {dall}")
    print(f"serve sflv3_ac hospital 0: buckets={BUCKETS} "
          f"compile_s={compile_s} requests={len(reqs)} "
          f"batch_n_mean={stats['batch_n_mean']} "
          f"bucket{b}_max_abs_diff={d8} requests_max_abs_diff={dall} "
          f"(tol {TOL_SERVE}) n_compiles={warm}->{svc.scorer.n_compiles}",
          flush=True)


def one_chip(sizes: Sizes):
    """Train, reference and serve phases on the default device."""
    clock = CompileClock()
    clients = make_clients(sizes)
    strat, state, logs = train_phase(sizes, clients, clock)
    kernel_refs(sizes)
    stepwise_ref(sizes, clients, clock, logs)
    serve_phase(clients, strat, state, clock)


def drift(rows) -> float:
    """‖θ_b − θ_a‖ / ‖θ_a − θ_init‖ over ``(segment, a, b, init)`` rows."""
    return float(np.sqrt(sum(np.sum((a - b) ** 2) for _, a, b, _ in rows)
                         / sum(np.sum((a - c) ** 2) for _, a, _, c in rows)))


def hospital_spread(method, strat, state) -> set:
    """(devices, hospitals per device) of every [C, ...] array the last
    run was called with and returned, read from the run's own recorded
    invocation and state.  fl returns only the replicated global params,
    which must span the same devices."""
    import jax
    _, args = strat._last_run_invocation
    pairs = [(leaf, axis) for i, axis in HOSPITAL_ARGS[method].items()
             for leaf in jax.tree.leaves(args[i])]
    if method == "fl":
        pairs += [(leaf, None) for leaf in jax.tree.leaves(state["params"])]
    else:
        pairs += [(leaf, 0) for leaf in jax.tree.leaves(
            (state["stacked_clients"], state["c_opt"]))]
    spread = set()
    for leaf, axis in pairs:
        if axis is not None and len(leaf.shape) <= axis:
            continue                          # Adam's replicated step count
        sh = leaf.sharding
        if sh is None:
            spread.add((1, None))             # never placed on the mesh
        elif axis is None:
            spread.add((len(sh.device_set),
                        "replicated" if sh.is_fully_replicated else None))
        else:
            spread.add((len(sh.device_set), sh.shard_shape(leaf.shape)[axis]))
    return spread


def shard_parity(method, clients, sizes: Sizes, clock):
    """One method with ``shard=True`` against ``shard=False``: losses,
    params relative to their movement, and where the [C, ...] arrays
    lived."""
    import jax
    from repro.wire import Transport
    kw = {"transport": Transport("identity")} if method == "sflv3_ac" else {}
    with jax.default_matmul_precision(SHARD_PRECISION[method]):
        runs = {shard: train(method, clients, sizes, clock, shard=shard,
                             **kw)
                for shard in (False, True)}
    (sa, xa, la, ia), (sb, xb, lb, ib) = runs[False], runs[True]
    check(ia["dispatches"] == ib["dispatches"] == 1,
          f"{method}: dispatches {ia['dispatches']}/{ib['dispatches']}")
    dlosses = np.abs(np.concatenate([np.asarray(log.losses) for log in la])
                     - np.concatenate([np.asarray(log.losses) for log in lb]))
    dloss = float(np.max(dlosses))
    x0 = sa.setup(jax.random.key(SEED))            # the runs' common init
    rows = []            # (segment, shard=False, shard=True, init) leaves
    for i in range(sizes.n_clients):
        pa, pb, p0 = (s.params_for_eval(x, i)
                      for s, x in ((sa, xa), (sb, xb), (sa, x0)))
        for seg in pa:
            rows += [(seg, *(np.asarray(v, np.float64) for v in t))
                     for t in zip(*(jax.tree.leaves(p[seg])
                                    for p in (pa, pb, p0)))]
    dparam = max(float(np.max(np.abs(a - b))) for _, a, b, _ in rows)
    rel = drift(rows)
    segs = {g: drift([r for r in rows if r[0] == g]) for g in pa}
    check(dloss <= TOL_STEPWISE and rel <= TOL_SHARD_REL,
          f"{method}: shard=True off shard=False: loss {dloss}, "
          f"params rel {rel} (max abs {dparam})")
    place, n_dev = sb.placement, jax.device_count()
    per_chip = place.c_pad // n_dev
    check(place.enabled and place.c_pad % n_dev == 0,
          f"{method}: placement {place}")
    spread = hospital_spread(method, sb, xb)
    want = {(n_dev, per_chip)} | ({(n_dev, "replicated")}
                                  if method == "fl" else set())
    check(spread == want, f"{method}: (devices, hospitals per device) of "
          f"the run's arrays {spread}, want {want}")
    # the server segment is replicated: every chip's copy must agree
    replica_diff = max((float(np.max(np.abs(np.asarray(a.data)
                                            - np.asarray(b.data))))
                        for leaf in jax.tree.leaves(xb.get("server", {}))
                        for a, b in zip(leaf.addressable_shards,
                                        leaf.addressable_shards[1:])),
                       default=None)
    check(replica_diff in (None, 0.0),
          f"{method}: the chips' server replicas differ by {replica_diff}")
    print(f"four_chips {method} blocks={sizes.cfg.blocks}: "
          f"link={kw['transport'].codec.name if kw else None} "
          f"precision={SHARD_PRECISION[method]} "
          "shard=True vs shard=False "
          f"max_abs_loss_diff={dloss} (tol {TOL_STEPWISE}) "
          f"hospital0_first_loss_diff={float(dlosses[0])} "
          f"param_diff_over_movement={rel} (tol {TOL_SHARD_REL}) "
          + "".join(f"{g}_over_movement={v} " for g, v in segs.items()) +
          f"max_abs_param_diff={dparam} c_pad={place.c_pad} "
          f"hospitals_per_chip={per_chip} device_set={n_dev} "
          f"run_arrays={sorted(spread, key=str)} "
          f"server_replica_max_abs_diff={replica_diff} "
          f"compile_s={ia['compile_s']}/{ib['compile_s']} "
          f"process_peak_bytes={[peak_bytes(d) for d in jax.devices()]}",
          flush=True)


def four_chips(sizes: Sizes):
    """fl and sflv3_ac with the hospital axis on a 4-device mesh against
    the same runs on one device: 5 hospitals pad to 8, two per chip."""
    clock = CompileClock()
    clients = make_clients(sizes)
    for method in ("fl", "sflv3_ac"):
        shard_parity(method, clients, sizes, clock)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only shard=True vs shard=False on 4 chips")
    args = ap.parse_args(argv)
    device = device_gate(4 if args.four_chips else None)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {device} compile cache: {enable_compile_cache()}",
          flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        from repro.configs.paper_models import DENSENET121_PAPER
        four_chips(Sizes(model=dataclasses.replace(
            DENSENET121_PAPER, blocks=FOUR_CHIP_BLOCKS)))
    else:
        one_chip(Sizes())
    print(f"wall_s={time.perf_counter() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
