"""The harness: finds a cell's parts by name, runs it, prints the result.

One run is one process on the chips of the machine it starts on::

    set-up (data, weights, program load or compile, warm-up)
    -> the measured window (``--seconds``; ``--trace 1``: a traced window)
    -> device memory peak -> program state freed -> reference comparison

and ends with one JSON line on stdout.  The compared numbers, each beside
its limit, are the last lines on stderr and the last key of that line.

A cell's parts, looked up in ``Library.dirs`` (this directory first):
``configs/<config>.json``, ``families/<family>.py``, ``mixes/<mix>.json``
(whose ``driver`` names ``drivers/<driver>.py``), ``limits/<cell>.json``
and ``metrics/<metric>.py`` for each per-layer metric of the cell.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class CompileClock:
    """XLA compile seconds (or persistent-cache loads) and cache hits
    since construction, from JAX's monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Library:
    """Finds a cell's files by name, in ``dirs`` in order."""

    def __init__(self, dirs=(HERE,)):
        self.dirs = [Path(d) for d in dirs]

    def path(self, kind: str, name: str, suffix: str) -> Path:
        for d in self.dirs:
            p = d / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind}/{name}{suffix} in "
                                f"{[str(d) for d in self.dirs]}")

    def json(self, kind: str, name: str) -> dict:
        return json.loads(self.path(kind, name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self.path(kind, name, ".py")
        mod_name = f"chip_{kind}_{name}".replace(".", "_").replace("-", "_")
        if mod_name in sys.modules:
            return sys.modules[mod_name]
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod


def _resolve(dotted: str):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)


class Context:
    """Everything a driver is given: the cell's configuration, mix and
    family, the run's seed and length, the trace switch, the compile
    clock, a log to stderr and the traced-window hook."""

    def __init__(self, bench: dict, cell: dict, lib: Library, seed: int,
                 seconds: float, trace: bool):
        self.bench, self.cell, self.lib = bench, cell, lib
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.cfg = lib.json("configs", cell["config"])
        self.mix = lib.json("mixes", cell["traffic"])
        self.family = lib.module("families", self.cfg["family"])
        self.limits = lib.json("limits", cell["name"])
        self.clock = None
        self.traced_record = None      # set by ``traced``

    def log(self, *parts):
        print(*parts, file=sys.stderr, flush=True)

    def program_adapter(self):
        """The program's model at this configuration's sizes."""
        p = self.cfg["program"]
        model = _resolve(p["build"])(_resolve(p["config"])(
            **{k: tuple(v) if isinstance(v, list) else v
               for k, v in self.cfg["model"].items()}))
        return _resolve(p["adapter"])(model)

    @contextlib.contextmanager
    def traced(self):
        """Profile the block; the trace is reduced by the harness."""
        import jax
        d = tempfile.mkdtemp(prefix="chip_trace_")
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation("bench.sync"):
                sync = time.perf_counter()
            yield
        finally:
            jax.profiler.stop_trace()
        self.traced_record = {"dir": d, "sync": sync}


def device_gate(n_chips: int) -> dict:
    """The TPU, in compiled mode, with at least the cell's chips, or exit
    without a result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {d.platform!r} "
                         f"({d.device_kind}, {len(devs)} devices)")
    from repro.kernels import compat
    if compat.INTERPRET:
        raise SystemExit("the Pallas kernels are in interpret mode")
    if len(devs) < n_chips:
        raise SystemExit(f"the cell needs {n_chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": n_chips}


def memory_peak(n_chips: int):
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        stats = d.memory_stats()
        if stats is None:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             lib: Library | None = None, device: dict | None = None,
             t_start: float | None = None) -> dict:
    """Run one cell and return its result line (``device`` given: the
    device gate was passed by the caller)."""
    t_start = time.perf_counter() if t_start is None else t_start
    lib = lib or Library()
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    ctx = Context(bench, cell, lib, seed, seconds, trace)
    if device is None:
        device = device_gate(cell["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    ctx.log(f"device {device} compile cache {enable_compile_cache()}")
    ctx.clock = CompileClock()
    with jax.default_matmul_precision(ctx.cfg["matmul_precision"]):
        return _run(ctx, lib, bench, cell, device, t_start)


def _run(ctx, lib, bench, cell, device, t_start) -> dict:
    name = cell["name"]
    trace = ctx.trace
    driver = lib.module("drivers", ctx.mix["driver"])
    run = driver.Cell(ctx)
    setup_s = time.perf_counter() - t_start
    c0 = ctx.clock.compiles
    ctx.log(f"setup_s {setup_s} compile_s {ctx.clock.seconds} "
            f"compiles {c0} cache_hits {ctx.clock.cache_hits}")
    window = run.window()
    in_window = ctx.clock.compiles - c0
    if in_window:
        raise RuntimeError(f"{in_window} compiles inside the measured window")
    device = dict(device, memory_peak_bytes=memory_peak(cell["chips"]))
    ctx.log(f"memory_peak_bytes {device['memory_peak_bytes']} "
            f"(the process's running peak)")
    if trace:
        readers = _readers(bench, cell, lib)
        rec = _reduce_trace(ctx, window, device, readers)
        metrics = {}
        ctx.log(f"trace kernels {rec['trace']['kernels']} calls "
                f"{rec['trace']['kernel_calls']} custom calls "
                f"{rec['trace']['custom_calls']}")
        for m, reader in readers:
            value = reader.read(rec)
            if value is None:
                ctx.log(f"metric {m['name']} silent: nothing to read")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": window["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if m["name"] != "setup_s"
                   and name in m.get("workloads", [name])}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    run.release()
    numbers = run.check()
    from chip.compare import verdict
    ok, lines = verdict(numbers, ctx.limits)
    ok &= window["failed"] == 0
    metrics = {k: {"value": _finite(v["value"]), "unit": v["unit"]}
               for k, v in metrics.items()}
    result = {"correct": ok, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = rec["breakdown"]
    result["compared"] = {n: {"value": v, "limit": ctx.limits.get(n)}
                          for n, v in numbers.items()}
    for line in lines:
        ctx.log(f"compared {line}")
    return result


def _readers(bench, cell, lib) -> list:
    """The per-layer metrics of this cell with their readers."""
    return [(m, lib.module("metrics", m["name"])) for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def _reduce_trace(ctx, window, device, readers) -> dict:
    """The record the readers read: the driver's window, the reduced
    trace, the chip's peaks and the model's operations per image."""
    from chip import flops, trace_reduce
    from chip.peaks import peaks
    tr = ctx.traced_record
    if tr is None:
        raise RuntimeError("the driver traced no window")
    kernels = sorted({r.KERNEL for _, r in readers if hasattr(r, "KERNEL")})
    try:
        trace = trace_reduce.Trace.load(trace_reduce.find_xplane(tr["dir"]))
        red = trace_reduce.reduce(trace, tr["sync"], window["span"],
                                  window.get("host_spans", ()), kernels)
    finally:
        shutil.rmtree(tr["dir"], ignore_errors=True)
    device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    model, size = ctx.cfg["model"], ctx.cfg["image_size"]
    return dict(window, trace=red, breakdown=red["breakdown"],
                peaks=peaks(device["kind"]), chips=ctx.cell["chips"],
                flops_per_image=flops.model_flops_per_image(
                    ctx.family, model, size),
                cut_elements_per_image=flops.cut_elements(
                    ctx.family, model, size))


def _finite(v):
    """A number as JSON can hold it: ``None`` for inf or nan."""
    import math
    return v if math.isfinite(v) else None


def main(argv=None, t_start=None):
    t_start = time.perf_counter() if t_start is None else t_start
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=t_start)
    print(json.dumps(result), flush=True)
