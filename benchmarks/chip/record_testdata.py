#!/usr/bin/env python3
"""Record a small chip trace to test the trace reduction against.

    python benchmarks/chip/record_testdata.py [--summary] [--out DIR]

On a TPU: a jitted step (a product, then the int8 cut-layer kernel of
``kernels/cut_fuse``) runs three times, each after 20 ms of host work in
a ``pack`` span, under the profiler.  Writes ``small.xplane.pb``
and ``small.json`` into ``testdata/`` (or ``--out``) (the sync reading, the window, the spans, and
what ``trace_reduce`` made of them on the chip).  ``--summary`` prints
every plane, line and device operation name with its stats.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "benchmarks")]
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")


def main():
    import jax
    import jax.numpy as jnp
    from chip import harness, trace_reduce
    from repro.kernels.cut_fuse.ops import fused_roundtrip
    harness.device_gate(1)
    x = jax.random.normal(jax.random.key(0), (512, 256), jnp.float32)

    @jax.jit
    def step(x):
        return fused_roundtrip(jnp.tanh(x @ x.T) @ x)

    step(x).block_until_ready()
    d = tempfile.mkdtemp()
    spans = []
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
        sync = time.perf_counter()
    t0 = time.perf_counter()
    for _ in range(3):
        a = time.perf_counter()
        time.sleep(0.02)
        spans.append(("pack", a, time.perf_counter()))
        step(x).block_until_ready()
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(d)
    red = trace_reduce.reduce(trace_reduce.Trace.load(path), sync, (t0, t1),
                              spans, ["roundtrip"])
    out = sys.argv[sys.argv.index("--out") + 1] if "--out" in sys.argv \
        else OUT
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    with open(os.path.join(out, "small.json"), "w") as f:
        json.dump({"sync": sync, "window": [t0, t1], "spans": spans,
                   "kernels": ["roundtrip"], "reduced": red}, f, indent=1)
    print(json.dumps({k: red[k] for k in ("window_s", "busy_s", "kernels",
                                          "kernel_calls", "breakdown")}))
    if "--summary" in sys.argv:
        from jax.profiler import ProfileData
        for plane in ProfileData.from_file(path).planes:
            print("PLANE", plane.name)
            for line in plane.lines:
                evs = list(line.events)
                print("  LINE", line.name, len(evs))
                if plane.name.startswith("/device"):
                    for e in evs[:40]:
                        print("    ", e.name, e.duration_ns,
                              [(k, str(v)[:80]) for k, v in e.stats])
    shutil.rmtree(d, ignore_errors=True)
    print(glob.glob(os.path.join(out, "*")))


if __name__ == "__main__":
    main()
