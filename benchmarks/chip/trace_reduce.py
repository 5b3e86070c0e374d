"""Reduce a profiler trace (``.xplane.pb``) of a measured window to the
numbers the per-layer metrics read.

The host's clock and the trace's are tied by one annotation: the harness
reads ``time.perf_counter()`` inside a ``TraceAnnotation`` named
``SYNC``, and the annotation's start in the trace is that instant.  Every
window and host span the benchmark keeps in ``perf_counter`` seconds is
mapped through it.

A TPU trace names each operation by its HLO text (``%name = type
opcode(operands), ...``); the reduction keeps the name before `` = ``.
Control flow (``while``, ``conditional``, ``call``) is left out: its
event spans the operations it runs.

* busy — the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices; idle is the
  window less busy.
* ops — device seconds per operation name (inside the window, summed
  over devices).
* kernels — device seconds of the custom calls (Pallas kernels) whose
  name contains a given marker: a kernel is named after its function.
  ``custom_calls`` keeps every custom call's seconds by name, so that a
  marker that matches nothing can be told from a kernel that is gone.
* idle gaps — the stretches of the window in which device 0 ran
  nothing, each instant named by the innermost host span that covers it
  (``other`` where none does), summed per name.
"""

from __future__ import annotations

import glob
import os
import re

SYNC = "bench.sync"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {files}")
    return files[0]


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


_CONTROL = re.compile(r"\s(while|conditional|call)\(")


def _op(text: str) -> tuple[str, bool, bool]:
    """(name, is control flow, is a custom call) of an op's HLO text."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, False, False
    return (name.lstrip("%"), bool(_CONTROL.search(" " + rest)),
            " custom-call(" in rest)


class Trace:
    """One trace's device operations and host clock anchor, from a
    ``jax.profiler.ProfileData`` (``Trace.load`` reads a file)."""

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(path))

    def __init__(self, data):
        self.devices = {}          # device id -> [(start_ns, end_ns, name, custom)]
        self.sync_ns = None
        for plane in data.planes:
            m = _DEVICE.match(plane.name)
            if m:
                ops = []
                for line in plane.lines:
                    if line.name != _OPS_LINE:
                        continue
                    for e in line.events:
                        name, control, custom = _op(e.name)
                        if not control:
                            ops.append((e.start_ns,
                                        e.start_ns + e.duration_ns, name,
                                        custom))
                self.devices[int(m.group(1))] = ops
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == SYNC and self.sync_ns is None:
                            self.sync_ns = e.start_ns
        if self.sync_ns is None:
            raise RuntimeError(f"no {SYNC!r} annotation in the trace")
        if not self.devices:
            raise RuntimeError("no TPU device plane in the trace")

    def ns(self, t_host: float, sync_host: float) -> float:
        return self.sync_ns + (t_host - sync_host) * 1e9


def reduce(trace: Trace, sync_host: float, window: tuple[float, float],
           spans=(), kernel_markers=()) -> dict:
    """``window`` and ``spans`` (``(name, start, end)``) are host
    ``perf_counter`` seconds; ``sync_host`` the host reading of ``SYNC``."""
    w0, w1 = (trace.ns(t, sync_host) for t in window)
    length = (w1 - w0) * 1e-9
    busy, ops, customs = [], {}, {}
    kernels = {k: 0.0 for k in kernel_markers}
    kernel_calls = {k: 0 for k in kernel_markers}
    merged0 = []
    for dev, events in sorted(trace.devices.items()):
        inside = []
        for a, b, name, custom in events:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            inside.append((a, b))
            sec = (b - a) * 1e-9
            ops[name] = ops.get(name, 0.0) + sec
            if custom:
                customs[name] = customs.get(name, 0.0) + sec
            for k in kernel_markers:
                if custom and k in name:
                    kernels[k] += sec
                    kernel_calls[k] += 1
        merged = _merge(inside)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if not merged0 and dev == min(trace.devices):
            merged0 = merged
    gaps, t = [], w0
    for a, b in merged0:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    host = [(n, trace.ns(a, sync_host), trace.ns(b, sync_host))
            for n, a, b in spans]
    by_name = {}
    for a, b in gaps:
        cuts = sorted({a, b} | {t for _, s0, s1 in host for t in (s0, s1)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            inner = [(s1 - s0, n) for n, s0, s1 in host
                     if s0 <= lo and hi <= s1]
            name = min(inner)[1] if inner else "other"
            by_name[name] = by_name.get(name, 0.0) + (hi - lo) * 1e-9
    n_dev = len(trace.devices)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": length, "busy_s": sum(busy) / n_dev,
            "devices": n_dev, "ops": ops, "kernels": kernels,
            "kernel_calls": kernel_calls, "custom_calls": customs,
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in top_gaps]}}
