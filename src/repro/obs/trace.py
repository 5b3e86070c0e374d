"""Run tracing — one merged Chrome-trace/Perfetto JSON per run.

Three clock domains meet here and each gets its own ``pid`` lane:

  * **engine host** (``PID_ENGINE``): real wall-clock spans recorded by
    ``Tracer`` around the strategy's host phases.  One compiled run is
    ``run -> pack{gather, stack} -> enqueue -> wait -> account``: the
    spans of one run tile it and share its ordinal (``run`` arg).  The
    counters of each phase ride its span as args (bytes packed per data
    key, batch slots against real batches, the program's input bytes,
    the run's training images).  ``repro.obs.compile_log`` adds every
    trace, lowering and backend compile as a ``compile.*`` span.
  * **wire** (``PID_WIRE``): the *simulated*-time transfer timelines from
    ``wire.simulator.timeline_from_accounting`` — per-client tracks of
    upload/download events with tag + byte args.  Simulated seconds are
    mapped 1:1 onto trace microseconds; the lane is a model of the wire,
    not a measurement, and is labelled as such.
  * **serving** (``PID_SERVING``): the screening front end's externally
    timed phases (``Tracer.event``).

Every ``Tracer.span`` also opens a ``jax.profiler.TraceAnnotation`` named
``ANNOTATION_PREFIX + name`` with the span's args as stats, so under a
running profiler the program's phases and counters land in the
profiler's host plane, on the device operations' clock.  Finished spans
also go to a bounded process-wide log (``recent_spans``) on the
``time.perf_counter`` clock, which outlives the tracer.

``write_chrome_trace`` emits the standard ``{"traceEvents": [...]}`` JSON
that chrome://tracing and https://ui.perfetto.dev load directly.
"""

from __future__ import annotations

import collections
import contextlib
import json
import time

import jax

PID_ENGINE = 1
PID_WIRE = 2
PID_SERVING = 3

ANNOTATION_PREFIX = "repro."
RUN = "run"                 # a span with this name starts a new run ordinal
SPAN_LOG_SIZE = 4096

_RECENT: collections.deque = collections.deque(maxlen=SPAN_LOG_SIZE)


def _meta(pid, name, tid=None, tname=None):
    ev = [{"name": "process_name", "ph": "M", "pid": pid,
           "args": {"name": name}}]
    if tid is not None:
        ev.append({"name": "thread_name", "ph": "M", "pid": pid,
                   "tid": tid, "args": {"name": tname}})
    return ev


class Span:
    """One finished or open span: its name, its parent's name, the run
    ordinal it belongs to, ``perf_counter`` start and end seconds and its
    args.  ``set`` records counters where the work happens; ``program``
    holds the ``(jitted fn, abstract args)`` an ``enqueue`` span called,
    so its device operations can be named afterwards
    (``repro.obs.scopes``)."""

    __slots__ = ("name", "parent", "run", "start", "end", "args",
                 "program", "_annotation")

    def __init__(self, name, parent, run, start, args, annotation=None):
        self.name, self.parent, self.run = name, parent, run
        self.start, self.end = start, None
        self.args = dict(args)
        self.program = None
        self._annotation = annotation

    def set(self, **counters) -> None:
        self.args.update(counters)
        if self._annotation is not None:
            self._annotation.set_metadata(**_stats(counters))


def _stats(args: dict) -> dict:
    """Span args as profiler stats (numbers and strings; None dropped)."""
    return {k: v if isinstance(v, (int, float, str)) else str(v)
            for k, v in args.items() if v is not None}


def recent_spans(t0: float | None = None, t1: float | None = None) -> list:
    """Finished spans of every ``Tracer`` in this process (the last
    ``SPAN_LOG_SIZE``), oldest first; with ``t0``/``t1`` only those that
    start and end inside ``[t0, t1]`` (``perf_counter`` seconds)."""
    return [s for s in list(_RECENT)
            if (t0 is None or s.start >= t0) and (t1 is None or s.end <= t1)]


class Tracer:
    """Host-side span tree: nested ``with tracer.span(name) as sp:``
    blocks become Chrome complete ("X") events on one engine-host track,
    each with its ``depth``, ``parent`` and ``run`` ordinal as args.  A
    strategy given to ``Strategy.attach_tracer`` records its run phases
    here (see the module docstring)."""

    def __init__(self, pid: int = PID_ENGINE, tid: int = 1):
        self.pid, self.tid = pid, tid
        self.events: list = []
        self._open: list = []
        self._runs = 0
        self._t0 = time.perf_counter()

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _context(self, name):
        parent = self._open[-1] if self._open else None
        if name == RUN:
            self._runs += 1
            run = self._runs
        else:
            run = parent.run if parent is not None else None
        return (parent.name if parent is not None else None), run

    @contextlib.contextmanager
    def span(self, name: str, **args):
        parent, run = self._context(name)
        ann = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + name,
            **_stats(dict(args, parent=parent, run=run)))
        ann.__enter__()
        sp = Span(name, parent, run, time.perf_counter(), args, ann)
        self._open.append(sp)
        try:
            yield sp
        finally:
            sp.end = time.perf_counter()
            self._open.pop()
            ann.__exit__(None, None, None)
            sp._annotation = None
            self._finish(sp)

    def add(self, name: str, start: float, end: float, **args) -> Span:
        """Record a span timed elsewhere, in ``perf_counter`` seconds,
        under the span open now (``repro.obs.compile_log`` forwards each
        compile here)."""
        parent, run = self._context(name)
        sp = Span(name, parent, run, start, args)
        sp.end = end
        self._finish(sp)
        return sp

    def _finish(self, sp: Span) -> None:
        self.events.append({
            "name": sp.name, "ph": "X", "ts": (sp.start - self._t0) * 1e6,
            "dur": max((sp.end - sp.start) * 1e6, 0.01),
            "pid": self.pid, "tid": self.tid,
            "args": {**sp.args, "depth": len(self._open),
                     "parent": sp.parent, "run": sp.run}})
        _RECENT.append(sp)

    def event(self, name: str, t0_s: float, t1_s: float,
              tid: int | None = None, **args) -> None:
        """Record an externally-timed complete span from a pair of
        ``tracer.now()`` readings (seconds since this tracer's epoch) —
        used by the serving front end, whose phases are timed where they
        happen (enqueue in the caller, dispatch in the batcher thread)
        rather than around a single ``with`` block."""
        self.events.append({
            "name": name, "ph": "X", "ts": t0_s * 1e6,
            "dur": max((t1_s - t0_s) * 1e6, 0.01),
            "pid": self.pid, "tid": self.tid if tid is None else tid,
            "args": args})

    def now(self) -> float:
        """Seconds since this tracer's epoch (pairs with ``event``)."""
        return time.perf_counter() - self._t0

    def find(self, name: str) -> dict | None:
        """Most recent finished span with this name (e.g. "enqueue")."""
        for ev in reversed(self.events):
            if ev["name"] == name:
                return ev
        return None

    def trace_events(self) -> list:
        return _meta(self.pid, "engine host", self.tid, "strategy") \
            + list(self.events)


def wire_events(sim_result, pid: int = PID_WIRE, label: str = "") -> list:
    """``wire.simulator.SimResult`` transfer events as per-client trace
    tracks (simulated seconds -> trace microseconds)."""
    name = f"wire (simulated{', ' + label if label else ''})"
    out = _meta(pid, name)
    clients = sorted({e.client for e in sim_result.events})
    for tid, c in enumerate(clients, start=1):
        out += _meta(pid, name, tid, f"client {c}")[1:]
        for e in sim_result.events:
            if e.client != c:
                continue
            out.append({"name": e.tag, "ph": "X", "ts": e.t_start * 1e6,
                        "dur": max((e.t_end - e.t_start) * 1e6, 0.01),
                        "pid": pid, "tid": tid,
                        "args": {"bytes": int(e.nbytes),
                                 "direction": e.direction}})
    return out


def merge_events(*event_lists, pid_offset: int = 0) -> list:
    """Concatenate event lists into one trace; ``pid_offset`` shifts every
    pid of the merged lists so several strategies' lanes can coexist in
    one file (offset by, say, 10 per strategy)."""
    out = []
    for evs in event_lists:
        for e in evs:
            e = dict(e)
            e["pid"] = e.get("pid", 0) + pid_offset
            out.append(e)
    return out


def write_chrome_trace(events: list, path) -> str:
    """Write ``{"traceEvents": [...]}`` JSON loadable by chrome://tracing
    and Perfetto."""
    path = str(path)
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f, indent=None)
    return path


__all__ = ["Tracer", "Span", "recent_spans", "wire_events", "merge_events",
           "write_chrome_trace", "ANNOTATION_PREFIX", "PID_ENGINE",
           "PID_WIRE", "PID_SERVING"]
