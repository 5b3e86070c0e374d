"""Compile log: which function JAX traced, lowered and compiled, and when.

One set of ``jax.monitoring`` listeners, installed once when ``repro.obs``
is imported (the strategies import it, so before any set-up jit runs),
keeps a bounded in-memory log of every

  * ``trace``   — ``/jax/core/compile/jaxpr_trace_duration``,
  * ``lower``   — ``/jax/core/compile/jaxpr_to_mlir_module_duration``,
  * ``backend`` — ``/jax/core/compile/backend_compile_duration`` (an XLA
    compile, or a load from the persistent compile cache: ``cache_hit``),

as an ``Entry`` of the function's name, its ``time.perf_counter`` start
and its duration.  JAX announces each of these phases when it starts (a
scalar event) and when it ends (a duration event); only the outermost of
nested phases is logged, so the jitted ``jax.numpy`` helpers a program
traces through while it is traced or lowered do not bury the program
itself.  Each
entry is also forwarded to the tracers attached
to a strategy (``attach``) as a ``compile.<event>`` span, so a trace
shows which function (re)compiled inside which run phase.  A program that
is already compiled logs nothing when it runs again.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import threading
import time

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_HIT = "/jax/compilation_cache/cache_hits"
MAX_ENTRIES = 1024


@dataclasses.dataclass(frozen=True)
class Entry:
    event: str            # "trace" | "lower" | "backend"
    fun_name: str         # the jitted function's name, ``jit_`` dropped
    start: float          # time.perf_counter() seconds
    duration: float
    cache_hit: bool       # backend: loaded from the persistent cache

    @property
    def end(self) -> float:
        return self.start + self.duration


_log: collections.deque = collections.deque(maxlen=MAX_ENTRIES)
_tracers: list = []
_local = threading.local()
_installed = False


_JIT_NAME = re.compile(r"^jit[_(](.*?)\)?$")


def _name(fun_name) -> str:
    """``jit(f)`` / ``jit_f`` (lowering, backend compile) -> ``f``."""
    m = _JIT_NAME.match(str(fun_name))
    return m.group(1) if m else str(fun_name)


def _on_event(event, **_):
    if event == CACHE_HIT:
        _local.cache_hit = True


def _on_start(event, value, **_):
    if event in EVENTS:
        _local.depth = getattr(_local, "depth", 0) + 1


def _on_duration(event, duration, **kwargs):
    kind = EVENTS.get(event)
    if kind is None:
        return
    end = time.perf_counter()
    _local.depth = max(getattr(_local, "depth", 0) - 1, 0)
    hit = False
    if kind == "backend":
        hit = getattr(_local, "cache_hit", False)
        _local.cache_hit = False
    if _local.depth:
        return                    # nested inside another logged phase
    entry = Entry(kind, _name(kwargs.get("fun_name", "")),
                  end - duration, float(duration), hit)
    _log.append(entry)
    for tracer in list(_tracers):
        tracer.add(f"compile.{kind}", entry.start, entry.end,
                   fun_name=entry.fun_name, cache_hit=hit)


def install() -> None:
    """Register the listeners (once per process)."""
    global _installed
    if _installed:
        return
    import jax
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _installed = True


def entries(since: float | None = None) -> list:
    """Logged entries, oldest first (those that started at or after
    ``since``, a ``perf_counter`` reading, when given)."""
    return [e for e in list(_log) if since is None or e.start >= since]


def attach(tracer) -> None:
    if tracer not in _tracers:
        _tracers.append(tracer)


def detach(tracer) -> None:
    if tracer in _tracers:
        _tracers.remove(tracer)


__all__ = ["Entry", "EVENTS", "install", "entries", "attach", "detach"]
