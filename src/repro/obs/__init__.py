"""repro.obs — observability for the compiled multi-hospital engine.

Six layers, threaded through the strategy stack (DESIGN.md §12):

  * ``telemetry``  — in-program metric taps riding the engine's scans
                     (``Telemetry`` spec; per-round x per-hospital stats).
  * ``trace``      — host-side span tree of each run (with its counters,
                     mirrored into the profiler as annotations) merged
                     with ``wire.simulator`` transfer timelines into one
                     Chrome-trace/Perfetto JSON.
  * ``scopes``     — the named scopes of the compiled programs (model
                     segments, cut link, update) and their device time.
  * ``compile_log``— every trace, lowering and backend compile by
                     function (installed on import).
  * ``profile``    — ``jax.profiler`` wrapper + compile-time / dispatch /
                     HLO-cost capture via ``launch.hlo_analysis``.
  * ``report``     — ``RUNLOG_*.json`` + markdown run reports.
"""

from repro.obs.telemetry import (RoundTelemetry, RunTelemetry, Telemetry,
                                 as_telemetry)
from repro.obs.trace import (PID_SERVING, Tracer, merge_events,
                             recent_spans, wire_events, write_chrome_trace)
from repro.obs import compile_log
from repro.obs.profile import cost_summary, hlo_cost, jax_profile
from repro.obs.report import render_markdown, write_runlog

__all__ = ["Telemetry", "RoundTelemetry", "RunTelemetry", "as_telemetry",
           "Tracer", "merge_events", "recent_spans", "wire_events",
           "write_chrome_trace", "PID_SERVING", "cost_summary", "hlo_cost",
           "jax_profile", "render_markdown", "write_runlog", "compile_log"]

compile_log.install()
