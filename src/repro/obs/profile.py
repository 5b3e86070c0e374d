"""Profiling — compile time, dispatch counts, and HLO cost per strategy.

Three independent captures feeding the per-strategy cost summary (the
ROADMAP's roofline item):

  * ``jax_profile(dir)``: context manager around ``jax.profiler`` device
    tracing (TensorBoard/Perfetto dump); raises when the profiler cannot
    start.
  * ``hlo_cost(strategy)``: every compiled ``Strategy.run`` stashes its
    jitted whole-run callable + concrete args as
    ``strategy._last_run_invocation``; this re-lowers it (timed — on a
    warm jit cache the *first* call's compile dominates, so the AOT
    ``lower().compile()`` here measures a fresh backend compile), then
    feeds the optimized HLO text through ``launch.hlo_analysis.analyze``
    for flop / HBM-byte / collective estimates (while-loop trip counts
    included, so a whole-run program's cost covers every round).
  * dispatch counts: ``Strategy._dispatches`` tallies every
    host->device training-program invocation (compiled epoch/run calls,
    stepwise per-batch steps) — the compiled whole-run path shows 1 per
    ``run``, the stepwise oracle shows one per mini-batch.
"""

from __future__ import annotations

import contextlib
import time


@contextlib.contextmanager
def jax_profile(trace_dir):
    """Wrap a block in ``jax.profiler`` device tracing writing to
    ``trace_dir`` (viewable in TensorBoard / Perfetto).  A profiler that
    cannot start or stop raises: a trace that silently never gets written
    would leave the run looking traced when it was not."""
    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def hlo_cost(strategy) -> dict | None:
    """Compile-time + HLO cost model of the strategy's last compiled run
    program.  Returns None when the strategy has not dispatched a
    compiled run yet (stepwise engine, degenerate runs)."""
    inv = getattr(strategy, "_last_run_invocation", None)
    if inv is None:
        return None
    fn, args = inv
    from repro.launch import hlo_analysis
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    cost = hlo_analysis.analyze(compiled.as_text())
    # compiler's own buffer-assignment view: temp (peak scratch), argument,
    # output and donation-aliased bytes — the donated whole-run programs
    # show their HBM saving in alias_size vs argument_size
    ma = compiled.memory_analysis()
    mem = {f: int(getattr(ma, f))
           for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes")}
    return {"compile_seconds": compile_s, **cost, "memory": mem}


def cost_summary(strategy, wall_seconds: float | None = None,
                 total_steps: int | None = None) -> dict:
    """Per-strategy cost row: dispatch count, compile time, HLO flop/byte
    estimates, and steps/s when the caller timed the run."""
    out = {"strategy": strategy.name, "engine": strategy.engine,
           "dispatches": getattr(strategy, "_dispatches", 0),
           "run_calls": getattr(strategy, "_run_calls", 0)}
    hlo = hlo_cost(strategy)
    if hlo is not None:
        out["hlo"] = hlo
    if wall_seconds is not None:
        out["wall_seconds"] = wall_seconds
        if total_steps:
            out["steps_per_s"] = total_steps / max(wall_seconds, 1e-9)
    return out


__all__ = ["jax_profile", "hlo_cost", "cost_summary"]
