"""Named scopes of the compiled training programs, and their device time.

``SplitAdapter.full_loss`` runs each model segment under
``jax.named_scope(<segment>)`` (``front``, ``middle``, ``tail``) and the
cut-layer link under ``cut``; the step functions and the whole-run
programs run optimizer updates, the server-gradient mean and every
client average under ``update``.  The scopes are HLO metadata only: an
instruction's ``metadata={op_name="jit(sflv3_run)/while/body/.../
transpose(jvp(front))/conv_general_dilated"}`` names its scope, and
autodiff's ``jvp(...)``/``transpose(...)`` wrappers are stripped, so the
backward pass counts to its segment.  What a ``jax.checkpoint`` recomputes
in the backward pass (JAX names it ``rematted_computation``) is scope
``remat`` whatever segment holds it, so the cost of rematerialisation
reads apart from the forward pass it repeats.

``op_scopes`` maps a compiled module's instruction names (what a device
trace names its operations) to their scope; ``traced_scope_seconds``
sums a traced window's device seconds per scope over the programs its
``enqueue`` spans called.
"""

from __future__ import annotations

import collections
import re

SEGMENTS = ("front", "middle", "tail")
CUT = "cut"
UPDATE = "update"
REMAT = "remat"
SCOPES = SEGMENTS + (CUT, UPDATE, REMAT)
_REMATTED = "rematted_computation"     # JAX's name for the recomputation

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAP = re.compile(r"^[\w\-]+\((.*)\)$")


def _unwrap(part: str) -> str:
    """``transpose(jvp(front))`` -> ``front``."""
    m = _WRAP.match(part)
    while m:
        part = m.group(1)
        m = _WRAP.match(part)
    return part


def scope_of(op_name: str) -> str | None:
    """``remat`` for a checkpoint's recomputation, else the innermost of
    ``SCOPES`` in an ``op_name`` path, else None."""
    parts = op_name.split("/")
    if _REMATTED in parts:
        return REMAT
    for part in reversed(parts):
        base = _unwrap(part)
        if base in SCOPES:
            return base
    return None


def op_scopes(hlo_text: str) -> dict:
    """Instruction name -> scope for every scoped instruction of an HLO
    module's text (``Compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            scope = scope_of(m.group(2))
            if scope is not None:
                out[m.group(1)] = scope
    return out


_PROGRAM_SCOPES: collections.OrderedDict = collections.OrderedDict()
_KEPT_PROGRAMS = 8


def program_op_scopes(fn, args) -> dict:
    """``op_scopes`` of the compiled program ``fn`` at ``args`` (abstract
    avals are enough).  Lowers and compiles again (the compile is served
    by the persistent cache when one is set); the last few programs'
    maps are kept."""
    key = (fn, str(args))
    if key not in _PROGRAM_SCOPES:
        _PROGRAM_SCOPES[key] = op_scopes(fn.lower(*args).compile().as_text())
        while len(_PROGRAM_SCOPES) > _KEPT_PROGRAMS:
            _PROGRAM_SCOPES.popitem(last=False)
    return _PROGRAM_SCOPES[key]


def scope_seconds(ops: dict, scopes: dict) -> dict:
    """Seconds per scope of ``ops`` (operation name -> device seconds),
    with the unscoped remainder under None."""
    out = {s: 0.0 for s in SCOPES}
    out[None] = 0.0
    for name, sec in ops.items():
        out[scopes.get(name)] += sec
    return out


def traced_scope_seconds(ops: dict, t0: float, t1: float) -> dict | None:
    """``scope_seconds`` of a traced window ``[t0, t1]`` (``perf_counter``
    seconds): the scopes come from the programs the window's ``enqueue``
    spans called.  None when no such span is in the span log."""
    from repro.obs.trace import recent_spans
    programs = {id(sp.program[0]): sp.program
                for sp in recent_spans(t0, t1)
                if sp.name == "enqueue" and sp.program is not None}
    if not programs:
        return None
    scopes = {}
    for fn, args in programs.values():
        scopes.update(program_op_scopes(fn, args))
    return scope_seconds(ops, scopes)


__all__ = ["SEGMENTS", "CUT", "UPDATE", "REMAT", "SCOPES", "scope_of",
           "op_scopes", "program_op_scopes", "scope_seconds",
           "traced_scope_seconds"]
