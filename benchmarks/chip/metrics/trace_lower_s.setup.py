"""Seconds set-up spent tracing and lowering the whole-run program(s) the
traced window ran: the ``trace`` and ``lower`` entries of ``repro.obs``'s
compile log for the functions the window's ``enqueue`` spans called, that
ended before the window.  Silent where the program keeps no compile log
or names no program."""


def read(rec):
    try:
        from repro.obs import compile_log
        from repro.obs.trace import recent_spans
    except ImportError:
        return None
    w0, w1 = rec["span"]
    programs = {s.args.get("program") for s in recent_spans(w0, w1)
                if s.name == "enqueue"} - {None}
    seconds = [e.duration for e in compile_log.entries()
               if e.event in ("trace", "lower") and e.fun_name in programs
               and e.end <= w0]
    return sum(seconds) if seconds else None
