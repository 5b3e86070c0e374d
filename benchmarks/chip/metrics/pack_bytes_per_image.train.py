"""Bytes the strategy packed on the host per training image of the traced
window: the byte counters (``bytes_<data key>``) of the program's ``pack``
spans (``Strategy.run`` -> ``engine.pack_run``) that lie in the window,
read from the span log of ``repro.obs.trace``, over the window's images.
Silent where the program keeps no span log or no such counter."""


def read(rec):
    try:
        from repro.obs.trace import recent_spans
    except ImportError:
        return None
    w0, w1 = rec["span"]
    counts = [v for s in recent_spans(w0, w1) if s.name == "pack"
              for k, v in s.args.items() if k.startswith("bytes_")]
    if not counts or rec["images"] <= 0:
        return None
    return sum(counts) / rec["images"]
