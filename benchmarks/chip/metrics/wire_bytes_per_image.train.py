"""Cut-layer bytes on the wire per training image of the traced window:
the ``wire_bytes`` counters (both legs, as the link's codec sends them)
that the program's ``account`` spans (``Strategy.run`` ->
``Transport.account``) carry in the window, read from the span log of
``repro.obs.trace``, over the window's images.  Silent where the program
keeps no span log or sets no such counter."""


def read(rec):
    try:
        from repro.obs.trace import recent_spans
    except ImportError:
        return None
    w0, w1 = rec["span"]
    counts = [s.args["wire_bytes"] for s in recent_spans(w0, w1)
              if s.name == "account" and "wire_bytes" in s.args]
    if not counts or rec["images"] <= 0:
        return None
    return sum(counts) / rec["images"]
