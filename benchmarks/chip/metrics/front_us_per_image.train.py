"""Device microseconds per training image of the traced window in the
``front`` segment scope: the window's device seconds of the operations
that ``repro.obs.scopes`` names ``front`` (forward and backward) in the
compiled programs the window's ``enqueue`` spans called, over the
window's images.  Silent where the program names no scopes."""


def read(rec):
    try:
        from repro.obs.scopes import traced_scope_seconds
    except ImportError:
        return None
    seconds = traced_scope_seconds(rec["trace"]["ops"], *rec["span"])
    if not seconds or seconds["front"] <= 0 or rec["images"] <= 0:
        return None
    return 1e6 * seconds["front"] / rec["images"]
