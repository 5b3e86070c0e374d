"""Device microseconds per training image of the traced window that the
backward pass spends recomputing what ``jax.checkpoint`` did not keep:
the window's device seconds of the operations that ``repro.obs.scopes``
names ``remat`` in the compiled programs the window's ``enqueue`` spans
called, over the window's images.  Silent where the program names no
such scope or recomputes nothing."""


def read(rec):
    try:
        from repro.obs.scopes import traced_scope_seconds
    except ImportError:
        return None
    seconds = traced_scope_seconds(rec["trace"]["ops"], *rec["span"])
    if not seconds or seconds.get("remat", 0.0) <= 0 or rec["images"] <= 0:
        return None
    return 1e6 * seconds["remat"] / rec["images"]
