"""Share of the traced window the strategy spent packing batches on the
host (the program's ``pack`` spans, ``Strategy.run`` -> ``pack_run``)."""


def read(rec):
    w0, w1 = rec["span"]
    pack = sum(min(b, w1) - max(a, w0) for name, a, b in rec["host_spans"]
               if name == "pack" and b > w0 and a < w1)
    return 100.0 * pack / (w1 - w0)
