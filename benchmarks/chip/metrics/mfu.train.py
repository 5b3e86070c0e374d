"""Model FLOP utilization of the device's busy time: the analytic
operations of the training images in the traced window (three forward
passes each, ``flops.TRAIN_PASSES``) over the seconds in which an
operation ran on the device (the profiler trace) and the chips' bf16
peak.  Host packing and idle time are left out, so it moves when the
engine program itself gets faster, apart from the end-to-end rate.  A
float32 cell at ``highest`` runs every product as several bf16 passes,
so its ceiling lies well under 100%."""

from chip.flops import TRAIN_PASSES


def read(rec):
    busy = rec["trace"]["busy_s"]
    if busy <= 0:
        return None
    flops = rec["images"] * TRAIN_PASSES * rec["flops_per_image"]
    return 100.0 * flops / busy / (rec["chips"] * rec["peaks"]["flops_bf16"])
