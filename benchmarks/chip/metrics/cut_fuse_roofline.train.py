"""The fused int8 cut-layer kernel (``kernels/cut_fuse``) against its
roofline: the least time its bytes and operations allow (the larger of
bytes over HBM bandwidth and operations over peak; the bytes bound it)
over its summed device time in the traced window.  Bytes and operations
come from the cut's shapes (``flops.cut_elements``): every element that
crosses the cut, once per training image, in the forward pass."""

from chip.flops import CUT_BYTES_PER_ELEMENT, CUT_FLOPS_PER_ELEMENT

KERNEL = "roundtrip"


def read(rec):
    seconds = rec["trace"]["kernels"].get(KERNEL, 0.0)
    if seconds <= 0:
        return None
    elements = rec["images"] * rec["cut_elements_per_image"]
    p = rec["peaks"]
    least = max(elements * CUT_BYTES_PER_ELEMENT / p["hbm_bytes_per_s"],
                elements * CUT_FLOPS_PER_ELEMENT / p["flops_bf16"])
    return 100.0 * least / seconds
