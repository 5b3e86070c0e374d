"""Each per-layer metric's reader on a hand-made record."""

import pytest

from chip import harness
from chip.peaks import PEAKS, peaks

V5E = PEAKS["TPU v5 lite"]


def reader(name):
    return harness.Library().module("metrics", name).read


def rec(**kw):
    base = {"span": (10.0, 12.0), "host_spans": [], "images": 400,
            "chips": 1, "peaks": V5E, "flops_per_image": 5e9,
            "cut_elements_per_image": 500_000,
            "trace": {"busy_s": 1.5, "window_s": 2.0, "kernels": {}}}
    base.update(kw)
    return base


def test_pack_share_clips_spans_to_the_window():
    r = rec(host_spans=[("pack", 9.5, 10.5), ("run", 9.0, 13.0),
                        ("pack", 11.0, 11.2)])
    assert reader("pack_share.train")(r) == pytest.approx(35.0)


def test_device_idle():
    assert reader("device_idle.train")(rec()) == pytest.approx(25.0)


def test_mfu_train_counts_three_passes_over_the_busy_time():
    # 400 images, 3 x 5 GFLOP each, over 1.5 busy seconds at 197 TFLOP/s
    assert reader("mfu.train")(rec()) == pytest.approx(
        100 * 400 * 3 * 5e9 / 1.5 / 197e12)


def test_cut_fuse_roofline_is_silent_without_the_kernel():
    assert reader("cut_fuse_roofline.train")(rec()) is None
    r = rec(trace={"busy_s": 1, "window_s": 2,
                   "kernels": {"roundtrip": 0.01}})
    least = 400 * 500_000 * 8 / 819e9
    assert reader("cut_fuse_roofline.train")(r) == pytest.approx(
        100 * least / 0.01)


def test_an_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        peaks("TPU v9000")
