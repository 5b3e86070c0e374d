"""Operation and cut counts from shapes, at the cells' real sizes (shapes
only: nothing is computed)."""

import json

import pytest

from chip import flops
from chip.harness import HERE, Library


@pytest.mark.parametrize("config,gflop,cut", [
    ("densenet121", 5.509, 501_760)])
def test_forward_flops_and_cut_elements(config, gflop, cut):
    lib = Library()
    cfg = lib.json("configs", config)
    fam = lib.module("families", cfg["family"])
    f = flops.model_flops_per_image(fam, cfg["model"], cfg["image_size"])
    assert f / 1e9 == pytest.approx(gflop, abs=5e-3)
    assert flops.cut_elements(fam, cfg["model"], cfg["image_size"]) == cut


def test_counts_a_product_and_a_grouped_convolution():
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((2, 8, 8, 4), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 1, 4), jnp.float32)
    m = jax.ShapeDtypeStruct((4, 5), jnp.float32)

    def f(x, w, m):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=4)
        return y.mean(axis=(1, 2)) @ m

    # depthwise: 2*8*8*4 outputs x 9 taps x 1 input channel; product 2x4x5
    assert flops.forward_flops(f, x, w, m) == 2 * (2 * 8 * 8 * 4 * 9) \
        + 2 * (2 * 5 * 4)


def test_config_files_name_their_source_and_cuts():
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((HERE.parents[1] / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        assert set(cfg["assumed"])
