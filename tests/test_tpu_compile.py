"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The chip is described, not attached: the TPU compiler installed with JAX
compiles each kernel with ``interpret=False`` at the paper's real cut
shapes, so tiling and VMEM refusals surface here at no chip time.  Each
compile takes a second or two; whole-run programs (minutes each) are
compiled by hand before a chip run, not here.

The topology is described inside a module fixture — never at import —
because only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.act_compress.act_compress import (dequantize_pallas,
                                                     quantize_pallas)
from repro.kernels.cut_fuse.cut_fuse import (noise_roundtrip_pallas,
                                             roundtrip_pallas)
from repro.kernels.dp_clip.dp_clip import scale_accum_pallas, sqnorms_pallas

# (rows, features) of cut-layer activations, flattened as the ops layer
# hands them to the kernels: DenseNet-121 @224 after unit 4 at batch 8
# (8 x 56 x 56 rows, 160 ch), a 728-channel activation of 8 x 24 x 24
# rows, and the paper U-Net @768 at batch 4: its bottleneck (4 x 48 x 48
# rows, 728 ch) and its largest skip (4 x 768 x 768 rows, 64 ch)
CUT_SHAPES = {"densenet": (25088, 160), "unet": (4608, 728),
              "unet768_bottleneck": (9216, 728),
              "unet768_skip64": (2359296, 64)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
DP_BATCH = 8


@pytest.fixture(scope="module")
def topo():
    # without it the TPU compiler writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def compile_text(fn, sharding, *avals):
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in avals]
    return jax.jit(fn).lower(*specs).compile().as_text()


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("model", list(CUT_SHAPES))
@pytest.mark.parametrize("kernel", ["roundtrip", "noise_roundtrip",
                                    "quantize", "dequantize"])
def test_cut_kernels_compile_for_v5e(one_chip, kernel, model, dtype):
    t, d = CUT_SHAPES[model]
    dt = DTYPES[dtype]
    if kernel == "roundtrip":
        fn = lambda x: roundtrip_pallas(x, interpret=False)     # noqa: E731
        avals = [sds((t, d), dt)]
    elif kernel == "noise_roundtrip":
        fn = lambda x, z, w: noise_roundtrip_pallas(           # noqa: E731
            x, z, w, interpret=False)
        avals = [sds((t, d), dt), sds((t, d), jnp.float32),
                 sds((t, 1), jnp.float32)]
    elif kernel == "quantize":
        fn = lambda x: quantize_pallas(x, interpret=False)      # noqa: E731
        avals = [sds((t, d), dt)]
    else:
        fn = lambda q, s: dequantize_pallas(q, s, dt,           # noqa: E731
                                            interpret=False)
        avals = [sds((t, d), jnp.int8), sds((t, 1), jnp.float32)]
    assert "tpu_custom_call" in compile_text(fn, one_chip, *avals)


def densenet121_largest_leaf() -> int:
    from repro.configs.paper_models import DENSENET121_PAPER
    from repro.core.partition import cnn_adapter
    from repro.models.cnn import build_densenet
    adapter = cnn_adapter(build_densenet(DENSENET121_PAPER))
    shapes = jax.eval_shape(adapter.init, jax.random.key(0))
    return max(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


@pytest.mark.parametrize("kernel", ["sqnorms", "scale_accum"])
def test_dp_clip_compiles_for_v5e_at_densenet121_largest_leaf(one_chip,
                                                              kernel):
    d = densenet121_largest_leaf()
    assert d == 1024 * 512          # transition 3's 1x1 conv, 1024 -> 512
    g = sds((DP_BATCH, d), jnp.float32)
    if kernel == "sqnorms":
        txt = compile_text(lambda g: sqnorms_pallas(g, interpret=False),
                           one_chip, g)
    else:
        txt = compile_text(
            lambda g, s: scale_accum_pallas(g, s, interpret=False),
            one_chip, g, sds((DP_BATCH, 1), jnp.float32))
    assert "tpu_custom_call" in txt
