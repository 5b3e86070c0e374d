"""Launch-layer tests: sharding rules, cache specs, HLO analyzer, and a
subprocess 512-device mesh construction check."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import analyze


class FakeMesh:
    """Duck-typed mesh for spec_for tests (no 256 devices needed)."""

    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.zeros(shape)


def test_spec_for_rules():
    from repro.launch.mesh import spec_for
    mesh = FakeMesh((16, 16), ("data", "model"))
    # ff -> model, embed -> data
    assert spec_for(("embed", "ff"), (1024, 4096), mesh) == P("data", "model")
    # indivisible vocab falls back to replicated (MiniCPM's 122753)
    assert spec_for(("embed", "vocab"), (2304, 122753), mesh) == P("data")
    # a mesh axis is never used twice in one spec
    assert spec_for(("ff", "ff"), (4096, 4096), mesh) == P("model")
    # clients axis consumes data; embed then falls to pod (absent) -> None
    assert spec_for(("clients", "embed", "ff"), (16, 1024, 4096), mesh) == \
        P("data", None, "model")


def test_spec_for_multipod_fsdp():
    from repro.launch.mesh import spec_for
    mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    # embed prefers data; with clients on data it falls to pod
    assert spec_for(("clients", "embed"), (32, 7168), mesh) == P("data", "pod")


def test_analyze_counts_scan_iterations():
    def scan6(x, ws):
        def body(c, w):
            return jnp.dot(c, w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    txt = jax.jit(scan6).lower(x, ws).compile().as_text()
    ana = analyze(txt)
    assert ana["flops"] == 6 * 2 * 64 ** 3
    # raw cost_analysis counts the body once — the analyzer must not
    ca = jax.jit(scan6).lower(x, ws).compile().cost_analysis()
    assert ca["flops"] < ana["flops"]


def test_analyze_collectives_zero_on_single_device():
    txt = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile().as_text()
    ana = analyze(txt)
    assert ana["collective_total"] == 0


def test_production_mesh_subprocess():
    """make_production_mesh needs 512 host devices; run in a fresh process."""
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=512';"
        "from repro.launch.mesh import make_production_mesh;"
        "m1=make_production_mesh();"
        "assert m1.devices.shape==(16,16) and m1.axis_names==('data','model');"
        "m2=make_production_mesh(multi_pod=True);"
        "assert m2.devices.shape==(2,16,16);"
        "assert m2.axis_names==('pod','data','model');"
        "print('MESH_OK')"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             # without this a TPU-plugin build polls cloud metadata forever
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        timeout=120)
    assert "MESH_OK" in out.stdout, out.stderr[-500:]


def test_cache_specs_shard_decode_batch():
    from repro.launch.specs import cache_specs
    from repro.models.transformer import ModelConfig, TransformerLM
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512,
                      cut_layer=1, remat=False)
    model = TransformerLM.build(cfg)
    mesh = FakeMesh((16, 16), ("data", "model"))
    shapes, shardings = cache_specs(model, "decode_32k", mesh, as_pspec=True)
    flat = jax.tree.leaves(shardings)
    assert len(flat) > 0
    # k/v leaves: stacked layer dim unsharded, batch on data, seq on model
    from jax.sharding import PartitionSpec as PS
    leaves = jax.tree.leaves(shardings, is_leaf=lambda v: isinstance(v, PS))
    kv = [s for s, l in zip(leaves, jax.tree.leaves(shapes))
          if len(l.shape) == 5]
    assert all(s[1] == "data" for s in kv)
    assert all(s[2] == "model" for s in kv)


def test_cache_specs_long500k_unshardable_batch():
    from repro.launch.specs import cache_specs
    from repro.models.transformer import ModelConfig, TransformerLM
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512,
                      cut_layer=1, remat=False)
    model = TransformerLM.build(cfg)
    mesh = FakeMesh((16, 16), ("data", "model"))
    shapes, shardings = cache_specs(model, "long_500k", mesh, as_pspec=True)
    from jax.sharding import PartitionSpec as PS
    leaves = jax.tree.leaves(shardings, is_leaf=lambda v: isinstance(v, PS))
    kv = [s for s, l in zip(leaves, jax.tree.leaves(shapes))
          if len(l.shape) == 5]
    # batch==1: replicate batch, shard seq over every axis
    assert all(s[1] is None for s in kv)
    assert all(s[2] == ("data", "model") for s in kv)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The persistent compile cache goes to $JAX_COMPILATION_CACHE_DIR when
    that is set (JAX reads it itself), else to the checkout's fixed
    ``.jax_cache`` — never a per-process path."""
    from repro.launch import compile_cache as CC
    root = Path(__file__).resolve().parents[1]
    assert CC.DEFAULT_DIR == root / ".jax_cache"
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path / "env_cache"))
    assert CC.enable_compile_cache() == str(tmp_path / "env_cache")
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv(CC.ENV_VAR)
    try:
        assert CC.enable_compile_cache() == str(CC.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CC.DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
