"""``chip_smoke.py`` rehearsed on the CPU at a tiny width.

The script's phases run here exactly as on the chip, only with a small
DenseNet and the Pallas kernels in interpret mode (so the lowered run
programs hold no ``tpu_custom_call``; the tests stub that count); its
device gate must refuse the CPU before any phase runs.
"""

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY = ("DenseNetConfig(growth=4, blocks=(1, 1), stem_ch=8, cut_layer=2)")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # its dataclasses resolve here
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    from repro.models.cnn import DenseNetConfig
    return smoke.Sizes(model=DenseNetConfig(growth=4, blocks=(1, 1),
                                            stem_ch=8, cut_layer=2),
                       image=16, n_clients=3)


def test_main_refuses_cpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "platform 'cpu'" in str(exc.value.code)
    out = capsys.readouterr().out
    assert '"ok"' not in out          # no result line without a chip
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        smoke.main(["--four-chips"])


def test_one_chip_phases_tiny(smoke, tiny, capsys, monkeypatch):
    lowered = []
    real = smoke.custom_calls

    def interpret_calls(strat):
        # interpret-mode kernels lower to plain HLO: lower the run program
        # all the same, and stand in for the chip's custom-call count
        lowered.append(real(strat))
        return 1

    monkeypatch.setattr(smoke, "custom_calls", interpret_calls)
    smoke.one_chip(tiny)
    assert lowered == [0, 0]                  # fl and sflv3_ac, lowered
    out = capsys.readouterr().out
    for tag in ("train fl:", "train sl_am:", "train sflv3_ac:",
                "refs kernels:", "refs stepwise:", "serve sflv3_ac"):
        assert tag in out, out
    assert "dispatches=1 " in out and "n_compiles=4->4" in out
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.strip().splitlines()[-1])


def four_virtual_devices(code):
    """Run ``code`` after ``import chip_smoke as s`` in a child process
    with 4 virtual CPU devices (the device count is fixed at JAX
    start-up) and a tiny ``sizes``; return its stdout."""
    code = (
        "import os, sys;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=4';"
        f"sys.path.insert(0, {str(ROOT)!r});"
        "import jax; import chip_smoke as s;"
        "from repro.models.cnn import DenseNetConfig;"
        f"sizes = s.Sizes(model={TINY}, image=16);" + code)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_four_chips_phase_tiny_on_4_virtual_devices():
    """The ``--four-chips`` phase on 4 virtual CPU devices: shard=True
    matches shard=False and the run's [C, ...] arrays hold two hospitals
    on each of the 4 devices."""
    out = four_virtual_devices("s.four_chips(sizes)")
    lines = [ln for ln in out.splitlines() if ln.startswith("four_chips")]
    assert [ln.split()[1] for ln in lines] == ["fl", "sflv3_ac"], out
    for line in lines:
        assert "c_pad=8 hospitals_per_chip=2 device_set=4" in line, line
    assert "run_arrays=[(4, 'replicated'), (4, 2)]" in lines[0], lines[0]
    assert "link=identity" in lines[1] and "run_arrays=[(4, 2)]" in lines[1]


def test_four_chips_bound_refuses_a_run_without_the_cross_chip_sum():
    """Plant the fault the parity bound is there to catch: each device's
    server update uses only its own hospitals' gradients (the ``psum``
    across chips dropped).  The check must refuse it."""
    out = four_virtual_devices(
        "jax.lax.psum = lambda x, axis_name, **kw: x;"
        "clients = s.make_clients(sizes);\n"
        "try:\n"
        "    s.shard_parity('sflv3_ac', clients, sizes, s.CompileClock())\n"
        "except AssertionError as e:\n"
        "    print('refused:', e)\n")
    assert "refused: sflv3_ac: shard=True off shard=False" in out, out
