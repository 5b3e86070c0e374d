"""The harness: found by name, refuses what is not a chip, and takes a
configuration, a mix and a metric that are only added files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chip import harness
from chip.conftest import CPU, HERE

ROOT = HERE.parents[1]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "densenet121.sflv3_int8", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_the_cpu_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_needs_the_program(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_every_cell_finds_its_files(bench):
    lib = harness.Library()
    for cell in bench["workloads"]:
        cfg = lib.json("configs", cell["config"])
        lib.module("families", cfg["family"])
        mix = lib.json("mixes", cell["traffic"])
        lib.module("drivers", mix["driver"])
        assert set(lib.json("limits", cell["name"]))
    for m in bench["per_layer"]:
        assert callable(lib.module("metrics", m["name"]).read)


def test_added_files_make_a_new_cell_and_metric(tmp_path, tiny, bench):
    """A configuration, a mix, a limit file and a per-layer metric that
    exist only as new files in another directory are found by name."""
    _, tiny_lib = tiny
    for sub in ("configs", "mixes", "limits", "metrics"):
        (tmp_path / sub).mkdir()
    cfg = tiny_lib.json("configs", "tiny_densenet121")
    cfg["model"]["growth"] = 6
    (tmp_path / "configs" / "wider.json").write_text(json.dumps(cfg))
    mix = tiny_lib.json("mixes", "tiny_sflv3_int8")
    mix["train_per_client"] = [8, 8]
    (tmp_path / "mixes" / "two.json").write_text(json.dumps(mix))
    (tmp_path / "limits" / "wider.two.json").write_text(
        json.dumps({"loss_gap": 1e-4}))
    (tmp_path / "metrics" / "images.added.py").write_text(
        "def read(rec):\n    return float(rec['images'])\n")
    new = json.loads(json.dumps(bench))
    new["workloads"] = [{"name": "wider.two", "config": "wider",
                         "traffic": "two", "chips": 1}]
    for m in new["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = (["wider.two"]
                              if m["name"] == "train_images_per_s" else [])
    new["per_layer"] = [{"name": "images.added", "unit": "images",
                         "workloads": ["wider.two"]}]
    lib = harness.Library([HERE, *tiny_lib.dirs[1:], tmp_path])
    out = harness.run_cell(new, "wider.two", 11, 0.2, False, lib=lib,
                           device=dict(CPU))
    assert out["correct"] is True
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"train_images_per_s", "setup_s"}
    readers = harness._readers(new, new["workloads"][0], lib)
    assert [m["name"] for m, _ in readers] == ["images.added"]
    assert readers[0][1].read({"images": 3}) == 3.0


def test_unknown_workload_exits(bench):
    with pytest.raises(SystemExit):
        harness.run_cell(bench, "no.such", 1, 1, False, device=dict(CPU))
