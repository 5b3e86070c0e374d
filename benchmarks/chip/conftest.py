"""Tiny stand-ins for the benchmark's cells, for the CPU rehearsal tests.

Each tiny cell keeps its real cell's mix, shrinks the model and the
traffic, and has limits of its own, set as the real ones are: between
the program's readings and the ``high3`` control's at the tiny size on
the CPU (seeds 2**31+3, 2**31+7, 11, 12): first-step loss gap, program
at most 1.1920928955078125e-07, control at least 2.4437904357910156e-06;
The files live in a temporary directory that the
harness searches after this one, as a later PR's added files would.
"""

import json
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

TINY_MODELS = {     # (model, image size)
    "densenet121": ({"growth": 4, "blocks": [2, 2], "stem_ch": 8,
                     "compression": 0.5, "in_ch": 1, "n_classes": 1,
                     "cut_layer": 2}, 16),
}
TINY_LIMITS = {
    "densenet121.sflv3_int8": {"first_loss_gap": 1e-6, "change_gap": 0.3},
}
TINY_MIXES = {
    "sflv3_int8": {"train_per_client": [24, 16, 8], "batch": 4},
}


@pytest.fixture(scope="session")
def bench():
    return json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())


# the benchmark's cells, with their end-to-end metrics
CELLS = {"densenet121.sflv3_int8": ["train_images_per_s"]}


@pytest.fixture(scope="session")
def tiny(tmp_path_factory, bench):
    """``(bench, library)`` in which every cell ``<c>.<m>`` of ``CELLS``
    has a tiny twin ``tiny_<c>.tiny_<m>`` on ``TINY_LIMITS``."""
    from chip import harness
    d = tmp_path_factory.mktemp("tiny_lib")
    for sub in ("configs", "mixes", "limits"):
        (d / sub).mkdir()
    for name, (model, size) in TINY_MODELS.items():
        cfg = json.loads((HERE / "configs" / f"{name}.json").read_text())
        cfg.update(model=model, image_size=size)
        (d / "configs" / f"tiny_{name}.json").write_text(json.dumps(cfg))
    for name, change in TINY_MIXES.items():
        mix = json.loads((HERE / "mixes" / f"{name}.json").read_text())
        mix.update(change)
        (d / "mixes" / f"tiny_{name}.json").write_text(json.dumps(mix))
    tiny_bench = json.loads(json.dumps(bench))
    e2e = {m["name"]: m for m in tiny_bench["end_to_end"]}
    for name, metrics in CELLS.items():
        config, traffic = name.split(".")
        t = f"tiny_{name}"
        tiny_bench["workloads"].append(
            {"name": t, "config": f"tiny_{config}",
             "traffic": f"tiny_{traffic}", "chips": 1})
        (d / "limits" / f"{t}.json").write_text(json.dumps(TINY_LIMITS[name]))
        for m in metrics:
            e2e[m]["workloads"].append(t)
    return tiny_bench, harness.Library([HERE, d])
