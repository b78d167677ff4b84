"""Fixtures for the chip benchmark's CPU tests: a catalog whose cells run
a tiny configuration through the real harness files."""

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmarks.chip import catalog  # noqa: E402

TINY = {"n_clients": 7, "k": 2, "t": 1, "m": 16, "d": 24,
        "public_points": {"beta": 1, "alpha": 4, "lambda": 11}}


def tiny_config(name: str = "tiny") -> dict:
    cfg = json.loads((catalog.HERE / "configs" / "cifar10_case1.json")
                     .read_text())
    cfg.update(TINY, name=name)
    return cfg


@pytest.fixture(scope="session")
def tiny_catalog(tmp_path_factory):
    """The benchmark's traffic, driver, metric and reference files beside a
    tiny configuration, with one steps and one fit cell on it."""
    files = tmp_path_factory.mktemp("chipbench")
    for kind in ("traffic", "drivers", "metrics", "references"):
        shutil.copytree(catalog.HERE / kind, files / kind)
    (files / "configs").mkdir()
    (files / "configs" / "tiny.json").write_text(json.dumps(tiny_config()))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for traffic in ("steps", "fit"):
        name = f"tiny.{traffic}"
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": traffic, "chips": 1,
                                  "why": "CPU test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if any(w.endswith(f".{traffic}") for w in m.get("workloads", ())):
                m["workloads"].append(name)
    return catalog.Catalog(spec, files)
