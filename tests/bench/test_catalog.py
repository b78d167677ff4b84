"""Every cell resolves by name, new files are found without an edit, and
the run command refuses a host with no TPU."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from benchmarks.chip import catalog, run

SPEC = catalog.Catalog.load().spec
CELLS = [c["name"] for c in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_has_the_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (catalog.REPO / p).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cat = catalog.Catalog.load()
    cell = cat.cell(name)
    cfg = cat.config(cell["config"])
    assert cfg["name"] == cell["config"]
    mix = cat.traffic(cell["traffic"])
    assert hasattr(cat.driver(mix["driver"]), "Driver")
    assert hasattr(cat.reference(cfg["reference"]), "Reference")
    e2e = [m["name"] for m in cat.metrics("end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert all(callable(cat.reader(n).read) for n in e2e)
    layer = cat.metrics("per_layer", cell)
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        assert callable(cat.reader(m["name"]).read)
    entry = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    assert (catalog.REPO / entry["file"]).is_file()


PACED_WINDOW = """

def window(drv, seconds, mix):
    # open loop: one call due every gap_s; a call's duration counts from
    # when it was due
    import time
    t0 = time.perf_counter()
    took, work = [], 0
    while not took or time.perf_counter() - t0 < seconds:
        due = t0 + len(took) * mix["gap_s"]
        time.sleep(max(0.0, due - time.perf_counter()))
        work += drv.call()
        took.append(time.perf_counter() - due)
    return {"window_s": time.perf_counter() - t0, "calls": len(took),
            "work": work, "durations": took}
"""


def test_new_files_are_found_without_an_edit(tmp_path, tiny_catalog):
    """A configuration, a traffic mix with a driver and window of its own,
    an end-to-end metric and a per-layer metric, each added as a file with
    an entry, are found and run through the harness."""
    spec = json.loads(json.dumps(tiny_catalog.spec))
    files = tiny_catalog.files
    cfg = tiny_catalog.config("tiny")
    cfg["name"] = "newcfg"
    new = tmp_path / "files"
    for kind in ("configs", "traffic", "drivers", "metrics", "references"):
        (new / kind).mkdir(parents=True)
        for f in (files / kind).iterdir():
            (new / kind / f.name).write_bytes(f.read_bytes())
    (new / "configs" / "newcfg.json").write_text(json.dumps(cfg))
    (new / "traffic" / "newmix.json").write_text(json.dumps(
        {"driver": "paced", "iters_per_call": 50, "gap_s": 0.05,
         "traced_calls": 2}))
    (new / "drivers" / "paced.py").write_text(
        (files / "drivers" / "steps.py").read_text() + PACED_WINDOW)
    (new / "metrics" / "worst_call_ms.py").write_text(
        "def read(ctx):\n    return 1e3 * max(ctx.durations)\n")
    (new / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec["workloads"].append({"name": "newcfg.newmix", "config": "newcfg",
                              "traffic": "newmix", "chips": 1, "why": "t"})
    spec["end_to_end"].append({"name": "worst_call_ms", "unit": "ms",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["newcfg.newmix"]})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "device_trace",
                              "layer": "device", "moves": "worst_call_ms",
                              "workloads": ["newcfg.newmix"]})
    cat = catalog.Catalog(spec, new)
    cell = cat.cell("newcfg.newmix")
    assert cat.config("newcfg")["n_clients"] == cfg["n_clients"]
    assert [m["name"] for m in cat.metrics("per_layer", cell)] == \
        ["new_metric"]
    assert cat.reader("new_metric").read(None) == 42.0
    res = run.run_cell(cat, cell, 2**33 + 9, 0.3, False, time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"worst_call_ms", "setup_s"}
    assert res["metrics"]["worst_call_ms"]["value"] > 0
    with pytest.raises(FileNotFoundError):
        cat.config("absent")


def test_a_metric_part_falls_back_to_its_quantity(tiny_catalog):
    shared = tiny_catalog.reader("idle_share")
    for part in ("idle_share.steps", "idle_share.fit", "idle_share.serve"):
        assert tiny_catalog.reader(part).__doc__ == shared.__doc__
    with pytest.raises(FileNotFoundError):
        tiny_catalog.reader("no_such_metric.steps")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(catalog.HERE / "run.py"), "--workload", CELLS[0],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=catalog.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
