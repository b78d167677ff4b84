"""Find a cell's files by name.

BENCHMARK.json lists configurations, cells and metrics; everything that
belongs to one of them is a file of its own under this directory:

  configs/<config>.json        sizes, precision, public points and limits
  references/<reference>.py    the plain reference a configuration names
  traffic/<traffic>.json       a traffic mix's parameters, with the name
                               of the driver that runs them
  drivers/<driver>.py          a driver: class Driver(mix, cfg, x, y, seed,
                               key) with warm_up(), call() and last(), and
                               optionally window(drv, seconds, mix), the
                               measured window (run.closed_loop if absent)
  metrics/<metric>.py          a metric's reader, read(ctx); a metric
                               `<q>.<part>` with no file of its own is read
                               by metrics/<q>.py

An end-to-end reader's `ctx` holds what the window returned: `setup_s`,
`window_s`, `calls`, `work` (iterations or fits completed), `durations`
(seconds per call), with the cell's `cfg` and `mix`.  A per-layer reader's
`ctx` holds the traced run's `trace` (xplane.Trace), the modules `xplane`
and `counts`, `cfg`, `mix`, `work` (traced) and `device_kind`.  `read`
returns a number, or None where there is nothing to read.

A new configuration, mix, driver or metric is a new file and a new entry:
nothing that is there needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


class Catalog:
    def __init__(self, spec: dict, files: Path = HERE):
        self.spec = spec
        self.files = Path(files)

    @classmethod
    def load(cls) -> "Catalog":
        return cls(json.loads((REPO / "BENCHMARK.json").read_text()))

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        known = ", ".join(c["name"] for c in self.spec["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")

    def config(self, name: str) -> dict:
        return json.loads(self._file("configs", name, ".json").read_text())

    def traffic(self, name: str) -> dict:
        return json.loads(self._file("traffic", name, ".json").read_text())

    def reference(self, name: str):
        return self._module("references", name)

    def driver(self, name: str):
        return self._module("drivers", name)

    def reader(self, metric: str):
        own = self.files / "metrics" / f"{metric}.py"
        return self._module("metrics", metric if own.is_file()
                            else metric.rsplit(".", 1)[0])

    def metrics(self, section: str, cell: dict) -> list:
        """The entries of `section` ("end_to_end" or "per_layer") that
        `cell` reports: those listing it, and those with no list whose
        moved metric the cell reports."""
        e2e = {m["name"] for m in self._reported("end_to_end", cell)}
        if section == "end_to_end":
            return self._reported("end_to_end", cell)
        return [m for m in self.spec["per_layer"]
                if cell["name"] in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in e2e)]

    def _reported(self, section: str, cell: dict) -> list:
        return [m for m in self.spec[section]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def _file(self, kind: str, name: str, suffix: str) -> Path:
        path = self.files / kind / f"{name}{suffix}"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
        return path

    def _module(self, kind: str, name: str):
        path = self._file(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name}".replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
