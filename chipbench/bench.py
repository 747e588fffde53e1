"""What a cell is made of, found by name: the benchmark's data files.

``BENCHMARK.json`` names every cell, configuration and metric. Each
name resolves to files of its own under ``chipbench/``:

- configuration ``c``: the file its ``configs`` entry names;
- traffic mix ``t``: ``traffic/<t>.json``, which names its driver
  (``drivers/<driver>.py``) and holds the mix's parameters;
- cell ``w``: ``limits/<w>.json``, the limits its correctness numbers
  are held to;
- per-layer metric ``n``: ``metrics/<n>.py``, whose ``read(reading)``
  returns the metric's value, or None where it finds nothing to read.

Nothing here knows a particular cell, so a later cell, mix, metric or
configuration is added as files and ``BENCHMARK.json`` entries alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import zlib
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    name = f"{name}_{zlib.crc32(str(path.resolve()).encode()):08x}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with every file it resolves to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: tuple  # BENCHMARK.json metric entries this cell reports
    per_layer: tuple

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


class Spec:
    """``BENCHMARK.json`` with the files its names resolve to under
    ``root`` (the checkout)."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "chipbench"
        self.data = load_json(self.root / "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic_path(self, name: str) -> Path:
        return self.bench / "traffic" / f"{name}.json"

    def limits_path(self, workload: str) -> Path:
        return self.bench / "limits" / f"{workload}.json"

    def metric_path(self, name: str) -> Path:
        return self.bench / "metrics" / f"{name}.py"

    def driver_path(self, name: str) -> Path:
        return self.bench / "drivers" / f"{name}.py"

    def reports(self, metric: dict, workload: str, e2e_names) -> bool:
        """Whether ``workload`` reports ``metric``: its ``workloads`` list
        where it has one; else every cell, for an end-to-end metric, and
        every cell that reports the end-to-end metric it moves, for a
        per-layer one."""
        if "workloads" in metric:
            return workload in metric["workloads"]
        if "moves" in metric:
            return metric["moves"] in e2e_names
        return True

    def cell(self, name: str) -> Cell:
        w = self.workload(name)
        entry = self.config_entry(w["config"])
        e2e = tuple(m for m in self.data["end_to_end"]
                    if self.reports(m, name, ()))
        names = {m["name"] for m in e2e}
        per_layer = tuple(m for m in self.data["per_layer"]
                          if self.reports(m, name, names))
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config_name=w["config"],
            config=load_json(self.root / entry["file"]),
            traffic_name=w["traffic"],
            traffic=load_json(self.traffic_path(w["traffic"])),
            limits=load_json(self.limits_path(name)),
            end_to_end=e2e,
            per_layer=per_layer,
        )

    def driver(self, cell: Cell):
        return load_module(self.driver_path(cell.driver),
                           f"chipbench_driver_{cell.driver}")

    def reader(self, metric: str):
        return load_module(self.metric_path(metric),
                           "chipbench_metric_" + metric.replace(".", "_"))

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self.bench / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device kind {device_kind!r} is not in chipbench/peaks.json"
            )
        return table["devices"][device_kind]
