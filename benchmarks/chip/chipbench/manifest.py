"""The benchmark as data: ``BENCHMARK.json`` at the repository root names
the cells and metrics; each configuration, traffic mix, cell and
per-layer metric lives in a file of its own under ``benchmarks/chip``,
found by its name. Adding one is adding a file and an entry."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Callable, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict       # configs/<config>.json
    traffic: dict      # traffic/<traffic>.json
    limits: dict       # workloads/<name>.json["limits"]
    end_to_end: list   # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list    # BENCHMARK.json per_layer entries this cell reports


class Manifest:
    def __init__(self, root: pathlib.Path = ROOT) -> None:
        self.root = pathlib.Path(root)
        self.bench_dir = self.root / BENCH_DIR.relative_to(ROOT)
        self.data = _load_json(self.root / "BENCHMARK.json")

    def names(self, key: str) -> list[str]:
        return [e["name"] for e in self.data[key]]

    def _entry(self, key: str, name: str) -> dict:
        for e in self.data[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r}")

    @staticmethod
    def reports(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        conf = self._entry("configs", w["config"])
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=_load_json(self.root / conf["file"]),
            traffic=_load_json(self.bench_dir / "traffic" / f"{w['traffic']}.json"),
            limits=_load_json(self.bench_dir / "workloads" / f"{name}.json")["limits"],
            end_to_end=[m for m in self.data["end_to_end"]
                        if self.reports(m, name)],
            per_layer=[m for m in self.data["per_layer"]
                       if self.reports(m, name)],
        )

    def reader(self, metric: str) -> Callable:
        """``read(ctx) -> float | None`` of ``metrics/<metric>.py``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load_peaks(device_kind: str, path: Optional[pathlib.Path] = None) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown device
    is an error, never a default."""
    table = _load_json(path or BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
