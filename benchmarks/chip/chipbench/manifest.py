"""The benchmark as data: ``BENCHMARK.json`` at the repository root names
the cells and metrics; each configuration, traffic mix, cell, per-layer
metric and runner (the program a configuration's cells run) lives in a
file of its own under ``benchmarks/chip``, found by its name. Adding one
is adding a file and an entry."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import types
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
    runner: types.ModuleType  # runners/<config["runner"]>.py


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
        """The cell's files, read and checked: its configuration names a
        runner that exists and accepts it, and its limits name exactly
        the runner's ``NUMBERS``."""
        w = self._entry("workloads", name)
        conf = self._entry("configs", w["config"])
        config = _load_json(self.root / conf["file"])
        if "runner" not in config:
            raise KeyError(f"configuration {conf['name']!r} names no runner")
        runner = self.runner(config["runner"])
        runner.check_config(config)
        limits = _load_json(
            self.bench_dir / "workloads" / f"{name}.json")["limits"]
        if set(limits) != set(runner.NUMBERS):
            raise ValueError(f"cell {name!r} has limits for {sorted(limits)}; "
                             f"its runner compares {list(runner.NUMBERS)}")
        return Cell(
            name=name,
            chips=int(w["chips"]),
            config=config,
            traffic=_load_json(self.bench_dir / "traffic" / f"{w['traffic']}.json"),
            limits=limits,
            end_to_end=[m for m in self.data["end_to_end"]
                        if self.reports(m, name)],
            per_layer=[m for m in self.data["per_layer"]
                       if self.reports(m, name)],
            runner=runner,
        )

    def reader(self, metric: str) -> Callable:
        """``read(ctx) -> float | None`` of ``metrics/<metric>.py``."""
        return self._module("metrics", metric).read

    def runner(self, name: str) -> types.ModuleType:
        """``runners/<name>.py``: ``NUMBERS``, ``check_config(cfg)``,
        ``build(cell, seed)`` and ``check(cell, seed, outputs)`` (see
        ``chipbench.harness``)."""
        return self._module("runners", name)

    def _module(self, kind: str, name: str) -> types.ModuleType:
        """``<kind>/<name>.py`` under the benchmark, loaded by its path."""
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no {kind}/{name}.py in {self.bench_dir}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def load_peaks(device_kind: str, path: Optional[pathlib.Path] = None) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown device
    is an error, never a default."""
    table = _load_json(path or BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
