"""Finds a cell's files by name.

A cell ``<name>`` is ``workloads/<name>.json``; it names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``).
A configuration names its model family (``families/<family>.py``) and its
plain reference (``reference/<reference>.py``), and a metric is
``metrics/<metric>.py``. ``BENCHMARK.json`` at the root above this
directory says which metrics a cell reports. Nothing here lists cells,
families or metrics, so a new cell, even of a new family, is new files
only.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Optional

HERE = Path(__file__).resolve().parent


def _load(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    return json.loads(path.read_text())


class Bench:
    """The benchmark's files under ``root`` (the repository root)."""

    def __init__(self, root: Optional[Path] = None):
        self.root = Path(root) if root is not None else HERE.parent
        self.dir = self.root / HERE.name

    def config(self, name: str) -> dict:
        return _load(self.dir / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return _load(self.dir / "traffic" / f"{name}.json")

    def workload(self, name: str) -> dict:
        w = _load(self.dir / "workloads" / f"{name}.json")
        w["name"] = name
        return w

    def benchmark(self) -> dict:
        return _load(self.root / "BENCHMARK.json")

    def _applies(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.benchmark()["end_to_end"]
                if self._applies(m, cell)]

    def per_layer(self, cell: str) -> List[dict]:
        return [m for m in self.benchmark()["per_layer"]
                if self._applies(m, cell)]

    def metric_reader(self, name: str):
        """``metrics/<name>.py``'s ``read(ctx)``."""
        import importlib.util
        path = self.dir / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def peaks(self, device_kind: str) -> dict:
        table = _load(self.dir / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {device_kind!r}; "
                           f"known: {sorted(table['devices'])}")
        return table["devices"][device_kind]
