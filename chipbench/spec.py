"""Finds a cell's pieces by the names in ``BENCHMARK.json``: its
configuration file, its traffic mix (``traffic/<name>.json``), its
driver (``drivers/<kind>.py``), its reference (``reference/<family>.py``),
its limits (``limits/<cell>.json``) and its per-layer readers
(``metrics/<metric>.py``, or ``metrics/<stem>.py`` for a metric named
``<stem>.<split>``).  A new cell is new files and new entries; no file
that is there changes."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

#: the checkout's root: the directory that holds ``BENCHMARK.json``
ROOT = Path(__file__).resolve().parent.parent


def _load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str, root: Path = ROOT):
        self.root = Path(root)
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = json.loads(
            (self.root / self.config_entry["file"]).read_text())
        self.bench_dir = self.root / "chipbench"
        self.traffic = json.loads(
            (self.bench_dir / "traffic" / f"{self.entry['traffic']}.json")
            .read_text())
        self.limits = json.loads(
            (self.bench_dir / "limits" / f"{name}.json").read_text())

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def _reports(self, metric: dict) -> bool:
        listed = metric.get("workloads")
        return self.name in listed if listed is not None else True

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        """Per-layer metrics of this cell: those listing it, and those
        with no list that move an end-to-end metric the cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def driver(self) -> ModuleType:
        return _load_module(self.bench_dir / "drivers"
                            / f"{self.traffic['driver']}.py")

    def reference(self) -> ModuleType:
        return _load_module(self.bench_dir / "reference"
                            / f"{self.config['family']}.py")

    def reader_of(self, metric: str) -> ModuleType:
        """The reader of an end-to-end metric (``end_to_end/<name>.py``)
        or a per-layer one (``metrics/<name>.py``, else
        ``metrics/<stem>.py`` for ``<stem>.<split>``)."""
        if any(m["name"] == metric for m in self.bench["end_to_end"]):
            kind, stems = "end_to_end", (metric,)
        else:
            kind, stems = "metrics", (metric, metric.split(".")[0])
        for stem in stems:
            path = self.bench_dir / kind / f"{stem}.py"
            if path.is_file():
                return _load_module(path)
        raise FileNotFoundError(f"no reader for metric {metric!r} under "
                                f"{self.bench_dir / kind}")
