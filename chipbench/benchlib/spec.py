"""Find a cell and everything it names, by name, in data files.

``BENCHMARK.json`` (at the checkout's root) lists cells, configurations
and metrics.  A configuration ``<c>`` is ``chipbench/configs/<c>.json``,
a traffic mix ``<t>`` is ``chipbench/traffic/<t>.json``, a metric
``<m>`` is read by ``chipbench/metrics/<m>.py`` and a reference ``<r>``
is ``chipbench/refs/<r>.py``.  Adding a cell is adding such files and
entries; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, file or key that the benchmark cannot resolve."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # chipbench/configs/<config>.json
    mix: dict               # chipbench/traffic/<traffic>.json
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)
    peaks: dict = field(default_factory=dict)         # by device_kind
    bench_dir: Path = BENCH_DIR

    @property
    def model(self) -> dict:
        return self.config["model"]

    def reference(self):
        return load_module(self.bench_dir / "refs"
                           / f"{self.config['reference']}.py")


def read_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e


def load_module(path: Path):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(root / cfg_entry["file"]),
        mix=read_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        peaks=read_json(bench_dir / "peaks.json"),
        bench_dir=bench_dir)


def metric_reader(bench_dir: Path, name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return load_module(bench_dir / "metrics" / f"{name}.py").read
