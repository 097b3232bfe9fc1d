"""Find a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
pieces are files of their own, so a later change adds a cell, a
configuration, a mix or a metric by adding files and entries, never by
editing one:

- the configuration: the file the ``configs`` entry names, whose
  ``reference`` key names the architecture module
  ``bench/configs/<reference>.py`` beside it;
- the traffic mix: ``bench/traffic/<traffic>.json``;
- the cell's fixed load and correctness limits: ``bench/cells/<cell>.json``;
- each metric: a reader ``bench/metrics/<metric name>.py`` with a
  ``read(run)`` function.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    """One workload of the benchmark, with its pieces loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    load: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    def arch(self):
        """The configuration's architecture module (reference, weights)."""
        return load_module(self.root / "configs"
                           / f"{self.config['reference']}.py")


def load_module(path: Path):
    """Import a file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        "bench_piece_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Optional[Path] = None,
              benchmark: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``benchmark`` (default: ``BENCHMARK.json``
    beside ``root``, the benchmark's directory)."""
    root = Path(root or BENCH_DIR)
    benchmark = Path(benchmark or root.parent / "BENCHMARK.json")
    spec = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark} "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = benchmark.parent / configs[w["config"]]["file"]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads(cfg_file.read_text()),
        traffic=json.loads((root / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        load=json.loads((root / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


def reader(root: Path, metric: str) -> Callable:
    """The ``read`` function of metric ``metric``."""
    return load_module(Path(root) / "metrics" / f"{metric}.py").read


def read_metrics(root: Path, metrics: List[dict], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each metric whose reader found
    something to read in ``run``; a reader that returns None is left
    out."""
    out = {}
    for m in metrics:
        value = reader(root, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
