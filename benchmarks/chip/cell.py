"""A cell of BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one architecture, one
traffic mix or one cell lives in a file of its own, found by name:

  configs/<config>.json   the configuration as it is run (the cell's ``file``)
  archs/<architecture>.py the block of the configuration's ``architecture``:
                          ``arch_config``, ``layer_leaves``, ``layer``
                          (the reference's), ``train_flops_per_token`` and
                          ``attention_shapes``
  traffic/<traffic>.json  the job: layout, schedule, batch, tokens, optimizer
  limits/<cell>.json      the limit of each number the correctness check compares
  metrics/<metric>.py     the reader of one per-layer metric, ``read(run)``;
                          it may state ``SCOPES``, the program scopes it reads
                          beyond ``scoped.PROGRAM_SCOPES``
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: benchmarks/chip -> repo root


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    arch: ModuleType
    traffic: Dict[str, Any]
    limits: Dict[str, Optional[float]]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def tokens_per_step(self) -> int:
        t = self.traffic
        return t["microbatches"] * t["microbatch_size"] * t["seq_len"]


def _read(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """The Python file at ``path``, loaded as a module called ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_dir(root: pathlib.Path = ROOT) -> pathlib.Path:
    """``metrics/`` under the checkout ``root``: one reader module a metric."""
    return root / HERE.relative_to(ROOT) / "metrics"


def architecture(config: Dict[str, Any], root: pathlib.Path = ROOT) -> ModuleType:
    """The module of the configuration's ``architecture``, from ``archs/``."""
    archs = root / HERE.relative_to(ROOT) / "archs"
    name = config.get("architecture")
    path = archs / f"{name}.py"
    if not isinstance(name, str) or not path.is_file():
        known = sorted(p.stem for p in archs.glob("*.py"))
        raise ValueError(
            f"configuration {config.get('name')!r} names architecture {name!r}, "
            f"which is no module of {archs}: {known}"
        )
    return load_module(path, f"bench_arch_{name}")


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, architecture, traffic,
    limits and metrics, all read under the checkout ``root``."""
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    here = root / HERE.relative_to(ROOT)
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read(root / cfg_entry["file"])
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        arch=architecture(config, root),
        traffic=_read(here / "traffic" / f"{w['traffic']}.json"),
        limits=_read(here / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )
