"""A cell of BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one cell
lives in a file of its own, found by name:

  configs/<config>.json   the configuration as it is run (the cell's ``file``)
  traffic/<traffic>.json  the job: layout, schedule, batch, tokens, optimizer
  limits/<cell>.json      the limit of each number the correctness check compares
  metrics/<metric>.py     the reader of one per-layer metric
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: benchmarks/chip -> repo root


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
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


def load(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and metrics."""
    bench = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read(root / cfg_entry["file"]),
        traffic=_read(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_read(HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def arch_config(config: Dict[str, Any]):
    """The program's ArchConfig for a configuration file."""
    from repro.models.lm import ArchConfig

    return ArchConfig(
        name=config["name"],
        family="dense",
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        block_pattern=(("attn", "mlp"),),
        dtype=config["torch_dtype"],
        source=config["source"],
    )
