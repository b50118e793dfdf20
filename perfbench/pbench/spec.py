"""What one cell is: its entry in BENCHMARK.json and the files it names.

Everything a cell needs is data found by name: the configuration file
that ``configs[].file`` names, ``traffic/<traffic>.json``,
``limits/<cell>.json`` and one reader ``metrics/<metric>.py`` per
per-layer metric. A new cell, configuration, traffic mix or metric is new
files plus a new entry; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]              # the configuration file, as run
    traffic: Dict[str, Any]             # traffic/<traffic>.json
    limits: Dict[str, float]            # limits/<cell>.json
    end_to_end: List[Dict[str, Any]]    # this cell's end-to-end metrics
    per_layer: List[Dict[str, Any]]     # this cell's per-layer metrics
    bench_dir: Path = field(default=BENCH_DIR)

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        return load_reader(self.bench_dir, metric)


def _for_cell(metric: Dict[str, Any], cell: str, e2e: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a metric without a cell list is due wherever its end-to-end metric is
    return metric["moves"] in e2e


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    bench_dir = root / bench["paths"][0]
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(bench_dir / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _for_cell(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, bench_dir=bench_dir)


def load_reader(bench_dir: Path, metric: str
                ) -> Callable[[Any], Optional[float]]:
    """``metrics/<metric>.py``'s ``read(run)``: the metric's value from a
    finished traced run, or None where the run holds nothing to read."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {metric!r}")
    mod_name = "pbench_metric_" + re.sub(r"\W", "_", metric)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
