"""What ``BENCHMARK.json`` and the data files under ``bench/`` say about one
cell: its configuration, traffic mix, cell parameters, and the metrics it
reports.  Each is found by its name, so a cell, configuration, mix or
metric is added by adding files and entries, never by editing code."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

__all__ = ["ROOT", "BENCH", "Cell", "load_cell", "metric_reader", "peaks"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic: str
    config: dict            # bench/configs/<config>.json
    mix: dict               # bench/mixes/<traffic>.json
    params: dict            # bench/cells/<workload>.json
    end_to_end: List[dict]  # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def _applies(metric: dict, cell: str, reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(workload: str, bench_json: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    b = _json(bench_json)
    cells = {w["name"]: w for w in b["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in b["configs"]}
    e2e = [m for m in b["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in b["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(name=workload, chips=w["chips"], config_name=w["config"],
                traffic=w["traffic"],
                config=_json(ROOT / configs[w["config"]]["file"]),
                mix=_json(BENCH / "mixes" / f"{w['traffic']}.json"),
                params=_json(BENCH / "cells" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The reader module of a per-layer metric: ``bench/metrics/<name>.py``,
    or, for a name split by cell kind (``step_mfu.chat``), the reader of
    the name without its last part."""
    parts = name.split(".")
    while parts:
        path = BENCH / "metrics" / (".".join(parts) + ".py")
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + "_".join(parts), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
        parts.pop()
    raise FileNotFoundError(f"no reader for metric {name!r}")


def peaks() -> Dict[str, dict]:
    return _json(BENCH / "peaks.json")["devices"]
