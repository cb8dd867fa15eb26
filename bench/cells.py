"""Cells of `BENCHMARK.json`, each resolved by name to its files."""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the deployment, as `bench/configs/<config>.json`
    traffic: dict         # the mix, as `bench/traffic/<traffic>.json`
    end_to_end: tuple     # entries of BENCHMARK.json that this cell reports
    per_layer: tuple


def manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    m = manifest(root)
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in m["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(x for x in m["end_to_end"] if _applies(x, name, set()))
    names = {x["name"] for x in e2e}
    per_layer = tuple(x for x in m["per_layer"] if _applies(x, name, names))
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """`read(obs)` of `bench/metrics/<name>.py`: the metric's value from
    what the traced run observed, or None where it found nothing."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
