"""Where the benchmark's data lives, and how a cell is put together.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it:

  bench/configs/<config>.json    sizes of the model as it is run
  bench/traffic/<traffic>.json   parameters of the traffic generator
  bench/cells/<workload>.json    the cell's rate, lanes and check limit
  bench/metrics/<metric>.py      one per-layer reader, `read(run)`

A later change adds a cell or a metric by adding files and entries,
never by editing one that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SPEC_FILE = ROOT / "BENCHMARK.json"


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of `BENCHMARK.json` with its files read in."""

    name: str
    chips: int
    config: dict    # bench/configs/<config>.json
    traffic: dict   # bench/traffic/<traffic>.json
    cell: dict      # bench/cells/<name>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, workload: str, reported: set) -> bool:
    """A metric with `workloads` is reported in those cells; a per-layer
    one without it wherever the end-to-end metric it moves is."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(workload: str, spec_file: Path = SPEC_FILE) -> Cell:
    spec = _load_json(spec_file)
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in spec["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; known: {names}")
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload, reported)]
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=_load_json(BENCH_DIR / "configs" / f"{entry['config']}.json"),
        traffic=_load_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json"),
        cell=_load_json(BENCH_DIR / "cells" / f"{workload}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def load_reader(metric: str) -> Callable[[object], Optional[float]]:
    """The `read(run)` function of `bench/metrics/<metric>.py`."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip, keyed by JAX's `device_kind`. A
    kind that is not in the table is an error, never a default."""
    table = _load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]
