"""Where the benchmark's data lives, and how a cell is put together.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
`BENCHMARK.json` gives it:

  bench/configs/<config>.json    sizes of the model as it is run
  bench/traffic/<traffic>.json   parameters of the traffic generator
  bench/cells/<workload>.json    the cell's rate, lanes and check limit
  bench/metrics/<metric>.py      one per-layer reader, `read(run)`
  bench/references/<name>.py     the plain reference of a configuration
                                 whose file says `"reference": "<name>"`;
                                 a file without the key gets
                                 bench/reference.py

A later change adds a cell, a metric or a configuration by adding
files and entries, never by editing one that is already here.

A reference module is the yardstick of `correct` for its configuration.
It imports nothing of the program under test and takes nothing the
program made, and it provides

  logit_gaps(cfg, seed, seqs, shape, control=False) -> dict

  cfg      the configuration file, as loaded
  seed     the run's seed: the reference makes its own weights from it,
           along the same key tree as the program draws that
           configuration's weights from, in the served dtype
  seqs     [{"prompt": int32 ids, "served": the tokens the engine
           generated, in order}], at most `shape[0]` of them
  shape    (sequences, tokens): the fixed batch every run pads to, so
           that one compiled program serves every run
  control  also read the control: the same model one precision step
           below the served dtype, its own first choice at each
           position read against the reference

It computes in float32 at the highest matmul precision and returns
`tokens` (served tokens compared), `gap` (the widest gap between the
reference's best logit and its logit of a served token), `gap_per_seq`,
`argmax_share` (the share of served tokens that are the reference's
first choice), and with `control` also `control_gap` and
`control_argmax_share`.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
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


@functools.cache
def _load_module(path: Path, name: str) -> ModuleType:
    """The module in `path`, executed once a process: a reference's
    compiled programs then serve every call."""
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable[[object], Optional[float]]:
    """The `read(run)` function of `bench/metrics/<metric>.py`."""
    return _load_module(BENCH_DIR / "metrics" / f"{metric}.py",
                        f"bench_metric_{metric.replace('.', '_')}").read


def reference(cfg: dict) -> ModuleType:
    """The plain reference of a configuration: the module that its
    file's `reference` key names, or bench/reference.py without one."""
    name = cfg.get("reference")
    if name is None:
        return _load_module(BENCH_DIR / "reference.py", "bench_reference")
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name):
        raise ValueError(f"bad reference name {name!r}")
    return _load_module(BENCH_DIR / "references" / f"{name}.py",
                        f"bench_reference_{name.replace('.', '_')}")


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip, keyed by JAX's `device_kind`. A
    kind that is not in the table is an error, never a default."""
    table = _load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return table[device_kind]
