"""Finds everything by name: BENCHMARK.json -> cell -> configuration,
traffic mix, runner module, layer-metric files and their reader modules.

Nothing here lists a cell, a configuration, a traffic mix or a metric:
each is a file under `benchmark/`, found by its name, so a later PR adds
one by adding files (and its entry in BENCHMARK.json) and edits nothing.
"""

from __future__ import annotations

import fnmatch
import importlib
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_manifest() -> Dict[str, Any]:
    return _load(os.path.join(REPO, "BENCHMARK.json"))


def load_peaks() -> Dict[str, Any]:
    return _load(os.path.join(BENCH_DIR, "peaks.json"))


def load_cell(name: str) -> Dict[str, Any]:
    """The cell file, with its configuration and traffic files resolved
    into it under `config_file` and `traffic_file`."""
    if not NAME_RE.match(name):
        raise ValueError(f"not a cell name: {name!r}")
    path = os.path.join(BENCH_DIR, "cells", f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no cell file {path}")
    cell = _load(path)
    if cell.get("name") != name:
        raise ValueError(f"{path}: name {cell.get('name')!r} != {name!r}")
    cell["config_file"] = _load(
        os.path.join(BENCH_DIR, "configs", f"{cell['config']}.json"))
    cell["traffic_file"] = _load(
        os.path.join(BENCH_DIR, "traffic", f"{cell['traffic']}.json"))
    return cell


def list_names(kind: str) -> List[str]:
    d = os.path.join(BENCH_DIR, kind)
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


def layer_metrics_for(cell_name: str) -> List[Dict[str, Any]]:
    """Every layer-metric file whose `cells` globs match this cell."""
    out = []
    for name in list_names("layer_metrics"):
        m = _load(os.path.join(BENCH_DIR, "layer_metrics", f"{name}.json"))
        if m.get("name") != name:
            raise ValueError(f"layer metric file {name}.json names {m.get('name')!r}")
        if any(fnmatch.fnmatchcase(cell_name, g) for g in m["cells"]):
            out.append(m)
    return out


def load_runner(name: str):
    if not NAME_RE.match(name):
        raise ValueError(f"not a runner name: {name!r}")
    return importlib.import_module(f"benchmark.runners.{name}")


def load_reader(name: str):
    if not NAME_RE.match(name):
        raise ValueError(f"not a reader name: {name!r}")
    return importlib.import_module(f"benchmark.readers.{name}")


def read_layer_metrics(cell_name: str, evidence: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Run each matching metric's reader over the run's evidence. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in layer_metrics_for(cell_name):
        value = load_reader(m["reader"]).read(evidence, **m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def hf_config(config_file: Dict[str, Any], rehearsal: bool) -> Dict[str, Any]:
    """The published keys as run (everything but the `benchmark` block),
    with the toy widths on top for the CPU walk-through."""
    hf = {k: v for k, v in config_file.items() if k != "benchmark"}
    if rehearsal:
        hf.update(config_file["benchmark"]["rehearsal_overrides"])
    return hf


def section(cell: Dict[str, Any], key: str, rehearsal: bool) -> Dict[str, Any]:
    """A cell's settings block (`engine`, `optimizer`, ...) as run."""
    out = dict(cell.get(key) or {})
    if rehearsal:
        out.update((cell.get("rehearsal") or {}).get(key) or {})
    return out


def device_peaks(device_kind: str, peaks: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    peaks = peaks if peaks is not None else load_peaks()
    if device_kind not in peaks["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(peaks['devices'])}); a device without published "
            "peaks is an error, not a default")
    return peaks["devices"][device_kind]
