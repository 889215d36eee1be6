"""Everything the harness reads from files, found by name.

``BENCHMARK.json`` names cells, configurations, traffic mixes and
metrics.  Each of those lives in a file of its own under ``bench/``:

* a configuration: ``bench/configs/<config>.json`` (its ``file`` key);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a cell's correctness limits: ``bench/cells/<workload>.json``;
* a metric: ``bench/metrics/<metric>.py``, a module with
  ``read(run) -> float | None``;
* a plain reference: ``bench/references/<reference>.py``;
* device peaks: ``bench/peaks.json``, keyed by ``device_kind``.

Adding a cell, a mix or a metric adds files and entries; no code here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SPEC_FILE = ROOT / "BENCHMARK.json"
PEAKS_FILE = BENCH_DIR / "peaks.json"


class SpecError(ValueError):
    """A file the benchmark names is missing or malformed."""


def _load_json(path: Path) -> Any:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


def load_spec(path: Path = SPEC_FILE) -> Dict[str, Any]:
    return _load_json(path)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    moves: Optional[str]
    workloads: Optional[List[str]]
    read: Callable[[Any], Optional[float]]


def metric_file(name: str) -> Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def load_reader(name: str) -> Callable[[Any], Optional[float]]:
    path = metric_file(name)
    if not path.is_file():
        raise SpecError(f"metric {name!r} has no reader at {path}")
    mod_name = "bench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metric reader {path} defines no read(run)")
    return mod.read


def load_reference(name: str):
    path = BENCH_DIR / "references" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"reference {name!r} missing at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    traffic: Dict[str, Any]         # the traffic file's contents
    limits: Dict[str, Any]          # bench/cells/<name>.json
    metrics: List[Metric]           # end-to-end, then per-layer


def _applies(entry: Dict[str, Any], cell: str,
             reported: List[str]) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    if entry.get("moves") is not None:
        return entry["moves"] in reported
    return True


def load_cell(name: str, spec: Optional[Dict[str, Any]] = None) -> Cell:
    spec = load_spec() if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; known: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(ROOT / cfg_entry["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(BENCH_DIR / "cells" / f"{name}.json")
    metrics: List[Metric] = []
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, [])]
    reported = [m["name"] for m in e2e]
    for m in e2e:
        metrics.append(Metric(m["name"], m["unit"], m["better"],
                              m["source"], True, None, m.get("workloads"),
                              load_reader(m["name"])))
    for m in spec["per_layer"]:
        if _applies(m, name, reported):
            metrics.append(Metric(m["name"], m["unit"], m["better"],
                                  m["source"], False, m["moves"],
                                  m.get("workloads"),
                                  load_reader(m["name"])))
    return Cell(name, int(w["chips"]), config, traffic, limits, metrics)


def load_peaks(path: Path = PEAKS_FILE) -> Dict[str, Any]:
    return _load_json(path)["devices"]


def check_devices(devices, chips: int,
                  peaks: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The device the cell runs on, as JAX reports it, and its peaks.

    A cell runs on TPUs only, on at least ``chips`` of them, of a kind
    the peaks table knows.  Anything else is an error: no fallback."""
    peaks = load_peaks() if peaks is None else peaks
    if not devices:
        raise SpecError("JAX reports no device")
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SpecError(f"needs a TPU; JAX found platform "
                        f"{d0.platform!r}")
    if len(devices) < chips:
        raise SpecError(f"needs {chips} chip(s); JAX found "
                        f"{len(devices)}")
    kind = d0.device_kind
    if kind not in peaks:
        raise SpecError(f"device kind {kind!r} is not in {PEAKS_FILE.name}"
                        f" (known: {sorted(peaks)})")
    return peaks[kind]
