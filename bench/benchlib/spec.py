"""Find a cell's configuration, model, traffic mix and metric readers by
name.

``BENCHMARK.json`` names each cell's configuration and traffic; the files
live at ``bench/configs/<config>.json`` (its ``file`` entry),
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.  The
configuration file names its two modules, found beside it:
``<architecture>.py`` maps the file to the program's model, gives the
weight rules for the leaves it adds and counts the work it serves, and
``<reference>.py`` is its plain float32 reference.  A new cell, metric or
model (a new architecture too) is new files plus ``BENCHMARK.json``
entries, never an edit of this module.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CONFIGS_DIR = BENCH_DIR / "configs"


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    read: Callable[[Any], Optional[float]]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    config_dir: Path = CONFIGS_DIR   # where its modules are found


@functools.lru_cache(maxsize=None)
def _load_module(path: Path) -> ModuleType:
    """The module at ``path``, loaded once (registered under a name of its
    own, as its dataclasses need)."""
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    name = "bench_" + re.sub(r"\W", "_", f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_architecture(conf: Dict[str, Any],
                      directory: Path = CONFIGS_DIR) -> ModuleType:
    """The module a configuration file names under ``architecture``:
    ``model_config(conf)``, ``weight_rule(path, shape, d_model)`` and
    ``work(conf)`` (``benchlib.work.Work``)."""
    return _load_module(Path(directory) / f"{conf['architecture']}.py")


def load_reference(conf: Dict[str, Any],
                   directory: Path = CONFIGS_DIR) -> ModuleType:
    """The plain reference a configuration file names under
    ``reference``: ``logits_summary(weights, conf, tokens, rows, chosen,
    pad_to, control=False)``."""
    return _load_module(Path(directory) / f"{conf['reference']}.py")


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    return json.loads(path.read_text())


def _load_reader(name: str, root: Path) -> Callable[[Any], Optional[float]]:
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric(entry: Dict[str, Any], root: Path) -> Metric:
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], _load_reader(entry["name"], root))


def load_cell(name: str, root: Path = ROOT,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` with its files read and its metrics resolved."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_path = root / confs[w["config"]]["file"]
    conf = json.loads(conf_path.read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(entry):
        return "workloads" not in entry or name in entry["workloads"]

    e2e = [e for e in bench["end_to_end"] if applies(e)]
    e2e_names = {e["name"] for e in e2e}
    # a per-layer metric without ``workloads`` is read wherever the
    # end-to-end metric it moves is reported
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), conf, traffic,
                [_metric(e, root) for e in e2e],
                [_metric(m, root) for m in per_layer], conf_path.parent)
