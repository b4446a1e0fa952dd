"""Find what ``BENCHMARK.json`` names, each by its name, each in a file of
its own:

* a configuration: the ``file`` its entry names (``bench/configs/``);
  its ``data`` names the generator ``bench/gen/<data>.py``;
* a traffic mix: ``bench/traffic/<traffic>.json``, parameters only; its
  ``loop`` names ``bench/loops/<loop>.py`` and each ``answer`` its
  reference ``bench/references/<reference>.py``;
* a metric: ``bench/metrics/<metric>.py``, whose ``read(run)`` returns the
  number in the metric's unit, or ``None`` where there is nothing to read.
  A metric split by the cells it serves (``<base>.<part>``) may share the
  reader ``bench/metrics/<base>.py``.

A new cell, configuration, traffic mix, loop, generator, reference or
metric is new files and new entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

_MODULES: Dict[Path, ModuleType] = {}


def load(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: List[Dict[str, Any]], name: str, what: str
           ) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json "
                     f"(known: {known})")


def workload(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _named(spec["workloads"], name, "workload")


def config(spec: Dict[str, Any], name: str,
           root: Path = ROOT) -> Dict[str, Any]:
    entry = _named(spec["configs"], name, "config")
    with open(root / entry["file"]) as f:
        return json.load(f)


def resolve(value: Any, cfg: Dict[str, Any]) -> Any:
    """``value`` with every string ``"$key"`` replaced by ``cfg[key]``."""
    if isinstance(value, str) and value.startswith("$"):
        return cfg[value[1:]]
    if isinstance(value, dict):
        return {k: resolve(v, cfg) for k, v in value.items()}
    if isinstance(value, list):
        return [resolve(v, cfg) for v in value]
    return value


def traffic(name: str, cfg: Dict[str, Any],
            bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    """The traffic mix ``name``, its ``"$key"`` values taken from the
    configuration ``cfg``."""
    with open(bench_dir / "traffic" / f"{name}.json") as f:
        return resolve(json.load(f), cfg)


def module(kind: str, name: str, bench_dir: Path = BENCH_DIR
           ) -> ModuleType:
    """``bench/<kind>/<name>.py``, loaded once by its path."""
    path = bench_dir / kind / f"{name}.py"
    if path not in _MODULES:
        if not path.is_file():
            raise SystemExit(f"no {kind} module named {name!r} ({path})")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(name: str, bench_dir: Path = BENCH_DIR
           ) -> Callable[[Any], Optional[float]]:
    """``read`` of ``metrics/<name>.py``, else of ``metrics/<base>.py``
    where ``name`` is ``<base>.<part>``."""
    if not (bench_dir / "metrics" / f"{name}.py").is_file():
        name = name.split(".")[0]
    return module("metrics", name, bench_dir).read


def metrics_for(spec: Dict[str, Any], cell: str, traced: bool
                ) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics (``traced`` False) or its per-layer
    metrics (``traced`` True): those that list the cell, or list none.
    A per-layer metric without a list follows the cells of the
    end-to-end metric that it moves."""
    kind = "per_layer" if traced else "end_to_end"
    e2e = {m["name"]: m for m in spec["end_to_end"]}

    def applies(m: Dict[str, Any]) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        if "moves" in m:
            return applies(e2e[m["moves"]])
        return True

    return [m for m in spec[kind] if applies(m)]
