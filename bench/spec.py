"""Finds a cell's parts by name: the workload entry of ``BENCHMARK.json``,
``configs/<config>.json``, ``traffic/<mix>.json``, the family adapter
``families/<family>.py``, the plain reference ``configs/<reference>.py`` and
one reader ``metrics/<metric>.py`` per per-layer metric."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Mapping

from bench import generator

__all__ = ["BENCH", "ROOT", "Cell", "NAME_RE", "UNIT_RE", "load_benchmark", "cell",
           "family", "reference", "metric_reader", "load_file_module"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and mix."""

    name: str
    chips: int
    config: Dict[str, Any]
    mix: generator.Mix
    end_to_end: List[Dict[str, Any]]  # the end-to-end metrics this cell reports
    per_layer: List[Dict[str, Any]]  # the per-layer metrics read in this cell


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: Mapping, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name``; raises ``KeyError`` for an unknown workload."""
    bench = load_benchmark(root)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = generator.load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def load_file_module(path: Path) -> ModuleType:
    """Import the Python file ``path`` once per process."""
    mod_name = "bench_file__" + re.sub(r"\W", "_", str(path.relative_to(BENCH)))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    if not path.is_file():
        raise FileNotFoundError(path)
    spec_ = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec_)
    sys.modules[mod_name] = mod
    spec_.loader.exec_module(mod)
    return mod


def family(config: Mapping) -> ModuleType:
    """The adapter that builds the program for ``config``."""
    return importlib.import_module(f"bench.families.{config['family']}")


def reference(config: Mapping) -> ModuleType:
    """The plain reference beside the configuration files."""
    return load_file_module(BENCH / "configs" / f"{config['reference']}.py")


def metric_reader(name: str) -> ModuleType:
    """``metrics/<name>.py``: exposes ``read(run) -> float | None``."""
    return load_file_module(BENCH / "metrics" / f"{name}.py")
