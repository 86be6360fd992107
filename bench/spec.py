"""Loading a cell: its entry in ``BENCHMARK.json`` and the data files the
entry names.  Nothing here is specific to one cell, configuration or mix."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class SpecError(Exception):
    """A cell, configuration, mix or metric that cannot be loaded."""


def read_json(path: pathlib.Path) -> Dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: pathlib.Path, name: str) -> ModuleType:
    """Import a file whose name need not be a Python identifier."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path          # directory holding BENCHMARK.json
    entry: Dict[str, Any]       # the workloads entry
    knobs: Dict[str, Any]       # bench/cells/<cell>.json
    config: Dict[str, Any]      # bench/configs/<config>.json
    mix: Dict[str, Any]         # bench/traffic/<mix>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def reference(self) -> ModuleType:
        """The configuration's weights maker and plain reference."""
        name = self.entry["config"]
        return load_module(self.root / "bench" / "configs" / f"{name}.py",
                           f"bench_ref_{name.replace('-', '_').replace('.', '_')}")

    def metric_reducer(self, metric: str) -> ModuleType:
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric.replace('.', '_')}")

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports in a run with or without trace:
        those without a ``workloads`` key, and those that list the cell."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    entry = entries[0]
    d = root / "bench"
    return Cell(
        name=name, root=root, entry=entry,
        knobs=read_json(d / "cells" / f"{name}.json"),
        config=read_json(d / "configs" / f"{entry['config']}.json"),
        mix=read_json(d / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def program_config(config: Dict[str, Any]):
    """The program's ModelConfig for a configuration file: the registry's
    config with the file's sizes and dtype.  Every size the file states must
    name a field of the program's config; a size the registry holds
    differently is applied only if the file lists it under ``reduced`` (a
    cut) and refused otherwise, so the file is the configuration as run."""
    from repro.configs import get_config

    base = get_config(config["registry"])
    sizes = dict(config["sizes"])
    fields = {f.name for f in dataclasses.fields(base)}
    unknown = sorted(set(sizes) - fields)
    if unknown:
        raise SpecError(f"{config['registry']}: sizes {unknown} are not "
                        f"fields of the program's config")
    differ = sorted(k for k, v in sizes.items() if getattr(base, k) != v)
    unlisted = [k for k in differ if k not in config.get("reduced", [])]
    if unlisted:
        raise SpecError(f"{config['registry']}: sizes {unlisted} differ from "
                        f"the registry's config and are not under 'reduced'")
    return dataclasses.replace(base, dtype=config["dtype"], **sizes)
