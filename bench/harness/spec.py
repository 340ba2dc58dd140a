"""Find a cell and everything it names, by name, from BENCHMARK.json.

A cell is one entry of ``workloads``. Its configuration is the JSON file
that ``configs`` names, its traffic is ``bench/traffic/<traffic>.json``,
its per-layer metric readers are ``bench/metrics/<metric>.py`` and its
reference is ``bench/reference/<reference>.py``. Adding a cell adds
files and entries; nothing here needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]        # the configuration file as run
    traffic_name: str
    traffic: Dict[str, Any]       # the traffic mix's parameters
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return _load_json(root / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell called `name`, with its configuration and traffic loaded
    and the metrics it reports selected."""
    man = manifest(root)
    work = {w["name"]: w for w in man["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(root / cfg_entry["file"])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"] if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config: Dict[str, Any]):
    return load_module("reference", config["reference"])


def model_config(config: Dict[str, Any], param_dtype: str):
    """The program's ModelConfig for a configuration file: its
    ``program`` block, with the parameter dtype the traffic serves or
    trains in."""
    from repro.configs.base import ModelConfig, SSMConfig
    kw = dict(config["program"])
    if "ssm" in kw:
        kw["ssm"] = SSMConfig(**kw["ssm"])
    return ModelConfig(param_dtype=param_dtype, **kw)
