"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell names a configuration and a traffic mix. The configuration's
``file`` (``bench/configs/<config>.json``) holds the deployment as it is
run, and the module beside it (same path, ``.py``) its plain reference.
The mix is ``bench/traffic/<traffic>.json``. Each per-layer metric the
cell reports has a reader ``bench/metrics/<metric>.py``. Adding a cell
means adding such files and a ``workloads`` entry; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

from bench import traffic

ROOT = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: str
    cfg: dict
    reference: ModuleType
    traffic: str
    mix: dict
    end_to_end: list = field(default_factory=list)  # manifest entries
    per_layer: list = field(default_factory=list)  # (entry, reader module)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (metric names carry dots)."""
    name = "bench_file_" + "_".join(path.with_suffix("").parts[-2:]).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(workload: str, root: Path = ROOT) -> Cell:
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}")
    w = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    cfg_path = root / conf["file"]
    with open(cfg_path) as f:
        cfg = json.load(f)

    e2e = [m for m in man["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}

    def reported(m):
        if "workloads" in m:
            return workload in m["workloads"]
        return m["moves"] in e2e_names

    per_layer = [
        (m, load_module(root / "bench" / "metrics" / f"{m['name']}.py"))
        for m in man["per_layer"]
        if reported(m)
    ]
    return Cell(
        root=root,
        name=workload,
        chips=w["chips"],
        config=w["config"],
        cfg=cfg,
        reference=load_module(cfg_path.with_suffix(".py")),
        traffic=w["traffic"],
        mix=traffic.load(w["traffic"], root / "bench" / "traffic"),
        end_to_end=e2e,
        per_layer=per_layer,
    )
