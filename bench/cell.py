"""One cell of BENCHMARK.json, built from its files by name.

A cell names a configuration (`configs[].file`: sizes, space and constants
as run) and a traffic mix (`bench/traffic/<traffic>.json`, parameters
only). `Cell` turns them into the program's objects; the traffic's entry
(`bench/entries/<entry>.py`) answers items with them, and its loop
(`bench/loops/<loop>.py`) sends the window's items. Nothing here knows a
cell, an entry or a loop by name.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (lowerings, entries, loops
    and per-layer readers are found by name, not registered)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: Path = ROOT) -> tuple:
    """(cell entry, configuration dict, traffic dict) of one cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config, traffic


def lowering(config: dict):
    return load_module(BENCH / "lowering" / f"{config['lowering']}.py")


class Cell:
    """The program side of one (configuration, traffic) pair: its
    workloads by name, space and constants, and the traffic's entry."""

    def __init__(self, config: dict, traffic: dict):
        from repro.core.factorized import FactorizedSpace
        from repro.core.photonic_model import DeviceConstants

        self.config = config
        self.traffic = traffic
        low = lowering(config)
        self.workloads = {n: low.program_workload(n, w)
                          for n, w in config["workloads"].items()}
        self.space = FactorizedSpace.full(int(config["space"]["n_z"]))
        self.c = DeviceConstants(**config["constants"])
        self.objective = traffic["objective"]
        self.metrics = tuple(traffic.get("pareto_metrics")
                             or ("area", "power", "edp"))
        self.entry = load_module(
            BENCH / "entries" / f"{traffic['entry']}.py").Entry(self)
        self.loop = load_module(
            BENCH / "loops" / f"{traffic.get('loop', 'closed')}.py")
