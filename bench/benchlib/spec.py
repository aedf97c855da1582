"""What one cell is: its entry in BENCHMARK.json, its configuration file and
its traffic file, all found by name.

    bench/configs/<config>.json   sizes as published (keys of the model's own
                                  config.json), what was cut (``reduced``),
                                  what the program needs to run it
                                  (``program``, ``memory``), and its
                                  architecture (``arch``)
    bench/archs/<arch>.py         what the harness knows of that
                                  architecture's shape: the program's
                                  config, the weights, the FLOPs of a step,
                                  the CPU scale-down
    bench/traffic/<traffic>.json  parameters of one traffic mix

Nothing here imports the program: ``serve_config`` and an architecture's
``program_config`` are handed the program's classes by the caller.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict

from benchlib import readers

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]      # bench/configs/<config>.json
    traffic: Dict[str, Any]     # bench/traffic/<traffic>.json
    bench: Dict[str, Any]       # BENCHMARK.json

    def end_to_end(self):
        """The cell's end-to-end metric entries."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """The cell's per-layer metric entries."""
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])]


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / conf["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                bench=bench)


def arch(config: Dict[str, Any]):
    """The architecture module a configuration file names (``arch``):
    bench/archs/<arch>.py."""
    return readers.module("archs", config["arch"])


def serve_config(config: Dict[str, Any], traffic: Dict[str, Any],
                 ServeConfig, OffloadConfig):
    """The program's ServeConfig: deployment knobs from the traffic file
    (slots, context), method and page from the configuration; every other
    engine knob keeps the program's default."""
    prog, eng = config["program"], traffic["engine"]
    return ServeConfig(max_len=int(eng["max_len"]), n_slots=int(eng["n_slots"]),
                       method=prog["method"], tp=int(prog["tp"]),
                       page=int(config["memory"]["page"]),
                       offload_cfg=OffloadConfig(mode=prog["offload"]))
