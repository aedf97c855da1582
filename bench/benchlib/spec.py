"""What one cell is: its entry in BENCHMARK.json, its configuration file and
its traffic file, all found by name.

    bench/configs/<config>.json   sizes as published (keys of the model's own
                                  config.json), what was cut (``reduced``),
                                  what the program needs to run it
                                  (``program``, ``memory``)
    bench/traffic/<traffic>.json  parameters of one traffic mix

Nothing here imports the program: ``program_config`` and ``serve_config``
are handed the program's classes by the caller.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# published config.json key -> field of the program's ArchConfig
ARCH_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


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


def program_config(config: Dict[str, Any], ArchConfig, MemoryConfig):
    """The program's ArchConfig for a configuration file: every size from
    the file, the mechanisms the file's ``program`` block names."""
    prog = config["program"]
    mem = config["memory"]
    kw = {field: config[key] for key, field in ARCH_KEYS.items()}
    kw["head_dim"] = config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])
    kw["rope_theta"] = float(kw["rope_theta"])
    kw["norm_eps"] = float(kw["norm_eps"])
    return ArchConfig(
        name=config["name"], family=prog["family"],
        qk_norm=bool(prog["qk_norm"]), qkv_bias=bool(prog["qkv_bias"]),
        dtype=config.get("torch_dtype", "bfloat16"),
        memory=MemoryConfig(method=prog["method"],
                            index_heads=mem["index_heads"],
                            index_dim=mem["index_dim"], top_k=mem["top_k"],
                            min_context=mem["min_context"]),
        **kw)


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
