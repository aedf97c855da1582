"""Cells at a size a CPU test can hold: the cell's own files with every
width and length scaled down (the model by its architecture module's
``tiny``, the traffic here), so the whole run (weights, warm-up, window,
trace reduction, reference) can be driven without a chip."""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import spec  # noqa: E402

TINY_TRAFFIC = {
    "long-decode": {"engine": {"n_slots": 2, "max_len": 1024},
                    "sessions": {"count": 2, "max_new": 400, "warm_tokens": 4,
                                 "prompt": {"dist": "uniform", "lo": 300,
                                            "hi": 400}}},
    "chat": {"engine": {"n_slots": 4, "max_len": 512},
             "prompt": {"dist": "lognormal", "median": 48, "sigma": 1.0,
                        "lo": 16, "hi": 200},
             "output": {"dist": "lognormal", "median": 8, "sigma": 1.0,
                        "lo": 2, "hi": 40},
             "warm": {"batched_upto": 64}, "warm_s": 1.0, "drain_s": 20.0},
}


def tiny_cell(name: str) -> spec.Cell:
    """``<config>.<mix>`` from the files of that configuration and mix
    (whether or not BENCHMARK.json runs the pair), scaled down."""
    config, mix = name.split(".", 1)
    cell = spec.Cell(
        name=name, chips=1,
        config=spec.load_json(spec.BENCH / "configs" / f"{config}.json"),
        traffic=spec.load_json(spec.BENCH / "traffic" / f"{mix}.json"),
        bench=spec.load_json(spec.ROOT / "BENCHMARK.json"))
    cell.config = spec.arch(cell.config).tiny(cell.config)
    mix = cell.traffic["name"]
    for k, v in TINY_TRAFFIC[mix].items():
        cell.traffic[k] = v
    if cell.traffic.get("arrivals"):
        cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                        rate_per_s=4.0)
    return cell
