"""Finding the pieces that belong to one name: a per-layer metric's reader
(bench/metrics/<metric>.py, ``read(ctx)``), a kernel's cost function
(bench/kernels/<name>.py, ``cost(config, ...)``), a
configuration's plain reference (bench/reference/<name>.py) and its
architecture (bench/archs/<arch>.py), and the context a reader is
handed."""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import sys
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parents[1]
_MODULES: Dict[str, Any] = {}


def module(kind: str, name: str):
    """bench/<kind>/<name>.py, loaded once (names may hold dots)."""
    key = f"{kind}/{name}"
    if key not in _MODULES:
        path = BENCH / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return _MODULES[key]


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""
    cell: Any                         # spec.Cell
    stats: Dict[str, Any]             # drive.window_stats of the run
    trace: Optional[Any]              # scopes.ScopedTrace of the window,
                                      # or None
    peak: Dict[str, Any]              # bench/peaks.json entry of the device
    contexts: List[float]             # mean live context of each decoding
                                      # session over the window
    prompt_tokens_traced: int         # prompt tokens of requests whose
                                      # first token landed in the window
    decode_steps: int                 # decode steps the engine counted
                                      # in the window (Engine.stats)

    def cost(self, name: str):
        return module("kernels", name).cost

    def roofline(self, kernel: str) -> Optional[float]:
        """100 x max(flops / peak flops, bytes / peak bandwidth) over the
        kernel's device time, with its algorithmic work per call at the
        sessions' mean contexts (linear in context, so the mean is exact
        for contexts that grow by one token a step)."""
        if self.trace is None or not self.contexts:
            return None
        seconds, calls = self.trace.kernel(kernel)
        if not calls or seconds <= 0:
            return None
        flops, nbytes = self.cost(kernel)(self.cell.config, self.contexts)
        least = max(flops / self.peak["bf16_flops_per_s"],
                    nbytes / self.peak["hbm_bytes_per_s"])
        return 100.0 * calls * least / seconds


def read_all(ctx: Context, names: List[str]) -> Dict[str, float]:
    """Every reader that finds something; the others are left out."""
    out = {}
    for name in names:
        v = module("metrics", name).read(ctx)
        if v is not None:
            out[name] = float(v)
    return out
