"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so the second can be checked on a recorded trace:

``scopes.extract`` reads the ``.xplane.pb`` the JAX profiler wrote and
             keeps, as plain lists: the program executions of each device
             (plane ``/device:<KIND>:<n>``, line ``XLA Modules``), its
             operations (line ``XLA Ops``, by short name), and the host
             spans. Each operation also keeps the largest token count T of
             any [B, T, hidden] activation in its HLO text (``token_rows``).
``Trace``    sums those: busy time as the union of operation intervals per
             device; per program kind the time and executions (a program
             whose activations carry one token a row is ``decode``, more
             than one ``prefill``, none ``other``, so the jitted programs
             are told apart by what they compute and not by their names);
             per kernel (its Pallas name) time and calls; the longest
             operations, and the longest idle gaps, each named by the host
             span that covers most of it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    device: int
    start: float      # ns
    dur: float        # ns
    name: str         # short HLO name: "relevancy_topk", "convert_fusion"
    rows: int         # largest T of a [B, T, hidden] shape it touches


@dataclasses.dataclass
class Module:
    device: int
    start: float
    dur: float
    name: str
    run: int


@dataclasses.dataclass
class Span:
    start: float
    dur: float
    name: str


def short_name(hlo: str) -> str:
    """"%relevancy_topk.3 = (f32[...]) custom-call(...)" -> "relevancy_topk"."""
    head = hlo.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


def token_rows(hlo: str, hidden: int) -> int:
    """Tokens a row of the residual stream [B, T, hidden] a layer loop
    carries: the first such array of a ``while`` op's output tuple (the
    scan carry comes before the stacked layer weights). 0 for other ops."""
    if short_name(hlo) != "while":
        return 0
    out = hlo.split(" = ", 1)[1] if " = " in hlo else ""
    m = re.search(rf"\[\d+,(\d+),{hidden}\]", out.split(" while(", 1)[0])
    return int(m.group(1)) if m else 0


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def save(d: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(d, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(iv: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(iv):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


class Trace:
    def __init__(self, devices: List[int], modules: List[Module],
                 ops: List[Op], spans: List[Span]):
        self.devices = devices
        self.spans = spans
        win = [s for s in spans if s.name == WINDOW_SPAN]
        if win:
            self.t0, self.t1 = win[0].start, win[0].start + win[0].dur
        elif ops:
            self.t0 = min(o.start for o in ops)
            self.t1 = max(o.start + o.dur for o in ops)
        else:
            self.t0 = self.t1 = 0.0
        # only what ran inside the window
        inside = lambda x: x.start < self.t1 and x.start + x.dur > self.t0
        self.ops = [o for o in ops if inside(o)]
        self.modules = [m for m in modules if inside(m)]
        self._by_dev = defaultdict(list)
        for o in self.ops:
            self._by_dev[o.device].append(o)
        for v in self._by_dev.values():
            v.sort(key=lambda o: o.start)
        self._starts = {d: [o.start for o in v]
                        for d, v in self._by_dev.items()}
        self.kind = [self._kind(m) for m in self.modules]

    @classmethod
    def from_dict(cls, d: Dict) -> "Trace":
        return cls(list(d["devices"]), [Module(*m) for m in d["modules"]],
                   [Op(*o) for o in d["ops"]],
                   [Span(*s) for s in d["spans"]])

    def _inside(self, m: Module) -> List[Op]:
        """The ops that ran inside one program execution."""
        v, st = self._by_dev[m.device], self._starts.get(m.device, [])
        return v[bisect.bisect_left(st, m.start):
                 bisect.bisect_left(st, m.start + m.dur)]

    def _kind(self, m: Module) -> str:
        rows = max((o.rows for o in self._inside(m)), default=0)
        return "prefill" if rows > 1 else "decode" if rows == 1 else "other"

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, o: Op) -> Tuple[float, float]:
        return max(o.start, self.t0), min(o.start + o.dur, self.t1)

    def busy_s(self, exclude: Tuple[str, ...] = ()) -> float:
        """Seconds in which some operation ran, averaged over devices,
        leaving out the operations inside programs of the ``exclude``
        kinds."""
        if not self.devices:
            return 0.0
        skip = set()
        for m, k in zip(self.modules, self.kind):
            if k in exclude:
                skip.update(id(o) for o in self._inside(m))
        per = defaultdict(list)
        for o in self.ops:
            if id(o) not in skip:
                per[o.device].append(self._clip(o))
        return sum(_union(v) for v in per.values()) * 1e-9 / len(self.devices)

    def program(self, kind: str) -> Tuple[float, int]:
        """(busy seconds, executions) of the programs of one kind: the
        union of the op intervals inside their executions."""
        mods = [m for m, k in zip(self.modules, self.kind) if k == kind]
        busy = sum(_union([self._clip(o) for o in self._inside(m)])
                   for m in mods)
        return busy * 1e-9, len(mods)

    def kernel(self, name: str) -> Tuple[float, int]:
        """(device seconds, calls) of the operations named after a kernel."""
        hit = [o for o in self.ops if o.name == name]
        return sum(o.dur for o in hit) * 1e-9, len(hit)

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took most device time, by short name (loops and
        conditionals, which hold other ops, left out)."""
        tot = defaultdict(float)
        for o in self.ops:
            if o.name in CONTAINERS:
                continue
            s, e = self._clip(o)
            tot[o.name] += e - s
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest gaps with no operation on a device, each named by the
        host span that overlaps it most (``idle`` when none does)."""
        gaps = []
        per = defaultdict(list)
        for o in self.ops:
            per[o.device].append(self._clip(o))
        for iv in per.values():
            end = self.t0
            for s, e in sorted(iv):
                if s > end:
                    gaps.append((end, s))
                end = max(end, e)
            if self.t1 > end:
                gaps.append((end, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        host = [s for s in self.spans if s.name != WINDOW_SPAN]
        out = []
        for a, b in gaps[:n]:
            best, label = 0.0, "idle"
            for s in host:
                ov = min(b, s.start + s.dur) - max(a, s.start)
                if ov > best:
                    best, label = ov, s.name
            out.append([label, (b - a) * 1e-9])
        return out
