"""The program's named scopes and the engine's host spans in a profiler
trace: what the per-layer metrics of the memory-pipeline stages and of the
engine's host turn read.

Built on bench/benchlib/trace.py's ``Trace``:

``extract``      reads the ``.xplane.pb`` the JAX profiler wrote into the
                 dict ``ScopedTrace.from_dict`` reads: the programs, the ops
                 (device, start, duration, short name, token rows, and the
                 scope: the innermost of ``SCOPES`` in the op's ``op_name``
                 metadata, the program's ``jax.named_scope``, "" where none)
                 and the host spans of the benchmark (``bench.*``) and of
                 the engine (``engine.*``).
``ScopedTrace``  a ``trace.Trace`` that also sums device time per scope,
                 splits idle time by the innermost host span open at each
                 instant, names each long idle gap by the innermost span
                 over most of it, and keys its longest ops by
                 ``<scope>/<short name>``. On a trace without scopes or
                 engine spans every reading ``trace.Trace`` gives is the
                 same.

Where an op's ``op_name`` comes from: the HLO of its program, which the
profiler keeps in the ``/host:metadata`` plane (stat ``Hlo Proto``, one
per program id), by the op's instruction name. The op events of a TPU v5e
trace carry no ``op_name`` (no ``tf_op`` stat) under JAX 0.9. A fusion
carries the ``op_name`` of its root, so an op fused across two scopes
counts under its root's.

The readings (ms per decode step, over the ops outside prefill programs,
as ``decode_step_ms`` reads them):

``stage_ms``      one memory-pipeline stage's device time;
``host_turn_ms``  device idle time whose innermost host span is the
                  engine's.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchlib import trace
from benchlib.trace import CONTAINERS, WINDOW_SPAN

METADATA_PLANE = "/host:metadata"
_HLO_STAT = re.compile(r"hlo[ _]proto", re.I)     # "Hlo Proto" / "hlo_proto"
HOST_SPANS = ("bench.", "engine.")
ENGINE_SPAN = "engine."
# the memory pipeline's four stages, the step's K/V write into the pool and
# the dense model around them (core/pipeline.SCOPES); ops under none are
# ``other``
STAGES = ("prepare", "relevancy", "retrieve", "apply")
SCOPES = STAGES + ("kv_write", "dense")
OTHER = "other"
DECODE_ONLY = ("prefill",)        # the program kinds the readings leave out


@dataclasses.dataclass
class ScopedOp(trace.Op):
    scope: str = ""   # innermost of SCOPES in its op_name, "" if none


def scope_of(op_name: str) -> str:
    """The innermost of SCOPES in an ``op_name`` path:
    "jit(decode_paged)/while/body/retrieve/gather" -> "retrieve"."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return ""


def instruction_name(hlo: str) -> str:
    """"%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def extract(xplane_path: str, hidden: int) -> Dict:
    """Device programs, scoped ops and host spans of one xplane file, as a
    dict that ``ScopedTrace.from_dict`` reads and that can be stored as
    JSON."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    ops, modules, spans, devices = [], [], [], []
    instructions = []              # each op's HLO instruction name
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            try:
                dev = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            for ln in plane.lines:
                if ln.name == trace.OPS_LINE:
                    if dev not in devices:
                        devices.append(dev)
                    for e in ln.events:
                        instructions.append(instruction_name(e.name))
                        ops.append([dev, float(e.start_ns),
                                    float(e.duration_ns),
                                    trace.short_name(e.name),
                                    trace.token_rows(e.name, hidden), ""])
                elif ln.name == trace.MODULES_LINE:
                    for e in ln.events:
                        run = dict(e.stats).get("run_id", -1)
                        modules.append([dev, float(e.start_ns),
                                        float(e.duration_ns), e.name,
                                        int(run)])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_SPANS):
                        spans.append([float(e.start_ns), float(e.duration_ns),
                                      e.name])
    with open(xplane_path, "rb") as f:
        _scope_ops(ops, instructions, modules, hlo_op_names(f.read()))
    return {"devices": sorted(devices), "modules": modules, "ops": ops,
            "spans": spans}


def _program_id(module_name: str) -> Optional[int]:
    """"jit_decode_paged(1161777593079090362)" -> 1161777593079090362."""
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


def _scope_ops(ops, instructions, modules, names) -> None:
    """Give each op the scope of its instruction in the HLO of the program
    run around it."""
    runs = defaultdict(list)
    for dev, start, dur, name, _run in modules:
        runs[dev].append((start, start + dur, _program_id(name)))
    for v in runs.values():
        v.sort()
    starts = {d: [r[0] for r in v] for d, v in runs.items()}
    for op, instr in zip(ops, instructions):
        dev, start = op[0], op[1]
        j = bisect.bisect_right(starts.get(dev, []), start) - 1
        if j >= 0 and start < runs[dev][j][1]:
            op[5] = scope_of(names.get(runs[dev][j][2], {}).get(instr, ""))


# -- the HLO the profiler keeps: a protobuf reader for the few fields read --
# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map: key 1, value 2),
# .stat_metadata 5 (map: key 1, value 2); XEventMetadata.id 1, .stats 5;
# XStatMetadata.id 1, .name 2; XStat.metadata_id 1, .bytes_value 6;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2.


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``; a
    length-delimited value comes as its (start, end) in ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} before byte {i}")
        yield key >> 3, v


def _sub(buf, v, field: int) -> List:
    return [x for f, x in _fields(buf, *v) if f == field]


def _text(buf, v) -> str:
    return bytes(buf[v[0]:v[1]]).decode("utf-8", "replace")


def hlo_op_names(xspace: bytes) -> Dict[int, Dict[str, str]]:
    """program id -> {instruction name: op_name}, from the HLO protos of
    the xplane's metadata plane."""
    buf = memoryview(xspace)
    out: Dict[int, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name = _sub(buf, plane, 2)
        if not name or _text(buf, name[0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in _sub(buf, plane, 5):
            for meta in _sub(buf, entry, 2):
                ids, nm = _sub(buf, meta, 1), _sub(buf, meta, 2)
                if ids and nm:
                    stat_names[ids[0]] = _text(buf, nm[0])
        for entry in _sub(buf, plane, 4):
            for meta in _sub(buf, entry, 2):
                ids = _sub(buf, meta, 1)
                for stat in _sub(buf, meta, 5):
                    sid, val = _sub(buf, stat, 1), _sub(buf, stat, 6)
                    stat = stat_names.get(sid[0], "") if sid else ""
                    if ids and val and _HLO_STAT.fullmatch(stat):
                        out[ids[0]] = _instruction_op_names(buf, val[0])
    return out


def _instruction_op_names(buf, hlo_proto) -> Dict[str, str]:
    names = {}
    for module in _sub(buf, hlo_proto, 1):
        for comp in _sub(buf, module, 3):
            for inst in _sub(buf, comp, 2):
                nm = md = None
                for f, v in _fields(buf, *inst):
                    if f == 1:
                        nm = _text(buf, v)
                    elif f == 7:
                        md = v
                op = _sub(buf, md, 2) if md is not None else []
                if nm is not None and op:
                    names[nm] = _text(buf, op[0])
    return names


class ScopedTrace(trace.Trace):
    """``trace.Trace`` of ops that carry their scope, over host spans that
    may nest (``bench.poll`` holds ``engine.poll``, which holds the
    engine's phases)."""

    def __init__(self, devices, modules, ops, spans):
        super().__init__(devices, modules, ops, spans)
        self._segments = _innermost_segments(
            [s for s in spans if s.name != WINDOW_SPAN], self.t0, self.t1)
        self._seg_starts = [a for a, _, _ in self._segments]
        self._memo: Dict[Tuple[str, ...], Dict[str, float]] = {}

    @classmethod
    def from_dict(cls, d: Dict) -> "ScopedTrace":
        return cls(list(d["devices"]),
                   [trace.Module(*m) for m in d["modules"]],
                   [ScopedOp(*o) for o in d["ops"]],
                   [trace.Span(*s) for s in d["spans"]])

    # -- device time by scope ---------------------------------------------

    @property
    def scoped(self) -> bool:
        """Whether any op carries one of the program's scopes."""
        return any(o.scope for o in self.ops)

    def _busy(self, ops) -> float:
        """Seconds covered by the ops' intervals, averaged over devices."""
        per = defaultdict(list)
        for o in ops:
            per[o.device].append(self._clip(o))
        return (sum(trace._union(v) for v in per.values()) * 1e-9
                / max(len(self.devices), 1))

    def scope_s(self, exclude: Tuple[str, ...] = ()) -> Dict[str, float]:
        """Device seconds of each scope (SCOPES, then ``other``), averaged
        over devices, outside programs of the ``exclude`` kinds. A scope's
        time is the union of its ops' intervals (loops and conditionals,
        which hold other ops, count under none); ``other`` is the busy time
        no scoped op covers. So the buckets add up to ``busy_s(exclude)``
        plus the time ops of two scopes overlap."""
        if exclude not in self._memo:
            skip = set()
            for m, k in zip(self.modules, self.kind):
                if k in exclude:
                    skip.update(id(o) for o in self._inside(m))
            kept = [o for o in self.ops if id(o) not in skip]
            scoped = [o for o in kept
                      if o.scope and o.name not in CONTAINERS]
            out = {s: self._busy([o for o in scoped if o.scope == s])
                   for s in SCOPES}
            out[OTHER] = max(self._busy(kept) - self._busy(scoped), 0.0)
            self._memo[exclude] = out
        return dict(self._memo[exclude])

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took most device time, by ``<scope>/<short name>``
        (the bare short name where no scope claims the op; loops and
        conditionals, which hold other ops, left out)."""
        tot = defaultdict(float)
        for o in self.ops:
            if o.name in CONTAINERS:
                continue
            s, e = self._clip(o)
            tot[f"{o.scope}/{o.name}" if o.scope else o.name] += e - s
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    # -- idle time by host span -------------------------------------------

    def _gaps(self) -> List[Tuple[float, float]]:
        """Every interval of the window with no op on a device."""
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
        return gaps

    def _split(self, a: float, b: float) -> Dict[str, float]:
        """ns of [a, b] under each innermost host span; the rest, under no
        span, as ``idle``."""
        out: Dict[str, float] = defaultdict(float)
        covered = 0.0
        j = max(bisect.bisect_right(self._seg_starts, a) - 1, 0)
        for s, e, label in self._segments[j:]:
            if s >= b:
                break
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[label] += ov
                covered += ov
        if b - a > covered:
            out["idle"] += b - a - covered
        return out

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest gaps with no op on a device, each named by the
        innermost host span that covers most of it (``idle`` when no span
        covers any of it). Where spans do not nest, that is the span that
        overlaps the gap most, as ``trace.Trace`` names it."""
        gaps = sorted(self._gaps(), key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            split = self._split(a, b)
            split.pop("idle", None)
            out.append([max(split, key=split.get) if split else "idle",
                        (b - a) * 1e-9])
        return out

    def idle_by_span(self) -> Dict[str, float]:
        """Idle device seconds, averaged over devices, split by the
        innermost host span open at each instant (``idle`` where none)."""
        out: Dict[str, float] = defaultdict(float)
        for a, b in self._gaps():
            for label, ns in self._split(a, b).items():
                out[label] += ns * 1e-9 / max(len(self.devices), 1)
        return dict(out)


def _innermost_segments(spans: List[trace.Span], t0: float, t1: float
                        ) -> List[Tuple[float, float, str]]:
    """Cut [t0, t1] at every span boundary: (start, end, name) of each piece
    under some span, named by the innermost span open there (the latest
    opened: the spans of one thread nest)."""
    bounds = sorted({t0, t1} | {min(max(x, t0), t1) for s in spans
                                for x in (s.start, s.start + s.dur)})
    opens = sorted(spans, key=lambda s: (s.start, -s.dur))
    out, active, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(opens) and opens[k].start <= a:
            active.append(opens[k])
            k += 1
        active = [s for s in active if s.start + s.dur > a]
        if active:
            out.append((a, b, active[-1].name))
    return out


# -- the readings --------------------------------------------------------


def stage_ms(tr: Optional[ScopedTrace], decode_steps: int, stage: str,
             cell: str = "") -> Optional[float]:
    """Device ms per decode step of the ops under one scope, over the ops
    ``decode_step_ms`` reads (every op outside prefill programs) and the
    engine's decode steps. None without a trace or for a program that
    names no scope; an error where the program does but the stage took no
    time in a window that should have decoded."""
    if tr is None or not tr.scoped:
        return None
    sec = tr.scope_s(exclude=DECODE_ONLY)[stage]
    if not decode_steps or sec <= 0:
        raise RuntimeError(
            f"{stage}: {decode_steps} decode steps and {sec!r} s of "
            f"'{stage}' ops in the traced window of {cell}, whose program "
            f"names its stages")
    return 1e3 * sec / decode_steps


def host_turn_ms(tr: Optional[ScopedTrace], decode_steps: int,
                 cell: str = "") -> Optional[float]:
    """Device idle ms per decode step whose innermost host span is one of
    the engine's (``engine.poll`` and the phases inside it). None without
    a trace or for a program without those spans."""
    if tr is None:
        return None
    idle = [v for k, v in tr.idle_by_span().items()
            if k.startswith(ENGINE_SPAN)]
    if not idle:
        return None
    if not decode_steps:
        raise RuntimeError(f"host_turn_ms: no decode steps in the traced "
                           f"window of {cell}, which decodes in every window")
    return 1e3 * sum(idle) / decode_steps

