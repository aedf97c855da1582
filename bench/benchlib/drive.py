"""The benchmark's own loop around the serving engine: it submits each
request at its due time through ``Engine.submit``, pumps ``Engine.poll`` in
between, and after every poll stamps each token that landed. The program is
driven only through submit / poll / busy and read only through the request
handles.

Host spans (``jax.profiler.TraceAnnotation``) mark what the loop is doing,
so a traced run can say what the host did in each idle gap of the device:
``bench.submit``, ``bench.poll``, ``bench.stamp``, ``bench.wait``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

pc = time.perf_counter


@dataclasses.dataclass
class Rec:
    rid: int
    prompt: np.ndarray
    max_new: int
    phase: str                 # "session" | "warm" | "window" | "drain"
    due: float                 # host clock
    handle: object = None
    submitted: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def tokens(self) -> List[int]:
        return list(self.handle.tokens)

    @property
    def admitted(self) -> Optional[float]:
        return self.handle.admitted

    @property
    def done(self) -> bool:
        return self.handle.done


class ServingLoop:
    def __init__(self, engine, Request):
        self.eng = engine
        self.Request = Request
        self.recs: Dict[int, Rec] = {}
        self._active: List[Rec] = []
        self._rid = 0
        self.polls = 0

    def submit(self, prompt, max_new: int, phase: str, due: float) -> Rec:
        rec = Rec(self._rid, prompt, int(max_new), phase, due)
        self._rid += 1
        with TraceAnnotation("bench.submit"):
            rec.submitted = pc()
            rec.handle = self.eng.submit(self.Request(rec.rid, prompt,
                                                      rec.max_new))
        self.recs[rec.rid] = rec
        self._active.append(rec)
        return rec

    def poll(self) -> None:
        with TraceAnnotation("bench.poll"):
            self.eng.poll()
        self.polls += 1
        with TraceAnnotation("bench.stamp"):
            now = pc()
            keep = []
            for rec in self._active:
                n = len(rec.handle.tokens)
                if n > len(rec.stamps):
                    rec.stamps.extend([now] * (n - len(rec.stamps)))
                if not rec.handle.done:
                    keep.append(rec)
            self._active = keep

    def warm_shapes(self, groups) -> None:
        """Serve each group of requests to completion, one group at a time
        (a group is admitted together)."""
        for group in groups:
            recs = [self.submit(p, n, "shape", pc()) for p, n in group]
            while not all(r.done for r in recs):
                self.poll()

    def start_sessions(self, sessions, warm_tokens: int,
                       limit_s: float = 1200.0) -> List[Rec]:
        recs = [self.submit(p, n, "session", pc()) for p, n in sessions]
        t_end = pc() + limit_s
        while any(len(r.stamps) < warm_tokens for r in recs):
            if pc() > t_end:
                raise RuntimeError("sessions did not start decoding in "
                                   f"{limit_s:.0f}s")
            self.poll()
        return recs

    def run(self, items, t_base: float, t_open: float, t_close: float,
            drain_s: float, on_open: Callable[[], None],
            on_close: Callable[[], None]) -> None:
        """Open loop over ``items`` (due times relative to ``t_base``) until
        the window [t_open, t_close) has closed and every request due in it
        has its first token, or ``drain_s`` after the close."""
        i, n = 0, len(items)
        opened = closed = False
        window_recs: List[Rec] = []
        while True:
            now = pc()
            if not opened and now >= t_open:
                opened = True
                on_open()
            if not closed and now >= t_close:
                closed = True
                on_close()
            if closed and (now >= t_close + drain_s or all(
                    r.stamps for r in window_recs)):
                return
            while i < n and t_base + items[i].due <= now:
                it = items[i]
                rec = self.submit(it.prompt, it.max_new, it.phase,
                                  t_base + it.due)
                if it.phase == "window":
                    window_recs.append(rec)
                i += 1
            if self.eng.busy():
                self.poll()
                continue
            nxt = t_base + items[i].due if i < n else float("inf")
            if not opened:
                nxt = min(nxt, t_open)
            if not closed:
                nxt = min(nxt, t_close)
            if nxt == float("inf"):
                if closed:
                    return
                nxt = now + 0.001
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, nxt - pc()))


def percentile(x, q: float) -> Optional[float]:
    x = np.asarray(list(x), np.float64)
    return float(np.percentile(x, q)) if x.size else None


def window_stats(recs, t_open: float, t_close: float) -> Dict:
    """End-to-end figures of the window from the stamps."""
    toks = 0
    gaps, ttft, qwait, late = [], [], [], []
    attempted = failed = 0
    for r in recs:
        inside = [s for s in r.stamps if t_open <= s < t_close]
        toks += len(inside)
        for a, b in zip(r.stamps, r.stamps[1:]):
            if t_open <= a and b < t_close:
                gaps.append(b - a)
        if r.phase == "window":
            attempted += 1
            late.append(r.submitted - r.due)
            if r.stamps:
                ttft.append(r.stamps[0] - r.due)
            else:
                failed += 1
            if r.admitted is not None:
                qwait.append(r.admitted - r.due)
        elif r.phase == "session":
            attempted += 1
            failed += not inside
    return {"tokens": toks, "tok_s": toks / (t_close - t_open),
            "itl_s": gaps, "ttft_s": ttft, "queue_wait_s": qwait,
            "late_s": late, "attempted": attempted, "failed": failed}
