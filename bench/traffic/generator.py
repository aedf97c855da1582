"""The one traffic generator. A mix is a JSON file of parameters beside this
module (``<mix>.json``); the generator turns it and a seed into a plan:

* ``engine``    n_slots and max_len of the deployment the mix is served by;
* ``sessions``  requests admitted in set-up and decoding through the window
                (count, prompt-length distribution, max_new, and how many
                tokens each emits before the window opens);
* ``arrivals``  an open loop: ``process`` "poisson" (exponential gaps) at
                ``rate_per_s``, with prompt and output lengths from their
                distributions, running ``warm_s`` in set-up before the
                window and up to ``drain_s`` after it;
* ``warm``      which prompt-length buckets are admitted together and so are
                warmed at every batch size (``batched_upto``).

Every seed gets the same requests: each phase (warm, window, drain) holds
rate x its length of them, with lengths and gaps drawn once from
``shape_seed``. The seed only orders them, spaces them in that order, and
draws their token ids, so seeds differ in order and not in work.

Length distributions: {"dist": "uniform", "lo", "hi"} or {"dist":
"lognormal", "median", "sigma", "lo", "hi"} (clipped to [lo, hi]).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class Item:
    due: float                 # seconds after the open loop starts
    prompt: np.ndarray
    max_new: int
    phase: str                 # "warm" | "window" | "drain"


@dataclasses.dataclass
class Plan:
    shape_groups: List[List[Tuple[np.ndarray, int]]]
    sessions: List[Tuple[np.ndarray, int]]
    session_warm_tokens: int
    arrivals: List[Item]
    warm_s: float
    drain_s: float


def lengths(dist: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    elif dist["dist"] == "lognormal":
        x = float(dist["median"]) * np.exp(float(dist["sigma"])
                                           * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def gaps(arr: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return rng.exponential(1.0 / float(arr["rate_per_s"]), n)


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def shape_groups(traffic: Dict, vocab: int, rng) -> List[List]:
    """Requests that walk every shape the mix can reach, before any clock
    runs: for each power-of-two prompt bucket up to ``warm.batched_upto``,
    groups of 1..n_slots of its shortest prompt (admitted together); then
    one prompt at each power of two up to the longest prompt, which walks
    the chunk and decode views a long request passes through."""
    arr = traffic.get("arrivals")
    if not arr:
        return []
    p, slots = traffic["prompt"], int(traffic["engine"]["n_slots"])
    lo, hi = int(p["lo"]), int(p["hi"])
    upto = int(traffic.get("warm", {}).get("batched_upto", 0))
    toks = lambda n: rng.integers(0, vocab, int(n)).astype(np.int32)
    groups = []
    b = _pow2_at_least(lo)
    while b <= min(upto, _pow2_at_least(hi)):
        plen = max(lo, b // 2 + 1)
        groups += [[(toks(plen), 1) for _ in range(n)]
                   for n in range(1, slots + 1)]
        b *= 2
    b = _pow2_at_least(lo)
    while True:
        groups.append([(toks(min(b, hi)), 2)])
        if b >= hi:
            break
        b *= 2
    return groups


def plan(traffic: Dict, seed: int, vocab: int, seconds: float) -> Plan:
    rng = np.random.default_rng(seed)
    toks = lambda n: rng.integers(0, vocab, int(n)).astype(np.int32)
    shape = np.random.default_rng(int(traffic.get("shape_seed", 0)))
    sessions, warm_tokens = [], 0
    if traffic.get("sessions"):
        s = traffic["sessions"]
        plens = rng.permutation(lengths(s["prompt"], int(s["count"]), shape))
        sessions = [(toks(n), int(s["max_new"])) for n in plens]
        warm_tokens = int(s.get("warm_tokens", 1))
    items: List[Item] = []
    arr = traffic.get("arrivals")
    warm_s = float(traffic.get("warm_s", 0.0))
    drain_s = float(traffic.get("drain_s", 0.0))
    if arr:
        t = 0.0
        for phase, span in (("warm", warm_s), ("window", float(seconds)),
                            ("drain", drain_s)):
            n = int(round(float(arr["rate_per_s"]) * span))
            if not n:
                t += span
                continue
            g = rng.permutation(gaps(arr, n, shape))
            g = g * (span / g.sum())
            pl = rng.permutation(lengths(traffic["prompt"], n, shape))
            ol = rng.permutation(lengths(traffic["output"], n, shape))
            # the first request of a phase is due at its start
            due = t + np.concatenate([[0.0], np.cumsum(g)[:-1]])
            items += [Item(float(d), toks(a), int(b), phase)
                      for d, a, b in zip(due, pl, ol)]
            t += span
    return Plan(shape_groups=shape_groups(traffic, vocab, rng),
                sessions=sessions, session_warm_tokens=warm_tokens,
                arrivals=items, warm_s=warm_s, drain_s=drain_s)
