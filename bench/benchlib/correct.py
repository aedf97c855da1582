"""Whether what the timed path served is right: the served tokens of a
sample of requests are fed back through the configuration's plain
reference, and each token's gap below the reference's best logit at its
position is read; the mean and the widest gap are the numbers a cell may
compare against its limits.

Sample: every session of a session mix (all tokens served through the
window's close); for an open loop, the longest request that finished among
those due in the window, then others of them in an order drawn from the
seed, until ``TARGET_TOKENS`` served tokens are in.

The control is the same reference computed in float8 (weights and the inputs
of every weight product): its own first choice at every position, read
against the float32 reference. Which numbers a cell compares, and their
limits, are in bench/checks/<cell>.json; ``judge`` holds the program's
readings and the control's alike against them.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchlib import readers
from benchlib.spec import BENCH

TARGET_TOKENS = 400


def sample(recs, seed: int, t_close: float) -> List[Tuple[np.ndarray, np.ndarray]]:
    sessions = [r for r in recs if r.phase == "session"]
    if sessions:
        out = []
        for r in sessions:
            n = sum(1 for s in r.stamps if s < t_close)
            out.append((np.asarray(r.prompt), np.asarray(r.tokens[:n])))
        return out
    done = [r for r in recs if r.phase == "window" and r.done]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.prompt) + len(r.tokens)))
    rng = np.random.default_rng(seed)
    order = [done[0]] + [done[1:][i] for i in rng.permutation(len(done) - 1)]
    out, n = [], 0
    for r in order:
        if n >= TARGET_TOKENS:
            break
        out.append((np.asarray(r.prompt), np.asarray(r.tokens)))
        n += len(r.tokens)
    return out


def gaps(params, indexer, config, samples, quant: Optional[str] = None
         ) -> np.ndarray:
    """Gap of every served token (or, with ``quant``, of the control's own
    first choice) below the float32 reference's best logit."""
    ref = readers.module("reference", config["reference"])
    out = []
    for prompt, served in samples:
        if not len(served):
            continue
        P = len(prompt)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        rows = np.arange(P - 1, P - 1 + len(served), dtype=np.int32)
        query = np.asarray(served, np.int32)[:, None]
        if quant is not None:
            low = ref.score(params, indexer, config, seq, P, rows, query,
                            quant=quant)
            query = low["argmax"].astype(np.int32)[:, None]
        r = ref.score(params, indexer, config, seq, P, rows, query)
        out.append(r["max"] - r["at"][:, 0])
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def summary(g: np.ndarray) -> Dict[str, Optional[float]]:
    """The numbers a cell may compare: the widest gap and the mean gap over
    every served token of the sample (None when nothing was compared)."""
    if not g.size:
        return {"logit_gap_max": None, "logit_gap_mean": None}
    return {"logit_gap_max": float(g.max()), "logit_gap_mean": float(g.mean())}


def spread(g: np.ndarray) -> Dict[str, float]:
    """Where the gaps lie, for setting a limit: quantiles and the share of
    tokens that are not the reference's first choice."""
    if not g.size:
        return {}
    q = np.quantile(g, [0.5, 0.9, 0.99])
    return {"p50": float(q[0]), "p90": float(q[1]), "p99": float(q[2]),
            "off_top": float((g > 0).mean()), "tokens": int(g.size)}


def judge(found: Dict[str, Optional[float]], lims: Dict
          ) -> Tuple[Dict[str, Dict], bool]:
    """Each number a cell compares beside its limit, and whether every one
    is within it. With no limits (no checks file) nothing passes."""
    checks = {k: {"value": found.get(k), "limit": v.get("limit")}
              for k, v in lims.items()}
    ok = bool(checks) and all(
        c["value"] is not None and c["limit"] is not None
        and c["value"] <= c["limit"] for c in checks.values())
    return checks, ok


def limits(cell_name: str) -> Dict:
    """bench/checks/<cell>.json: the limit of each number compared, with
    the readings it was set from."""
    path = BENCH / "checks" / f"{cell_name}.json"
    if not path.exists():
        return {}
    with open(path) as f:
        return json.load(f)
