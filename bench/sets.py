#!/usr/bin/env python3
"""Runs of one cell, one process after another, and the spread of each
end-to-end metric over them: how the bounds in BENCHMARK.json are set.

    python3 bench/sets.py --workload <cell> --seconds 20 --out <dir> \
        --seeds 11 12 13 14 15 16 --sets 2 [--trace-seeds 21 22 23]

Each set runs ``bench/run.py`` once per seed (the same seeds in every set),
then each trace seed once with ``--trace 1``. Every run's standard output
and error go to ``<dir>/<cell>.<set>.<seed>.{out,err}``. The summary, one
JSON line per set and metric on standard output, gives the median, the
quartiles (Python's ``statistics.quantiles``, n=4) and the spread (Q3 - Q1)
/ median; a last line counts the runs with ``correct`` true. This process
never touches JAX, so each run has the chip to itself.
"""
import argparse
import json
import pathlib
import statistics
import subprocess
import sys

BENCH = pathlib.Path(__file__).resolve().parent


def one(args, out: pathlib.Path, tag: str, seed: int, trace: int):
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", args.workload,
           "--seed", str(seed), "--seconds", str(args.seconds),
           "--trace", str(trace)]
    stem = out / f"{args.workload}.{tag}.{seed}"
    with open(f"{stem}.out", "w") as fo, open(f"{stem}.err", "w") as fe:
        rc = subprocess.run(cmd, stdout=fo, stderr=fe).returncode
    lines = pathlib.Path(f"{stem}.out").read_text().strip().splitlines()
    res = json.loads(lines[-1]) if rc == 0 and lines else None
    print(json.dumps({"run": stem.name, "rc": rc,
                      "correct": res and res["correct"],
                      "metrics": res and {k: v["value"] for k, v in
                                          res["metrics"].items()},
                      "checks": res and res["checks"]}), flush=True)
    return res


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return {"median": med, "q1": q1, "q3": q3, "spread": (q3 - q1) / med,
            "min": min(values), "max": max(values), "n": len(values)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    results = []
    for s in range(args.sets if args.seeds else 0):
        runs = [one(args, out, f"set{s + 1}", seed, 0) for seed in args.seeds]
        results += runs
        ok = [r for r in runs if r]
        if len(ok) < 2:
            continue
        for name in ok[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in ok]
            print(json.dumps({"set": s + 1, "metric": name,
                              **spread(vals), "values": vals}), flush=True)
    results += [one(args, out, "trace", seed, 1) for seed in args.trace_seeds]
    n_ok = sum(1 for r in results if r and r["correct"])
    print(json.dumps({"workload": args.workload, "runs": len(results),
                      "correct": n_ok}), flush=True)
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
