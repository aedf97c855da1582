#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip at the
cell's own size: for each seed, one run of the cell (short window) with the
gaps of what the program served below the float32 reference, and the same
gaps of the control (the reference in float8, its own first choice at every
position), each judged against the cell's limits (bench/checks/<cell>.json)
by the comparison the benchmark makes. With ``--fault`` the program runs
with that fault planted (bench/benchlib/faults.py) and its readings and
verdict are the ones reported. One process, one JSON line per seed.

    python3 bench/calibrate.py --workload <cell> --seconds 8 --seeds 1 2 3
    python3 bench/calibrate.py --workload <cell> --seconds 8 --seeds 1 2 3 \
        --fault first_pages --no-control

The limit goes in bench/checks/<cell>.json, above every sound reading and
below every control reading, with the readings beside it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--fault", default=None,
                    help="plant this fault of bench/benchlib/faults.py")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("bench: calibrate needs a TPU", file=sys.stderr)
        return 2
    from benchlib import faults, runner, spec

    cell = spec.load_cell(args.workload)
    fault = faults.FAULTS[args.fault] if args.fault else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = runner.run_cell(cell, seed, args.seconds, False, t_start=t0,
                              control=not args.no_control, fault=fault)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, "sound": res["sound"],
                          "control": res.get("control"),
                          "metrics": res["metrics"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
