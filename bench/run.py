#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name through
BENCHMARK.json (bench/configs/, bench/traffic/). The system under test is
the package under src/. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of the window gives
its per-layer metrics, the device's busy and window seconds, and a
breakdown. Details of the run go to standard error; the numbers compared
for ``correct`` come last there, and last in the result line
(``checks``).

Without a TPU, with fewer chips than the cell asks for, or without the
program under src/, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the extracted trace (.json.gz) here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail("--seed must be >= 0")
    if not (ROOT / "src" / "repro" / "serving").is_dir():
        return fail(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(BENCH))
    from benchlib import spec

    try:
        cell = spec.load_cell(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX found no device: {e}")
    if devs[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips; JAX found "
                    f"{len(devs)}")
    from benchlib import runner

    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START, keep_trace=args.keep_trace)
    result["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": cell.chips,
                        **result["device"]}
    result.pop("sound")
    checks = result.pop("checks")
    result["checks"] = checks            # last key of the line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
