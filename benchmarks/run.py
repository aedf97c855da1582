"""Benchmark orchestrator — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (stdout) and writes the
structured payloads modules deposit via ``common.record_result`` to
``--out`` (default ``BENCH_PR9.json``) at the repo root (method, tokens/s,
per-stage fractions, ...) AND to the stable ``BENCH.json`` "latest" alias,
so the perf trajectory is diffable across PRs from one canonical filename
(the per-PR path used to be hardcoded, which left every later PR's
trajectory empty).

``--smoke``: tiny configs and single iterations (run in CI so benchmark code
can't silently rot). Smoke numbers are execution proofs, not measurements.
``--only SUBSTR``: run only benches whose label contains SUBSTR.
"""
import argparse
import json
import os
import sys
import time
import traceback

# allow both `python benchmarks/run.py` and `python -m benchmarks.run`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common
from repro.launch.compile_cache import enable_compile_cache
from benchmarks import (bench_memory_fraction, bench_kernel_speedup,
                        bench_e2e, bench_energy, bench_comm_bytes,
                        bench_hetero_overlap, bench_hetero_sharded,
                        bench_retrieval,
                        bench_main_mesh, bench_fused_decode, bench_router)

BENCHES = [
    ("memory_fraction (Fig 3/4/5)", bench_memory_fraction),
    ("kernel_speedup (Fig 9/10r)", bench_kernel_speedup),
    ("e2e_speedup (Fig 8/10l/11/12)", bench_e2e),
    ("energy (Table 3)", bench_energy),
    ("comm_bytes (App C.1/Fig 16)", bench_comm_bytes),
    ("hetero_overlap (§5.3 offload)", bench_hetero_overlap),
    ("hetero_sharded (Fig 6a per-shard offload)", bench_hetero_sharded),
    ("retrieval (dynamic RAG/MaC service)", bench_retrieval),
    ("main_mesh (Fig 6a seq-parallel apply)", bench_main_mesh),
    ("fused_decode (multi-step scan windows)", bench_fused_decode),
    ("router (fleet serving, Poisson load)", bench_router),
]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(ROOT, "BENCH_PR9.json")
LATEST = os.path.join(ROOT, "BENCH.json")   # stable cross-PR alias


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs, 1 iteration (CI execution check)")
    ap.add_argument("--only", default="",
                    help="run only benches whose label contains this")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="structured-results path; when it is the default "
                         "per-PR artifact the stable BENCH.json latest "
                         "alias is refreshed alongside it (a scratch --out "
                         "leaves the committed alias untouched)")
    args = ap.parse_args()
    enable_compile_cache()
    common.set_smoke(args.smoke)
    print("name,us_per_call,derived")
    failures = 0
    rows = []
    for label, mod in BENCHES:
        if args.only and args.only not in label:
            continue
        t0 = time.time()
        try:
            for r in mod.run():
                rows.append(r)
                print(r, flush=True)
            print(f"# {label}: done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {label}: FAILED\n# " +
                  traceback.format_exc().replace("\n", "\n# "), flush=True)
    payload = {"smoke": common.is_smoke(), "results": common.results(),
               "rows": rows}
    if (args.only or failures) and os.path.exists(args.out):
        # partial or partially-failed run: refresh the sections + rows that
        # actually ran; keep the rest of the committed cross-PR artifact
        # intact (every results payload carries its own "smoke" stamp from
        # common.record_result)
        with open(args.out) as f:
            old = json.load(f)
        old.setdefault("results", {}).update(payload["results"])
        by_name = {r.split(",", 1)[0]: r for r in rows}
        old["rows"] = [by_name.pop(r.split(",", 1)[0], r)
                       for r in old.get("rows", [])] + list(by_name.values())
        payload = old
    paths = [args.out]
    if os.path.abspath(args.out) == os.path.abspath(DEFAULT_OUT):
        paths.append(LATEST)     # the alias tracks the canonical artifact
    for path in paths:
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {path}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
