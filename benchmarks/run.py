"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV (spec) and writes
benchmarks/out/*.csv.  Mapping to the paper:

    put_get    — figs 8/9 (DTCT), 10/11 (DTIT), 12–15 (bandwidth),
                 + the §V.C constant-overhead model fit
                 + the typed_api series (GlobalArray front-end vs raw
                 byte API; runs in --quick too)
    collective — §IV.B.5 collectives overhead
    lock       — §IV.B.6 MCS lock + §VI balanced-tail comparison
    teamlist   — §IV.B.2 slot allocator + §VI O(1) variant
    alloc      — §IV.B.3 allocation/dereference costs

Roofline tables (§Roofline) are produced by the dry-run pipeline
(``python -m repro.launch.dryrun --all`` then
``python -m benchmarks.roofline``), not by this wall-clock harness.
"""

from __future__ import annotations

import argparse
import json
import sys

from .common import OUT_DIR, Report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full message-size sweep (to 2MiB)")
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: tiny sweeps, 2 repeats — checks every "
                         "suite still runs, numbers are not meaningful")
    ap.add_argument("--only", default=None,
                    help="run a single suite: put_get|collective|lock|"
                         "teamlist|alloc")
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args()
    if args.quick:
        args.repeats = 2
        args.full = False

    from repro.compile_cache import enable_compile_cache

    from . import (alloc_bench, collective_bench, lock_bench, put_get,
                   teamlist_bench)

    enable_compile_cache()

    slow_repeats = args.repeats if args.quick else max(args.repeats, 50)
    suites = {
        "put_get": lambda r: put_get.run(r, full=args.full,
                                         repeats=args.repeats,
                                         quick=args.quick),
        "collective": lambda r: collective_bench.run(
            r, repeats=args.repeats),
        "lock": lambda r: lock_bench.run(r, repeats=slow_repeats),
        "teamlist": lambda r: teamlist_bench.run(r, repeats=slow_repeats),
        "alloc": lambda r: alloc_bench.run(r, repeats=slow_repeats),
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    failures = 0
    for name, fn in suites.items():
        print(f"# === suite: {name} ===", flush=True)
        report = Report()
        try:
            fn(report)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# suite {name} FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
        path = report.save(f"{name}.csv")
        print(f"# wrote {path}", flush=True)

    if "put_get" in suites:
        # machine-readable engine trajectory (schema BENCH_engine/v8:
        # dispatch counts + µs/op for blocking vs coalesced vs
        # per-target vs mixed-size, the flush cost model — cold
        # compile vs warm plan-cache-hit µs/op and steady-state
        # recompile count — plus the v6 strided + narray series:
        # strided-vs-contiguous µs/op ratio, 1-dispatch strided runs,
        # varying-stride zero-recompile pin, tiled NArray column
        # gather — and the v7 faults series: clean vs faulted
        # flush µs/op, bounded retries, survivor throughput after
        # a unit death): the perf numbers dashboards diff across
        # PRs.
        # scripts/check_bench_schema.py (run by `make verify`) fails
        # CI on schema drift.
        try:
            profile = put_get.engine_profile(repeats=args.repeats,
                                             quick=args.quick)
            OUT_DIR.mkdir(parents=True, exist_ok=True)
            jpath = OUT_DIR / "BENCH_engine.json"
            with open(jpath, "w") as f:
                json.dump(profile, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"# wrote {jpath}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# engine profile FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
