"""Run a cell's traffic against the low-precision control instead of the
program, at the cell's own size, and print what the comparison reads.

    python3 dartbench/control.py --workload <cell> --seeds 11 12 13 --seconds 5

The control (:class:`dartbench.systems.LowPrecisionControl`) is the
plain reference with its payloads stored in bfloat16.  Every seed's
comparison has to come out not correct; the script exits non-zero if
one does not.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from dartbench.run import load_cell, metrics_of, run_cell  # noqa: E402
from dartbench.systems import LowPrecisionControl  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench, cell, config, mix = load_cell(args.workload)
    failed_all = True
    for seed in args.seeds:
        res = run_cell(cell, config, mix, metrics_of(bench, cell, False),
                       seed=seed, seconds=args.seconds, trace=False,
                       devices=[], peaks={},
                       system_factory=LowPrecisionControl)
        failed_all &= not res["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "check": res["check"]}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
