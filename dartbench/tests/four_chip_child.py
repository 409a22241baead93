"""Run a four-chip cell's checks at a tiny size on four virtual CPU
devices, in a process of its own (the test process keeps one device):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 dartbench/tests/four_chip_child.py <cell>

Prints one JSON line per check: where the units go on one device and
on four, the run as the program stands (untraced and traced), under the
low-precision control, and under each fault the cell can have
(``test_bench_run.FAULTS``).
"""

from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1]), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

import test_bench_run as tb  # noqa: E402
from dartbench import systems  # noqa: E402


def layouts(cell: str) -> dict:
    """Where the cell's units go on one device and on one device per
    unit, as rows held per device; and whether a count of devices that
    is neither is refused."""
    from repro.core import deref
    _, _, config, _ = tb._tiny(cell)
    units = int(config["units"])
    out = {}
    for n in (1, units):
        system = systems.DartSystem(config, jax.devices()[:n])
        try:
            poolid, _, _ = deref(system.ctx.heap, system.ctx.teams_by_slot,
                                 system.ga.gptr)
            arena = system.ctx.state[poolid]
            out[str(n)] = sorted(s.data.shape[0]
                                 for s in arena.addressable_shards)
        finally:
            system.close()
    try:
        systems.DartSystem(config, jax.devices()[:units - 1])
        out["refused"] = False
    except ValueError:
        out["refused"] = True
    return out


def result(res: dict) -> dict:
    return {"correct": res["correct"], "attempted": res["attempted"],
            "count": res["device"]["count"],
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "check": {k: c["value"] for k, c in res["check"].items()}}


def main(cell: str) -> int:
    if len(jax.devices()) < 4:
        print("four_chip_child: needs 4 devices", file=sys.stderr)
        return 2
    print(json.dumps({"name": "layouts", **layouts(cell)}), flush=True)
    print(json.dumps({"name": "program", **result(tb._run(cell))}),
          flush=True)
    print(json.dumps({"name": "traced", **result(tb._run(
        cell, trace=True, seconds=1.0))}), flush=True)
    print(json.dumps({"name": "control", **result(tb._run(
        cell, factory=systems.LowPrecisionControl))}), flush=True)
    for fault, _, _, cells in tb.FAULTS:
        if cell not in cells:
            continue
        with pytest.MonkeyPatch.context() as mp:
            tb.plant(mp, fault)
            res = tb._run(cell)
        print(json.dumps({"name": fault, **result(res)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
