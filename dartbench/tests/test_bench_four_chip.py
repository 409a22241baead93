"""Each four-chip cell's run, end to end on four virtual CPU devices at a
tiny size, in a child process (this one keeps one device): where the
units go, correct as the program stands (untraced and traced), not
correct under the control and under each fault the cell can have."""

import functools
import json
import os
import pathlib
import subprocess
import sys

import pytest

from test_bench_run import FAULTS, FOUR_CHIP_CELLS

HERE = pathlib.Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def _child(cell: str) -> dict:
    """``{check name: its JSON line}`` from one child run of ``cell``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(HERE / "four_chip_child.py"), cell],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(line) for line in p.stdout.splitlines()
             if line.startswith("{")]
    return {r.pop("name"): r for r in lines}


@pytest.mark.parametrize("cell", FOUR_CHIP_CELLS)
def test_layouts_place_the_units(cell):
    # one device holds every unit's row; four hold one row each; three
    # devices for four units are refused
    out = _child(cell)["layouts"]
    assert out["1"] == [4]
    assert out["4"] == [1, 1, 1, 1]
    assert out["refused"]


@pytest.mark.parametrize("cell", FOUR_CHIP_CELLS)
def test_four_chip_cell_is_correct(cell):
    res = _child(cell)["program"]
    assert res["correct"], res["check"]
    assert res["count"] == 4 and res["attempted"] > 0
    assert all(v == 0 for v in res["check"].values())


@pytest.mark.parametrize("cell", FOUR_CHIP_CELLS)
def test_four_chip_traced_run_reports_its_readers(cell):
    # the host-clock and program-span readers read the traced stretch;
    # the CPU profile holds no ``/device:`` plane, so the device-trace
    # readers read nothing there, and never a 0
    res = _child(cell)["traced"]
    assert res["correct"], res["check"]
    assert res["metrics"]["flush_host_us.lat4"] > 0
    assert res["metrics"]["wait_us.lat4"] > 0
    device = {"device_us_per_dispatch.lat4", "idle_share.lat4",
              "collective_us_per_dispatch.lat4"}
    assert not device & set(res["metrics"])


@pytest.mark.parametrize("cell", FOUR_CHIP_CELLS)
def test_four_chip_low_precision_control_is_not_correct(cell):
    res = _child(cell)["control"]
    assert not res["correct"]
    assert res["check"]["wrong_window_elems"] > 0


@pytest.mark.parametrize("cell,fault", [
    (c, f) for f, _, _, cells in FAULTS for c in cells
    if c in FOUR_CHIP_CELLS])
def test_four_chip_fault_makes_the_run_not_correct(cell, fault):
    res = _child(cell)[fault]
    assert not res["correct"], (fault, res["check"])
    assert res["check"]["window_compiles"] == 0
