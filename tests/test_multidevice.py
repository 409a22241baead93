"""Run device-plane checks in subprocesses with forced host device counts.

The main pytest process must keep jax at 1 device (per instructions), so
anything needing a mesh > 1 runs as a child python process.
"""

import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).parent
SRC = str(HERE.parent / "src")

pytestmark = pytest.mark.multidevice


def run_script(name, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(HERE / "multidevice" / name)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, (
        f"{name} failed\n--- stdout ---\n{proc.stdout[-4000:]}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}")
    assert "ALL:OK" in proc.stdout
    return proc.stdout


def test_shmem_and_team_collectives():
    out = run_script("shmem_checks.py")
    assert "CHECK:shmem_put_ring:OK" in out
    assert "CHECK:team_psum:OK" in out
    assert "CHECK:sharded_heap_putget:OK" in out
    assert "CHECK:sharded_heap_lane_path:OK" in out


def test_pallas_comm_kernels_vs_oracle():
    out = run_script("kernel_checks.py")
    assert "CHECK:ring_reduce_scatter_bf16:OK" in out
