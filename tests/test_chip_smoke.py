"""chip_smoke.py off the chip: it refuses to run without a TPU (and
without the repository next to it), and its phases — the same code the
chip runs at full size — pass here on the CPU at a tiny size."""

import os
import pathlib
import shutil
import subprocess
import sys

import chip_smoke
from repro.configs import get_config
from repro.models.config import reduced_for_smoke

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _run(args, cwd, **env):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full_env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    proc = _run([str(ROOT / "chip_smoke.py")], ROOT)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run([str(tmp_path / "chip_smoke.py")], tmp_path,
                PYTHONPATH="")
    assert proc.returncode != 0
    assert "repository is not next to this script" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_runtime_phase_tiny():
    chip_smoke.runtime_phase(0, n_units=4, pool_bytes=1 << 18,
                             max_bytes=1 << 12)


def test_serve_phase_tiny():
    chip_smoke.serve_phase(0, reduced_for_smoke(get_config("llama3-8b")))


def test_multichip_phase_on_four_host_devices():
    code = ("import chip_smoke; "
            "chip_smoke.multichip_phase(0, pool_bytes=2 << 20)")
    proc = _run(["-c", code], ROOT,
                PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}",
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "every chip holds only its own row" in proc.stdout
