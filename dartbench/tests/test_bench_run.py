"""Each one-chip cell's run, end to end on the CPU at a tiny size: correct
against the reference as the program stands, not correct under the
control and under each fault the cell can have, and no result without a
TPU.  Cells on four chips run the same checks in a child process with
four virtual devices (``test_bench_four_chip.py``)."""

import dataclasses
import json
import pathlib
import re
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dartbench import drive, run, systems
from repro.core import onesided

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**33 + 5


def _tiny(cell_name):
    bench, cell, config, mix = run.load_cell(cell_name)
    config = dict(config, window_bytes_per_unit=1 << 20)
    mix = dict(mix, epochs=min(mix["epochs"], 24))
    if mix["length"]["dist"] == "log_uniform":
        mix["length"] = dict(mix["length"],
                             min_elems=min(mix["length"]["min_elems"], 64),
                             max_elems=min(mix["length"]["max_elems"], 4096))
    return bench, cell, config, mix


def _run(cell_name, trace=False, factory=systems.DartSystem, seconds=0.5):
    bench, cell, config, mix = _tiny(cell_name)
    return run.run_cell(cell, config, mix,
                        run.metrics_of(bench, cell, trace), seed=SEED,
                        seconds=seconds, trace=trace,
                        devices=jax.devices()[:cell["chips"]],
                        peaks={"hbm_bytes_per_s": 819e9},
                        system_factory=factory)


WORKLOADS = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
#: cells run in this process, on its one device
CELLS = [w["name"] for w in WORKLOADS if w["chips"] == 1]
#: cells run on four virtual devices in a child process
FOUR_CHIP_CELLS = [w["name"] for w in WORKLOADS if w["chips"] == 4]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_and_reports_its_metrics(cell):
    res = _run(cell)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    assert len(res["metrics"]) >= 2
    assert list(res)[-1] == "check"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["check"].values())


def test_traced_run_reads_its_stretch():
    res = _run("rate-small", trace=True, seconds=1.0)
    assert res["correct"], res["check"]
    assert "enqueue_us.rate" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_low_precision_control_is_not_correct(cell):
    res = _run(cell, factory=systems.LowPrecisionControl)
    assert not res["correct"]
    assert res["check"]["wrong_window_elems"]["value"] > 0


def _unchanged(self, arena, run, disjoint=True):
    return arena


_ORIG_PUT = onesided.CommEngine._dispatch_put_run


def _half_batch(self, arena, run, disjoint=True):
    return _ORIG_PUT(self, arena, run[:max(1, len(run) // 2)], disjoint)


_ORIG_DECODE = onesided._host_decode


def _altered_answer(raw, shape, dtype):
    out = _ORIG_DECODE(raw, shape, dtype)
    out.reshape(-1)[0] += 1
    return out


def _row_shifted(self, arena, run, disjoint=True):
    rows = arena.shape[0]
    return _ORIG_PUT(self, arena, [
        dataclasses.replace(op, row=(op.row + 1) % rows) for op in run],
        disjoint)


_ORIG_GET = onesided.CommEngine._dispatch_get_run


def _exchange_left_out(self, arena, run):
    """The gather as the first device computes it alone: the rows that
    the other devices hold read as zeros."""
    held = arena.addressable_shards[0].index[0]
    rows = jnp.arange(arena.shape[0])[:, None]
    mine = (rows >= (held.start or 0)) & (rows < (held.stop or len(rows)))
    return _ORIG_GET(self, jnp.where(mine, arena, jnp.uint8(0)), run)


FAULTS = [
    # (fault, where it is planted, cells that can have it; a put landing
    # in another chip's row and the exchange between chips are faults
    # only a cell on several chips can have)
    ("state_unchanged", "_dispatch_put_run", _unchanged,
     CELLS + FOUR_CHIP_CELLS),
    ("half_batch", "_dispatch_put_run", _half_batch,
     ["rate-small", "bw-large"]),
    ("answer_altered", "_host_decode", _altered_answer,
     ["lat-small", "bw-large", "lat-small-4chip"]),
    ("row_shifted", "_dispatch_put_run", _row_shifted, ["lat-small-4chip"]),
    ("exchange_left_out", "_dispatch_get_run", _exchange_left_out,
     ["lat-small-4chip"]),
]


def plant(monkeypatch, fault: str) -> None:
    """Plant ``fault`` in the program for the rest of the test."""
    _, attr, fn, _ = next(f for f in FAULTS if f[0] == fault)
    if attr == "_host_decode":
        monkeypatch.setattr(onesided, attr, fn)
    else:
        monkeypatch.setattr(onesided.CommEngine, attr, fn)


@pytest.mark.parametrize("cell,fault", [
    (c, f) for f, _, _, cells in FAULTS for c in cells if c in CELLS])
def test_fault_makes_the_run_not_correct(monkeypatch, cell, fault):
    plant(monkeypatch, fault)
    res = _run(cell)
    assert not res["correct"], (fault, res["check"])


def test_peaks_refuse_an_unknown_device_kind():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.BenchError, match="no peaks"):
        run.peaks_for("cpu")


def test_run_refuses_to_measure_without_a_tpu():
    with pytest.raises(run.BenchError, match="no TPU"):
        run.tpu_devices(1)
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "dartbench" / "run.py"), "--workload",
         "lat-small", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(__import__("os").environ, **env))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_traced_window_begins_its_stretch_when_one_epoch_outlasts_it():
    # one epoch of 50 ms against a window of 10 ms: the stretch still
    # begins (after the first epoch) and ends, and holds an epoch
    class _Slow(drive.Driver):
        def issue(self, e):
            time.sleep(0.05)

    class _Tracer:
        skip_s, span_s = 0.002, 0.004

        def __init__(self):
            self.calls = []

        def begin(self):
            self.calls.append("begin")

        def end(self):
            self.calls.append("end")

    traffic = types.SimpleNamespace(n_epochs=2, period=1,
                                    kind=np.zeros(2, np.int64))
    system = types.SimpleNamespace(counters=lambda: {})
    tracer = _Tracer()
    win = _Slow(system, traffic).window(0.01, tracer)
    assert tracer.calls == ["begin", "end"]
    i0, i1 = win.stretch
    assert i1 - i0 >= 1


# -- BENCHMARK.json against the benchmark's contract ----------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_its_contract():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["dartbench"]
    assert (ROOT / bench["command"][1]).is_file()
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    cells = bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (ROOT / "dartbench" / "traffic" / f"{w['traffic']}.json"
                ).is_file()
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "dartbench" / "metrics" / f"{m['name']}.py").is_file()
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in cells}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert e2e[m["moves"]] and "\n" not in m["layer"]
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in cells:
        reported = [m for m in bench["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])
