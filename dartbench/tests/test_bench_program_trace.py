"""The readers of the program's own span totals: known answers on fixed
totals, nothing without a trace, a span or the program's tracing module,
and a value in a traced run of each cell that lists them."""

import importlib.util
import json
import pathlib
import sys

import jax
import pytest

from dartbench import run as run_mod
from dartbench import systems
import repro.core
from repro.core import tracing

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "dartbench" / "metrics"
SEED = 2**33 + 11
#: the metrics that read program totals, with the cell that lists each
PROGRAM = {"coerce_us.rate": "rate-small", "stage_us.rate": "rate-small",
           "wait_us.lat": "lat-small", "lane_use.bw": "bw-large"}

TOTALS = {
    "dart.coerce": {"n": 4, "s": 0.002, "h2d_bytes": 32},
    "dart.stage": {"n": 4, "s": 0.001, "d2h_bytes": 32},
    "dart.wait": {"n": 5, "s": 0.25, "arrays": 5},
    "dart.launch": {"n": 2, "s": 0.01, "asked_bytes": 300,
                    "lane_bytes": 1200, "h2d_bytes": 9, "miss": 0},
}
ANSWERS = {"coerce_us.rate": 500.0, "stage_us.rate": 250.0,
           "wait_us.lat": 50000.0, "lane_use.bw": 25.0}
SPAN_OF = {"coerce_us.rate": "dart.coerce", "stage_us.rate": "dart.stage",
           "wait_us.lat": "dart.wait", "lane_use.bw": "dart.launch"}


def _read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "p_" + metric.replace(".", "_"), METRICS / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class _Run:
    def __init__(self, trace=object()):
        self.trace = trace


@pytest.fixture
def fixed(monkeypatch):
    monkeypatch.setattr(tracing, "totals",
                        lambda: {k: dict(v) for k, v in TOTALS.items()})


@pytest.mark.parametrize("metric", sorted(PROGRAM))
def test_reader_gives_the_known_answer(fixed, metric):
    assert _read(metric, _Run()) == pytest.approx(ANSWERS[metric])


@pytest.mark.parametrize("metric", sorted(PROGRAM))
def test_reader_gives_nothing_without_a_trace(fixed, metric):
    assert _read(metric, _Run(trace=None)) is None


@pytest.mark.parametrize("metric", sorted(PROGRAM))
def test_reader_gives_nothing_without_its_span(monkeypatch, metric):
    others = {k: v for k, v in TOTALS.items() if k != SPAN_OF[metric]}
    monkeypatch.setattr(tracing, "totals", lambda: others)
    assert _read(metric, _Run()) is None


@pytest.mark.parametrize("metric", sorted(PROGRAM))
def test_reader_gives_nothing_for_a_program_without_tracing(
        monkeypatch, metric):
    # an older checkout: ``repro.core.tracing`` does not import (the
    # package attribute goes too, or the import would find the module
    # that this process has already loaded)
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    monkeypatch.delattr(repro.core, "tracing")
    assert _read(metric, _Run()) is None


def test_program_metrics_are_listed_for_their_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for metric, cell in PROGRAM.items():
        m = per_layer[metric]
        assert m["workloads"] == [cell]
        assert m["source"] in ("program_span", "program_counter")
    assert list(per_layer)[-len(PROGRAM):] == list(PROGRAM)


@pytest.mark.parametrize("cell", sorted(set(PROGRAM.values())))
def test_traced_run_reports_the_program_metrics(cell):
    bench, cell_d, config, mix = run_mod.load_cell(cell)
    config = dict(config, window_bytes_per_unit=1 << 20)
    mix = dict(mix, epochs=min(mix["epochs"], 24))
    if mix["length"]["dist"] == "log_uniform":
        mix["length"] = dict(mix["length"],
                             min_elems=min(mix["length"]["min_elems"], 64),
                             max_elems=min(mix["length"]["max_elems"], 4096))
    tracing.reset()
    res = run_mod.run_cell(cell_d, config, mix,
                           run_mod.metrics_of(bench, cell_d, True),
                           seed=SEED, seconds=1.0, trace=True,
                           devices=jax.devices()[:1],
                           peaks={"hbm_bytes_per_s": 819e9},
                           system_factory=systems.DartSystem)
    assert res["correct"], res["check"]
    for metric, listed in PROGRAM.items():
        if listed == cell:
            assert res["metrics"][metric]["value"] > 0
    if cell == "rate-small":
        m = res["metrics"]
        # both spans nest inside the benchmark's enqueue span
        assert (m["coerce_us.rate"]["value"] + m["stage_us.rate"]["value"]
                <= m["enqueue_us.rate"]["value"])
    if cell == "bw-large":
        assert res["metrics"]["lane_use.bw"]["value"] <= 100
