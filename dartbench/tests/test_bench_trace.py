"""The reduction from a trace to per-layer metrics, on a small synthetic
trace whose answers are known."""

import importlib.util
import pathlib

import numpy as np
import pytest

from dartbench import trace as tr

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def _read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "m_" + metric.replace(".", "_"), METRICS / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class _Run:
    def __init__(self, trace, stretch, peaks=None):
        self.trace = trace
        self.stretch = stretch
        self.peaks = peaks or {"hbm_bytes_per_s": 1e9}


@pytest.fixture
def synthetic():
    # stretch [0, 1000) ns; device A busy [100, 300) and [250, 400)
    # (overlapping: 300 ns), device B busy [350, 500) and [900, 1100)
    # (clipped to 1000: 150 + 100 ns); the union is [100, 500) and
    # [900, 1000): 500 ns.  Host: an op span [0, 1000) holding enqueue
    # [0, 90), flush [90, 600) and decode [600, 950).
    ops = {"/device:TPU:0": [("scatter", 100, 300), ("fusion", 250, 400)],
           "/device:TPU:1": [("scatter", 350, 500), ("gather", 900, 1100)]}
    spans = [("op", 0, 1000), ("enqueue", 0, 90), ("flush", 90, 600),
             ("decode", 600, 950)]
    return tr.Trace(0, 1000, ops, spans)


def test_busy_union_and_idle_share(synthetic):
    assert synthetic.busy_union() == [(100, 500), (900, 1000)]
    assert synthetic.busy_union_s() == pytest.approx(500e-9)
    per = synthetic.busy_per_device_s()
    assert per["/device:TPU:0"] == pytest.approx(300e-9)
    assert per["/device:TPU:1"] == pytest.approx(250e-9)
    assert synthetic.busy_mean_s() == pytest.approx(275e-9)
    run = _Run(synthetic, {"bytes": 0, "ops": 0, "dispatches": 0})
    for m in ("idle_share.lat", "idle_share.rate", "idle_share.bw"):
        assert _read(m, run) == pytest.approx(50.0)


def test_idle_gaps_by_innermost_span(synthetic):
    # gaps [0, 100) -> enqueue (mid 50), [500, 900) -> decode (mid 700)
    idle = synthetic.idle_by_span()
    assert idle == {"enqueue": pytest.approx(100e-9),
                    "decode": pytest.approx(400e-9)}
    bd = synthetic.breakdown()
    assert bd["idle_gaps"][0][0] == "decode"
    assert [n for n, _ in bd["device_ops"]] == ["scatter", "fusion",
                                                "gather"]
    assert bd["device_ops"][0][1] == pytest.approx(350e-9)


def test_hbm_roofline_counts_bytes_asked_for(synthetic):
    # 2 x 100 bytes at 1e9 B/s is 200 ns of least time over 500 ns busy
    run = _Run(synthetic, {"bytes": 100, "ops": 2, "dispatches": 1})
    assert _read("hbm_roofline.bw", run) == pytest.approx(40.0)
    run = _Run(synthetic, {"bytes": 0, "ops": 0, "dispatches": 1})
    assert _read("hbm_roofline.bw", run) is None


def test_device_time_per_dispatch(synthetic):
    run = _Run(synthetic, {"bytes": 0, "ops": 4, "dispatches": 4})
    assert _read("device_us_per_dispatch.lat", run) == pytest.approx(
        500e-9 / 4 * 1e6)
    run = _Run(synthetic, {"bytes": 0, "ops": 0, "dispatches": 0})
    assert _read("device_us_per_dispatch.lat", run) is None


def test_host_span_means(synthetic):
    run = _Run(synthetic, {"bytes": 0, "ops": 0, "dispatches": 0})
    assert _read("flush_host_us.lat", run) == pytest.approx(0.51)
    assert _read("flush_host_us.rate", run) == pytest.approx(0.51)
    assert _read("enqueue_us.rate", run) == pytest.approx(0.09)


def test_readers_say_nothing_without_device_work():
    idle = tr.Trace(0, 1000, {}, [("flush", 0, 10)])
    run = _Run(idle, {"bytes": 10, "ops": 1, "dispatches": 1})
    for m in ("idle_share.lat", "hbm_roofline.bw",
              "device_us_per_dispatch.lat"):
        assert _read(m, run) is None
    assert _read("idle_share.bw", _Run(None, None)) is None


def test_union_and_gaps_edges():
    assert tr.union([], 0, 10) == []
    assert tr.union([(5, 5), (-3, 2), (2, 4)], 0, 10) == [(0, 4)]
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]
    assert np.isclose(tr.total([(0, 4), (6, 8)]), 6)


def test_collective_time_per_dispatch_on_two_devices():
    # device A: an all-reduce [100, 200) overlapping its async start
    # [150, 250) -> 150 ns, a fusion that reads the all-reduce's result
    # (no collective); device B: all-gather [0, 50) and a mangled
    # collective-permute-done [900, 1200) clipped to 1000 -> 150 ns.
    # The mean over the two devices is 150 ns.
    ops = {"/device:TPU:0": [
               ("%all-reduce.3 = f32[4]{0} all-reduce(%p)", 100, 200),
               ("all-reduce-start.1", 150, 250),
               ("%fusion.5 = f32[4]{0} fusion(%all-reduce.3)", 300, 600)],
           "/device:TPU:1": [
               ("_all-gather.2___u8_4_128", 0, 50),
               ("_collective_permute_done.7___u8", 900, 1200),
               ("_dynamic_update_slice.11___u8", 60, 800)]}
    trace = tr.Trace(0, 1000, ops, [])
    assert [tr.is_collective(n) for n, _, _ in ops["/device:TPU:0"]] == [
        True, True, False]
    assert not tr.is_collective("reduce.4")
    assert not tr.is_collective("_while.10")
    assert tr.is_collective("reduce-scatter.1") and tr.is_collective(
        "all-to-all.2")
    assert trace.collective_mean_s() == pytest.approx(150e-9)
    run = _Run(trace, {"bytes": 0, "ops": 3, "dispatches": 3})
    assert _read("collective_us_per_dispatch.lat4", run) == pytest.approx(
        150e-9 / 3 * 1e6)
    quiet = tr.Trace(0, 1000, {"/device:TPU:0": [("fusion.1", 0, 10)]}, [])
    assert _read("collective_us_per_dispatch.lat4",
                 _Run(quiet, {"bytes": 0, "ops": 1, "dispatches": 1})) is None
    assert _read("collective_us_per_dispatch.lat4", _Run(None, None)) is None


def test_rehearsal_counts_collective_opcodes():
    from dartbench import rehearse
    hlo = """
  %all-reduce.1 = u8[4,16]{1,0} all-reduce(u8[4,16]{1,0} %f), to_apply=%a
  %fusion.3 = u8[64]{0} fusion(u8[4,16]{1,0} %all-reduce.1), kind=kLoop
  %ag = (u8[4]{0}, u8[16]{0}) all-gather-start(u8[4]{0} %p), dimensions={0}
  %agd = u8[16]{0} all-gather-done((u8[4]{0}, u8[16]{0}) %ag)
  %r = f32[] reduce(f32[8]{0} %x, f32[] %z), to_apply=%add
"""
    assert rehearse.collectives(hlo) == {"all-reduce": 1,
                                         "all-gather-start": 1,
                                         "all-gather-done": 1}
    assert rehearse.collectives("%w = s32[] while(s32[] %i)") == {}
