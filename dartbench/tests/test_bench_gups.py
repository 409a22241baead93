"""The GUPS cell (``gups-xor``: HPCC RandomAccess's XOR updates as
accumulates) on the CPU at the tests' 1 MiB windows: its op kind's
model is numpy's ``^`` on full-width words, its run takes the window
accumulate plan once per epoch, its traced stretch counts the program's
``dart.accumulate`` spans, and each fault planted in the accumulate
dispatch reads not correct."""

import dataclasses

import numpy as np
import pytest

from dartbench import generator, plugins, reference, run
from repro.core import onesided, tracing
from test_bench_run import _run

CELL = "gups-xor"
_ORIG_ACC = onesided.CommEngine._dispatch_acc_run


def _resolved(run, arena):
    for op in run:
        op.handle._resolve((arena,))
    return arena


def _unchanged(self, arena, run, disjoint=True):
    return _resolved(run, arena)


def _as_sum(self, arena, run, disjoint=True):
    return _ORIG_ACC(self, arena, [dataclasses.replace(op, op="sum")
                                   for op in run], disjoint)


def _as_put(self, arena, run, disjoint=True):
    puts = [onesided._PendingPut(op.poolid, op.row, op.off, op.payload,
                                 op.handle, op.ts, unit=op.unit)
            for op in run]
    return _resolved(run, self._dispatch_put_run(arena, puts, disjoint))


def _row_shifted(self, arena, run, disjoint=True):
    rows = arena.shape[0]
    return _ORIG_ACC(self, arena, [
        dataclasses.replace(op, row=(op.row + 1) % rows) for op in run],
        disjoint)


#: faults planted in ``CommEngine._dispatch_acc_run``
FAULTS = {"state_unchanged": _unchanged, "xor_as_sum": _as_sum,
          "xor_as_put": _as_put, "row_shifted": _row_shifted}


def test_words_are_full_width_and_the_model_is_numpy_xor():
    op = plugins.load("ops", "acc_xor")
    rng = np.random.default_rng(5)
    payload = rng.standard_normal(4096).astype(np.uint32)
    assert np.mean(payload == 0) > 0.5             # the pool is mostly 0
    w = op.words(payload, 3, 1000, 1 << 20, 0)
    assert w.dtype == np.uint32 and np.count_nonzero(w == 0) <= 1
    assert np.mean(w >= 1 << 31) == pytest.approx(0.5, abs=0.05)
    other = payload.copy()
    other[::2] += 1
    assert np.all((op.words(other, 3, 1000, 1 << 20, 0) != w)[::2])
    assert np.all(op.words(payload, 3, 1000, 1 << 20, 1) != w)
    rows = rng.integers(0, 2**32, (4, 64), dtype=np.uint64).astype(np.uint32)
    want = rows.copy()
    for pos in range(2):                 # the same update, twice
        want[2, 10:12] ^= op.words(payload[:2], 2, 10, 64, pos)
        assert op.model(rows, 2, 10, 2, payload[:2]) is None
    np.testing.assert_array_equal(rows, want)


def test_an_epoch_issued_twice_stays_visible_to_the_check():
    """``drive.py`` issues the warm-up epoch again as the window's first.
    Were a repeated update the same word, the pair would XOR away and a
    run that left the table untouched would read correct; each update's
    stream position keeps them apart."""
    bench, cell, config, mix = run.load_cell(CELL)
    config = dict(config, window_bytes_per_unit=1 << 20)
    traffic = generator.generate(dict(mix, epochs=4), config, 7)
    units = int(config["units"])
    untouched = np.zeros((units, (1 << 20) // 4), np.uint32)
    check = reference.compare(traffic, [0, 0], {}, untouched, 0)
    assert not check["correct"]
    # every element of the epoch's 1024 two-element updates
    assert check["numbers"]["wrong_window_elems"][0] == 2 * 1024


def test_each_epoch_is_one_window_dispatch(monkeypatch):
    seen = []

    def counted(self, arena, run, disjoint=True):
        w0 = self.window_dispatches
        out = _ORIG_ACC(self, arena, run, disjoint)
        seen.append((len(run), self.window_dispatches - w0))
        return out

    monkeypatch.setattr(onesided.CommEngine, "_dispatch_acc_run", counted)
    res = _run(CELL)
    assert res["correct"], res["check"]
    assert seen and all(s == (1024, 1) for s in seen)


def test_traced_run_reads_its_metrics_and_the_accumulate_spans():
    res = _run(CELL, trace=True, seconds=1.0)
    assert res["correct"], res["check"]
    m = res["metrics"]
    assert m["enqueue_us.rate"]["value"] > 0
    assert m["flush_host_us.rate"]["value"] > 0
    # the program's own totals of the stretch: every update one
    # ``dart.accumulate`` of 2 elements, every run a window launch
    totals = tracing.totals()
    acc, launch = totals["dart.accumulate"], totals["dart.launch"]
    assert acc["n"] > 0 and acc["elems"] == 2 * acc["n"]
    assert launch["n"] == launch["window"] == acc["n"] // 1024
    # the coerce and stage spans nest in the accumulate span, and that
    # in the benchmark's enqueue span
    assert (totals["dart.coerce"]["s"] + totals["dart.stage"]["s"]
            <= acc["s"])
    assert 1e6 * acc["s"] / acc["n"] <= m["enqueue_us.rate"]["value"]


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_makes_the_run_not_correct(monkeypatch, fault):
    monkeypatch.setattr(onesided.CommEngine, "_dispatch_acc_run",
                        FAULTS[fault])
    res = _run(CELL)
    assert not res["correct"], (fault, res["check"])
    assert res["check"]["wrong_window_elems"]["value"] > 0
