"""The runtime's host spans and counters (``repro.core.tracing``): off
without a profiler session, recorded and totalled under one, written
into the profiler's trace, and started afresh by each session."""

import os
import pathlib
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import DartConfig, dart_exit, dart_init, dart_waitall
from repro.core import tracing
from repro.kernels import segmented_copy as sc

#: every span the engine and the front end write, as docs/API.md lists
SPANS = ("dart.coerce", "dart.enqueue", "dart.stage", "dart.flush",
         "dart.coalesce", "dart.pack", "dart.launch", "dart.wait",
         "dart.d2h", "dart.decode")
#: (unit, element offset, elements) of the put_nb epoch: mixed sizes on
#: disjoint ranges of both units, so the epoch is one vectorized run
PUTS = [(0, 0, 5), (1, 16, 3), (0, 64, 20), (1, 128, 1), (0, 256, 7)]


@pytest.fixture()
def ga():
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    tracing.reset()
    # shm=False: every op takes the engine's device path, as on a chip
    yield ctx.alloc((512,), jnp.float32, shm=False)
    dart_exit(ctx)


def _epoch_and_get(ga, asarray=np.asarray):
    """One put_nb epoch of float32 payloads made by ``asarray``,
    completed, then a blocking get of its third put; returns the get's
    value."""
    handles = [ga.at[u, lo:lo + n].put_nb(
        asarray(np.arange(n, dtype=np.float32))) for u, lo, n in PUTS]
    ga.flush()
    dart_waitall(handles)
    return ga.at[0, 64:84].get()


def _host_events(log_dir, name):
    paths = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    assert paths, "the profiler wrote no trace"
    data = ProfileData.from_file(str(paths[-1]))
    return [ev for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def test_off_without_a_profiler_session(ga):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("dart.flush", epoch=1) is tracing.OFF
    with tracing.span("dart.coerce") as sp:
        assert sp is tracing.OFF and not sp.on
        sp.add(h2d_bytes=8)                     # does nothing
    np.testing.assert_array_equal(_epoch_and_get(ga), np.arange(20))
    assert tracing.totals() == {}


@pytest.mark.parametrize("payload", ["numpy", "jax"])
def test_on_records_every_span_and_its_counters(ga, tmp_path, monkeypatch,
                                                payload):
    packed = []
    pack = sc.pack_descriptors

    def spy(*a, **kw):
        out = pack(*a, **kw)
        packed.append(out)
        return out
    monkeypatch.setattr(sc, "pack_descriptors", spy)
    eng = ga.ctx.engine
    d0 = eng.dispatch_count
    asarray = np.asarray if payload == "numpy" else jnp.asarray
    with jax.profiler.trace(str(tmp_path)):
        value = _epoch_and_get(ga, asarray)
    np.testing.assert_array_equal(value, np.arange(20))
    t = tracing.totals()
    assert set(SPANS) <= set(t)
    assert all(t[name]["n"] >= 1 and t[name]["s"] >= 0 for name in SPANS)

    put_bytes = 4 * sum(n for _, _, n in PUTS)
    get_bytes = 4 * 20
    # one put run and one get run: two flushes, two dispatches
    assert t["dart.flush"]["runs"] == eng.dispatch_count - d0 == 2
    assert t["dart.flush"]["ops"] == len(PUTS) + 1
    assert t["dart.launch"]["n"] == 2
    assert t["dart.launch"]["asked_bytes"] == put_bytes + get_bytes
    assert len(packed) == 2
    assert t["dart.launch"]["lane_bytes"] == sum(
        desc.shape[0] * seg for desc, _, seg in packed)
    (pdesc, pflat, _), (gdesc, _, gseg) = packed
    assert t["dart.launch"]["h2d_bytes"] == (pdesc.nbytes + pflat.nbytes
                                            + gdesc.nbytes)
    assert t["dart.launch"]["miss"] in (0, 1, 2)
    # one coerce and one stage per put; neither uploads.  A host payload
    # of the ref's dtype stays on the host (no copy either way); a
    # device payload is staged down once
    assert t["dart.coerce"]["n"] == t["dart.stage"]["n"] == len(PUTS)
    assert t["dart.coerce"]["h2d_bytes"] == 0
    if payload == "numpy":
        assert t["dart.coerce"]["host"] == len(PUTS)
        assert t["dart.stage"].get("d2h_bytes", 0) == 0
    else:
        assert t["dart.coerce"]["host"] == 0
        assert t["dart.stage"]["d2h_bytes"] == put_bytes
    assert t["dart.enqueue"]["n"] == len(PUTS) + 1
    # the get: one copy of its (kb, seg) windows, one typed re-upload
    assert t["dart.d2h"]["d2h_bytes"] == gdesc.shape[0] * gseg
    assert t["dart.decode"]["h2d_bytes"] == get_bytes
    assert t["dart.wait"]["arrays"] >= len(PUTS) + 1


@pytest.mark.parametrize("kind", ["strided_put", "accumulate",
                                  "get_accumulate"])
def test_launch_counts_the_bytes_asked_of_every_run_kind(ga, tmp_path,
                                                         kind):
    """``asked_bytes`` is every op's bytes (a strided op's segments
    summed), whatever the plan, and never the bucket padding."""
    ref = ga.at[1, 0:96:8] if kind == "strided_put" else ga.at[0, 32:45]
    value = np.arange(ref.size, dtype=np.float32)
    with jax.profiler.trace(str(tmp_path)):
        if kind == "strided_put":
            ref.put_nb(value).wait()
        elif kind == "accumulate":
            ref.accumulate(value).wait()
            ref.accumulate(value).wait()
        else:
            ref.get_accumulate(value)
    launch = tracing.totals()["dart.launch"]
    runs = 2 if kind == "accumulate" else 1
    assert launch["n"] == runs
    assert launch["asked_bytes"] == runs * 4 * ref.size
    assert launch["lane_bytes"] > launch["asked_bytes"]


def test_trace_file_holds_flush_with_its_counters(ga, tmp_path):
    epoch = ga.ctx.engine.epoch
    with jax.profiler.trace(str(tmp_path)):
        _epoch_and_get(ga)
    flushes = _host_events(tmp_path, "dart.flush")
    stats = [dict(ev.stats) for ev in flushes]
    assert {"epoch": epoch, "ops": len(PUTS), "runs": 1} in stats
    assert {"epoch": epoch + 1, "ops": 1, "runs": 1} in stats
    launch = [dict(ev.stats) for ev in _host_events(tmp_path, "dart.launch")]
    assert {"asked_bytes", "lane_bytes", "h2d_bytes", "miss"} <= set(
        launch[0])
    # the launch nests inside its flush on the host timeline
    f = flushes[0]
    inside = [ev for ev in _host_events(tmp_path, "dart.launch")
              if f.start_ns <= ev.start_ns and ev.end_ns <= f.end_ns]
    assert inside


@pytest.mark.parametrize("between", ["nothing", "untraced_epoch"])
def test_each_session_starts_the_totals_afresh(ga, tmp_path, between):
    with jax.profiler.trace(str(tmp_path / "first")):
        _epoch_and_get(ga)
    assert tracing.totals()["dart.coerce"]["n"] == len(PUTS)
    if between == "untraced_epoch":
        _epoch_and_get(ga)
        assert tracing.totals()["dart.coerce"]["n"] == len(PUTS)
    with jax.profiler.trace(str(tmp_path / "second")):
        ga.at[1, 0:4].put_nb(np.ones(4, np.float32)).wait()
    t = tracing.totals()
    assert t["dart.coerce"]["n"] == t["dart.coerce"]["host"] == 1
    assert t["dart.coerce"]["h2d_bytes"] == 0
    assert "dart.decode" not in t
    tracing.reset()
    assert tracing.totals() == {}


def test_spans_from_many_threads_add_up(tmp_path):
    """The progress plane flushes from its own thread: totals written
    from more threads than cores lose no update."""
    tracing.reset()
    n_threads, per = (os.cpu_count() or 1) + 2, 300

    def work():
        for _ in range(per):
            with tracing.span("dart.test", items=2) as sp:
                sp.add(items=1)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    t = tracing.totals()["dart.test"]
    assert t["n"] == n_threads * per
    assert t["items"] == 3 * n_threads * per
    tracing.reset()
