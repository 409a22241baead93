"""Tests for the locality-aware non-blocking engine (CommEngine):
coalesced flush, handle state machine, shm fast path, dispatch counts."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (DART_TEAM_ALL, DartConfig, Locality,
                        classify_locality, dart_exit, dart_flush, dart_get,
                        dart_get_blocking, dart_get_nb, dart_init,
                        dart_memalloc, dart_put, dart_put_blocking,
                        dart_team_memalloc_aligned,
                        dart_team_memalloc_shared, dart_test, dart_testall,
                        dart_wait, dart_waitall, shm_supported)
from repro.core import onesided as _os


@pytest.fixture()
def ctx(engine_impl):
    # engine-impl parametrization (conftest.py): every ctx-based test
    # in this module runs under both impl='ref' and impl='pallas'
    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=8192, team_pool_bytes=8192))
    c.engine.impl = engine_impl
    yield c
    dart_exit(c)


# ----------------------------------------------------- handle lifecycle ----

def test_handle_state_machine(ctx):
    g = dart_memalloc(ctx, 512, unit=1)
    h = dart_put(ctx, g, jnp.arange(8, dtype=jnp.float32))
    assert h.state == "queued"
    assert not dart_test(h)                 # false before flush
    dart_flush(ctx)
    assert h.state in ("issued", "complete")
    dart_wait(h)
    assert dart_test(h)                     # true after flush+wait
    assert h.state == "complete"


def test_wait_on_queued_handle_triggers_flush(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    h = dart_put(ctx, g, jnp.full((16,), 3, jnp.int32))
    assert ctx.engine.pending_ops() == 1
    dart_wait(h)                            # implicit epoch close
    assert ctx.engine.pending_ops() == 0
    out = dart_get_blocking(ctx, g, (16,), jnp.int32)
    assert np.all(np.asarray(out) == 3)


def test_get_nb_value_flushes(ctx):
    g = dart_memalloc(ctx, 256, unit=2)
    dart_put(ctx, g, jnp.arange(4, dtype=jnp.int32))     # still queued
    h = dart_get_nb(ctx, g, (4,), jnp.int32)
    assert h.state == "queued" and not h.test()
    np.testing.assert_array_equal(np.asarray(h.value()), [0, 1, 2, 3])
    assert h.state == "complete"


def test_waitall_testall_mixed_pools(ctx):
    """Handles over the WORLD pool and a team pool in one epoch."""
    gw = dart_memalloc(ctx, 512, unit=0)
    gt = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 512)
    hs = [dart_put(ctx, gw, jnp.full((8,), 1, jnp.int32)),
          dart_put(ctx, gt.setunit(2), jnp.full((8,), 2, jnp.int32)),
          dart_put(ctx, gw + 128, jnp.full((8,), 3, jnp.int32)),
          dart_put(ctx, gt.setunit(3), jnp.full((8,), 4, jnp.int32))]
    assert not dart_testall(hs)
    dart_waitall(hs)
    assert dart_testall(hs)
    assert np.all(np.asarray(
        dart_get_blocking(ctx, gw, (8,), jnp.int32)) == 1)
    assert np.all(np.asarray(
        dart_get_blocking(ctx, gt.setunit(2), (8,), jnp.int32)) == 2)
    assert np.all(np.asarray(
        dart_get_blocking(ctx, gw + 128, (8,), jnp.int32)) == 3)
    assert np.all(np.asarray(
        dart_get_blocking(ctx, gt.setunit(3), (8,), jnp.int32)) == 4)


def test_flush_single_pool_leaves_other_queued(ctx):
    gw = dart_memalloc(ctx, 256, unit=0)
    gt = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
    hw = dart_put(ctx, gw, jnp.ones((4,), jnp.int32))
    ht = dart_put(ctx, gt, jnp.ones((4,), jnp.int32))
    dart_flush(ctx, gw)
    assert hw.state != "queued"
    assert ht.state == "queued"
    dart_flush(ctx)
    assert ht.state != "queued"


# ------------------------------------------------ coalescing + counters ----

def test_coalesced_flush_fewer_dispatches_and_bit_identical(ctx):
    """The acceptance-criterion test: N queued puts flush as ONE jitted
    dispatch (vs N for the blocking path), with identical bytes."""
    n_ops = 8
    g = dart_memalloc(ctx, 4096, unit=0)

    # blocking baseline: one dispatch per put
    d0 = ctx.engine.dispatch_count
    for k in range(n_ops):
        dart_put_blocking(ctx, g + 128 * k,
                          jnp.full((13,), float(k), jnp.float32))
    blocking_dispatches = ctx.engine.dispatch_count - d0
    assert blocking_dispatches == n_ops
    blocking_bytes = [np.asarray(dart_get_blocking(
        ctx, g + 128 * k, (13,), jnp.float32)).tobytes()
        for k in range(n_ops)]

    # coalesced: same values through the queue, one dispatch total
    for k in range(n_ops):          # clear the slots first
        dart_put_blocking(ctx, g + 128 * k, jnp.zeros((13,), jnp.float32))
    d0 = ctx.engine.dispatch_count
    hs = [dart_put(ctx, g + 128 * k,
                   jnp.full((13,), float(k), jnp.float32))
          for k in range(n_ops)]
    dart_flush(ctx)
    coalesced_dispatches = ctx.engine.dispatch_count - d0
    assert coalesced_dispatches == 1
    assert coalesced_dispatches < blocking_dispatches
    dart_waitall(hs)
    for k in range(n_ops):
        got = np.asarray(dart_get_blocking(
            ctx, g + 128 * k, (13,), jnp.float32)).tobytes()
        assert got == blocking_bytes[k]


def test_coalesced_gets_one_dispatch(ctx):
    g = dart_memalloc(ctx, 2048, unit=1)
    for k in range(6):
        dart_put_blocking(ctx, g + 128 * k, jnp.full((4,), k, jnp.int32))
    hs = [dart_get_nb(ctx, g + 128 * k, (4,), jnp.int32) for k in range(6)]
    d0 = ctx.engine.dispatch_count
    dart_flush(ctx)
    assert ctx.engine.dispatch_count - d0 == 1
    for k, h in enumerate(hs):
        assert np.all(np.asarray(h.value()) == k)


def test_program_order_overlapping_puts_last_writer_wins(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    dart_put(ctx, g, jnp.full((8,), 1, jnp.float32))
    dart_put(ctx, g, jnp.full((8,), 2, jnp.float32))     # same size: one run
    dart_put(ctx, g, jnp.full((4,), 3, jnp.float32))     # new size: new run
    dart_flush(ctx)
    out = np.asarray(dart_get_blocking(ctx, g, (8,), jnp.float32))
    np.testing.assert_array_equal(out, [3, 3, 3, 3, 2, 2, 2, 2])


def test_queued_put_bounds_checked_at_initiation(ctx):
    g = dart_memalloc(ctx, 128, unit=0)
    near_end = g + (ctx.config.non_collective_pool_bytes - 4 - g.addr)
    with pytest.raises(ValueError):
        dart_put(ctx, near_end, jnp.zeros(16, jnp.float32))
    assert ctx.engine.pending_ops() == 0     # nothing was enqueued


def test_epoch_counter_advances_on_flush(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    e0 = ctx.engine.epoch
    dart_put(ctx, g, jnp.ones((4,), jnp.float32))
    assert ctx.engine.epoch == e0            # enqueue is not an epoch close
    dart_flush(ctx)
    assert ctx.engine.epoch == e0 + 1
    dart_flush(ctx)                          # empty flush: no epoch close
    assert ctx.engine.epoch == e0 + 1


def test_get_nb_dropped_by_clear_raises():
    """A queued get whose op was cleared (dart_exit) must raise from
    value(), not silently return None."""
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    g = dart_memalloc(ctx, 256, unit=0)
    h = dart_get_nb(ctx, g, (4,), jnp.int32)
    dart_exit(ctx)                          # engine.clear() drops the op
    with pytest.raises(RuntimeError):
        h.value()


def test_put_dropped_by_clear_raises_on_wait():
    """Same for a queued put: wait()/waitall must not report a lost
    write as success."""
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    g = dart_memalloc(ctx, 256, unit=0)
    h1 = dart_put(ctx, g, jnp.ones((4,), jnp.int32))
    h2 = dart_put(ctx, g + 128, jnp.ones((4,), jnp.int32))
    dart_exit(ctx)
    with pytest.raises(RuntimeError):
        dart_wait(h1)
    with pytest.raises(RuntimeError):
        dart_waitall([h2])


# ----------------------------------------------------- shm fast path -------

def test_shm_fastpath_equivalence_and_zero_dispatch(ctx):
    """Zero-copy read == jitted-get result byte-for-byte, with no jitted
    dispatch issued by the routed blocking get."""
    if not shm_supported(ctx):
        pytest.skip("backend arenas not host-visible")
    gs = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 1024)
    val = jnp.arange(32, dtype=jnp.float32) * 1.5
    dart_put_blocking(ctx, gs.setunit(1), val)
    assert classify_locality(ctx, gs) is Locality.SHM_LOCAL

    jitted = _os.dart_get_blocking(ctx.state, ctx.heap, ctx.teams_by_slot,
                                   gs.setunit(1), (32,), jnp.float32)
    d0 = ctx.engine.dispatch_count
    routed = dart_get_blocking(ctx, gs.setunit(1), (32,), jnp.float32)
    assert ctx.engine.dispatch_count == d0   # no jitted dispatch
    assert np.asarray(routed).tobytes() == np.asarray(jitted).tobytes()


def test_shm_fastpath_sees_queued_puts(ctx):
    """The locality route must flush the pool first (RAW ordering)."""
    if not shm_supported(ctx):
        pytest.skip("backend arenas not host-visible")
    gs = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 256)
    dart_put(ctx, gs.setunit(2), jnp.full((8,), 9.0, jnp.float32))  # queued
    out = dart_get_blocking(ctx, gs.setunit(2), (8,), jnp.float32)
    assert np.all(np.asarray(out) == 9.0)


def test_non_shm_pointer_classifies_remote(ctx):
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    assert classify_locality(ctx, g) is Locality.REMOTE


# ------------------------------------------------- benchmark smoke ---------

@pytest.mark.slow
def test_put_get_benchmark_quick_runs_new_series():
    """`benchmarks/put_get.py` must run the coalesced + shm_fastpath
    series (acceptance criterion); quick mode keeps this cheap."""
    from benchmarks.common import Report
    from benchmarks import put_get
    report = Report()
    put_get.run(report, full=False, repeats=2, quick=True)
    names = [name for name, _, _ in report.rows]
    assert any(n.startswith("coalesced/put_flush/") for n in names)
    assert any(n.startswith("coalesced/get_flush/") for n in names)
    assert any(n.startswith("shm_fastpath/") for n in names)
    # typed GlobalArray front-end series: blocking put/get overhead vs
    # the raw byte API, the coalesced non-blocking path, and the
    # constant-overhead model fit
    assert any(n.startswith("typed_api/put/") for n in names)
    assert any(n.startswith("typed_api/get/") for n in names)
    assert any(n.startswith("typed_api/put_nb_coalesced/") for n in names)
    assert any(n.startswith("typed_api/overhead_fit/") for n in names)


@pytest.mark.slow
def test_engine_profile_machine_readable():
    """`benchmarks.run` emits BENCH_engine.json from this profile: the
    dispatch-count wins (coalescing, per-target isolation, mixed-size
    hoisting) must be present and assertable in the payload."""
    from benchmarks import put_get
    profile = put_get.engine_profile(repeats=2, quick=True)
    s = profile["series"]
    assert profile["schema"] == "BENCH_engine/v8"
    assert s["blocking"]["dispatches"] == profile["n_ops"]
    assert s["coalesced"]["dispatches"] == 1
    assert s["mixed_size_coalesced"]["dispatches"] == 1
    assert s["per_target_flush"]["dispatches_target_only"] == 1
    assert s["per_target_flush"]["ops_left_queued"] == profile["n_ops"] // 2
    # flush cost model: a warm (plan-cache-hit) flush must beat the
    # cold (compile) flush by >= 5x, and the steady-state loop of
    # varying-size epochs must not recompile at all
    fc = profile["flush_cost"]
    assert fc["compiles_cold"] >= 1
    assert fc["recompiles_steady_state"] == 0
    assert fc["cold_vs_warm_speedup"] >= 5.0
    assert profile["plan_cache"]["plan_cache_hits"] > 0
    # v3 reduce plane: N accumulates coalesce into ONE dispatch (vs
    # n_ops blocking), and the varying (shape, dtype, op)
    # allreduce+accumulate steady-state loop performs zero recompiles
    # — the assertable form of the shape-stable-allreduce ROADMAP item
    rp = profile["reduce_plane"]
    assert rp["acc_dispatches_blocking"] == profile["n_ops"]
    assert rp["acc_dispatches_coalesced"] == 1
    assert rp["allreduce_compiles_cold"] >= 1
    assert rp["allreduce_warm_recompiles"] == 0
    assert rp["recompiles_steady_state"] == 0
    # v6 strided IR: a column of N elements is ONE dispatch, its µs/op
    # stays within ~2x of the contiguous row path, and a varying-stride
    # loop at fixed buckets never recompiles (stride is plan DATA)
    sd = profile["strided"]
    assert sd["dispatches_per_strided_put"] == 1
    assert sd["dispatches_per_strided_get"] == 1
    assert sd["recompiles_steady_state"] == 0
    nr = profile["narray"]
    assert nr["get_col_dispatches"] <= nr["owning_tiles"]
    # v8 shm plane: a locality-routed put on a host-visible arena is a
    # locked host-side memcpy — zero jitted dispatches, >= 5x faster
    # than the jitted blocking put — and intra-node collectives run
    # shm-direct at zero dispatches with no steady-state recompiles
    sp = profile["shm_plane"]
    assert sp["shm_put_dispatches"] == 0
    assert sp["shm_put_speedup"] >= 5.0
    assert sp["broadcast_dispatches"] == 0
    assert sp["gather_dispatches"] == 0
    assert sp["scatter_dispatches"] == 0
    assert sp["recompiles_steady_state"] == 0
    import json
    json.dumps(profile)                  # machine-readable, no jnp leaks


# ------------------------------------------------- property-based ----------

@given(st.integers(2, 6), st.integers(0, 48),
       st.sampled_from(["float32", "int32", "bfloat16", "uint8"]),
       st.integers(1, 32))
@settings(max_examples=15, deadline=None)
def test_engine_roundtrip_property(n_units, word_off, dtype, n):
    """put → flush → get identity under random offsets/dtypes/units."""
    ctx = dart_init(n_units=n_units, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    try:
        g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 2048)
        ptr = g.setunit(word_off % n_units) + word_off * 4
        val = (jnp.arange(n) + 1).astype(dtype)
        h = dart_put(ctx, ptr, val)
        dart_flush(ctx)
        dart_wait(h)
        out = dart_get_blocking(ctx, ptr, (n,), dtype)
        assert (np.asarray(out).tobytes() == np.asarray(val).tobytes())
    finally:
        dart_exit(ctx)


@given(st.integers(1, 10), st.integers(0, 7))
@settings(max_examples=10, deadline=None)
def test_engine_many_puts_property(k, base_slot):
    """k queued same-size puts to distinct slots flush to one dispatch
    and every slot reads back its own payload."""
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=8192, team_pool_bytes=8192))
    try:
        g = dart_memalloc(ctx, 4096, unit=1)
        d0 = ctx.engine.dispatch_count
        hs = [dart_put(ctx, g + 128 * (base_slot + i),
                       jnp.full((7,), float(i), jnp.float32))
              for i in range(k)]
        dart_flush(ctx)
        assert ctx.engine.dispatch_count - d0 == 1
        dart_waitall(hs)
        for i in range(k):
            out = dart_get_blocking(ctx, g + 128 * (base_slot + i),
                                    (7,), jnp.float32)
            assert np.all(np.asarray(out) == i)
    finally:
        dart_exit(ctx)
