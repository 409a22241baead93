"""Tests for the typed GlobalArray front-end over the byte-offset DART
core (docs/API.md): allocators, NumPy-style addressing, engine
coalescing, typed collectives, local zero-copy view, epochs."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (DART_TEAM_ALL, DartConfig, GlobalArray, GlobalRef,
                        OutOfGlobalMemory, dart_exit, dart_init,
                        dart_team_create, group_from_units, shm_supported)
from repro.core.array import _element_run


@pytest.fixture()
def ctx(engine_impl):
    # engine-impl parametrization (conftest.py): every ctx-based test
    # in this module runs under both impl='ref' and impl='pallas'
    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=8192, team_pool_bytes=8192))
    c.engine.impl = engine_impl
    yield c
    dart_exit(c)


# ------------------------------------------------------- allocators --------

def test_ctx_alloc_identity_and_roundtrip(ctx):
    ga = ctx.alloc((8,), jnp.float32)
    assert isinstance(ga, GlobalArray)
    assert ga.units == (0, 1, 2, 3)
    assert ga.shape == (8,) and ga.dtype == jnp.dtype(jnp.float32)
    assert ga.nbytes_per_unit == 32
    val = jnp.arange(8, dtype=jnp.float32)
    ga[2].put(val)
    np.testing.assert_array_equal(np.asarray(ga[2].get()), np.asarray(val))
    # other units untouched
    assert np.all(np.asarray(ga[1].get()) == 0)


def test_team_alloc_scopes_units(ctx):
    team = dart_team_create(ctx, DART_TEAM_ALL, group_from_units([1, 3]))
    ga = ctx.teams[team].alloc(ctx, (4,), jnp.int32)
    assert ga.units == (1, 3)
    ga[3].put(np.array([5, 6, 7, 8]))
    np.testing.assert_array_equal(np.asarray(ga[3].get()), [5, 6, 7, 8])
    with pytest.raises(KeyError):
        ga[0]                                  # not a member
    with pytest.raises(KeyError):
        ga.at[2, 0:2]


def test_alloc_overflow_raises_out_of_global_memory(ctx):
    # a GlobalArray-sized request that overflows team_pool_bytes (8192)
    with pytest.raises(OutOfGlobalMemory):
        ctx.alloc((4096,), jnp.float32)        # 16 KiB per unit


def test_free_then_realloc_reuses_coalesced_block(ctx):
    """dart_team_memfree → re-alloc returns the coalesced block."""
    a = ctx.alloc((256,), jnp.float32)         # 1 KiB
    b = ctx.alloc((256,), jnp.float32)
    assert b.gptr.addr > a.gptr.addr
    a_addr = a.gptr.addr
    a.free()
    b.free()
    # both holes coalesced: a single allocation spanning the combined
    # extent fits again, at the first block's offset
    c = ctx.alloc((512,), jnp.float32)
    assert c.gptr.addr == a_addr


# ------------------------------------------------------- addressing --------

def test_at_slicing_translates_to_element_runs(ctx):
    ga = ctx.alloc((8,), jnp.float32)
    ga[1].put(jnp.zeros((8,), jnp.float32))
    ga.at[1, 3:7].put(jnp.full((4,), 9.0))
    out = np.asarray(ga[1].get())
    np.testing.assert_array_equal(out, [0, 0, 0, 9, 9, 9, 9, 0])
    np.testing.assert_array_equal(np.asarray(ga.at[1, 3:7].get()),
                                  [9.0] * 4)
    # scalar element, negative index
    assert float(np.asarray(ga.at[1, 3].get())) == 9.0
    assert float(np.asarray(ga.at[1, -1].get())) == 0.0
    ga.at[1, -1].put(2.5)                      # scalar broadcast put
    assert float(np.asarray(ga.at[1, 7].get())) == 2.5


def test_ref_chaining_and_gptr_consistency(ctx):
    ga = ctx.alloc((16,), jnp.int32)
    ref = ga[2][4:12][2:4]                     # chained slicing composes
    assert ref.shape == (2,) and ref.offset == 6
    # the substrate pointer is base + element_offset * itemsize
    assert ref.gptr - ga.gptr.setunit(2) == 6 * 4
    ref.put(np.array([11, 22]))
    out = np.asarray(ga[2].get())
    assert out[6] == 11 and out[7] == 22


def test_multidim_leading_axis_runs(ctx):
    ga = ctx.alloc((4, 3), jnp.float32)
    ga[0].put(jnp.arange(12, dtype=jnp.float32).reshape(4, 3))
    # whole row (integer leading index)
    np.testing.assert_array_equal(np.asarray(ga.at[0, 2].get()),
                                  [6.0, 7.0, 8.0])
    # contiguous row range
    np.testing.assert_array_equal(
        np.asarray(ga.at[0, 1:3].get()),
        np.arange(3, 9, dtype=np.float32).reshape(2, 3))
    # element inside a row
    assert float(np.asarray(ga.at[0, 2, 1].get())) == 7.0


def test_non_contiguous_indexing_lowers_or_rejects():
    # strided selections now lower to ONE (seg, stride, count) run
    assert _element_run((8,), slice(0, 8, 2)) == (0, (4,), 1, 2, 4)
    assert _element_run((4, 3), (slice(1, 3), 1)) == (4, (2,), 1, 3, 2)
    assert _element_run((4, 3), (slice(1, 3), slice(0, 2))) == (3, (2, 2), 2, 3, 2)
    # column selections after a FULL slice are strided runs too
    assert _element_run((4, 3), (slice(None), 1)) == (1, (4,), 1, 3, 4)
    assert _element_run((4, 3), (slice(None), slice(0, 2))) == (0, (4, 2), 2, 3, 4)
    # genuinely unaddressable: >1 strided level after dense-tail collapse
    with pytest.raises(IndexError):
        _element_run((4, 3, 2), (slice(0, 4, 2), slice(0, 2), slice(0, 1)))
    with pytest.raises(IndexError):
        _element_run((4,), (1, 2))             # too many indices
    with pytest.raises(IndexError):
        _element_run((4,), 4)                  # out of range
    with pytest.raises(TypeError):
        _element_run((4,), "x")
    with pytest.raises(ValueError):
        _element_run((8,), slice(None, None, -1))  # negative step
    # step > extent degenerates to the first element, not an error
    assert _element_run((8,), slice(0, 8, 16)) == (0, (1,), 1, 0, 1)
    # empty slice -> zero-element marker run
    assert _element_run((8,), slice(3, 3)) == (3, (0,), 0, 0, 1)
    # full trailing slices stay contiguous (stride 0 / count 1 degenerate)
    assert _element_run((4, 3), (slice(1, 3), slice(None))) == (3, (2, 3), 6, 0, 1)
    assert _element_run((4, 3), (slice(None), slice(None))) == (0, (4, 3), 12, 0, 1)


def test_put_shape_mismatch_raises(ctx):
    ga = ctx.alloc((8,), jnp.float32)
    with pytest.raises(ValueError):
        ga.at[0, 0:4].put(jnp.zeros((5,), jnp.float32))


# --------------------------------------------- engine lowering / epochs ----

def test_put_nb_distinct_units_flush_as_one_dispatch(ctx):
    """ACCEPTANCE: N typed put_nb calls to distinct units flush as
    exactly 1 engine dispatch (ctx.engine.dispatch_count)."""
    ga = ctx.alloc((8,), jnp.float32)
    d0 = ctx.engine.dispatch_count
    hs = [ga[u].put_nb(jnp.full((8,), float(u))) for u in ga.units]
    assert all(h.state == "queued" for h in hs)
    assert ctx.engine.dispatch_count == d0     # nothing dispatched yet
    with ctx.epoch():
        pass                                   # close the epoch
    assert ctx.engine.dispatch_count - d0 == 1
    assert all(h.state != "queued" for h in hs)
    for u in ga.units:
        assert np.all(np.asarray(ga[u].get()) == float(u))


def test_epoch_context_flushes_queued_ops(ctx):
    ga = ctx.alloc((4,), jnp.int32)
    with ctx.epoch():
        h = ga[1].put_nb(np.array([1, 2, 3, 4]))
        assert h.state == "queued"
        assert ctx.engine.pending_ops() == 1
    assert h.state != "queued"
    assert ctx.engine.pending_ops() == 0


def test_array_epoch_scopes_to_own_pool(ctx):
    team = dart_team_create(ctx, DART_TEAM_ALL, group_from_units([0, 1]))
    ga_all = ctx.alloc((4,), jnp.int32)
    ga_team = ctx.teams[team].alloc(ctx, (4,), jnp.int32)
    with ga_team.epoch():
        h_all = ga_all[0].put_nb(np.ones(4, np.int32))
        h_team = ga_team[1].put_nb(np.ones(4, np.int32))
    assert h_team.state != "queued"            # team pool flushed
    assert h_all.state == "queued"             # other pool still open
    with ctx.epoch():
        pass
    assert h_all.state != "queued"


def test_get_nb_value_flushes_and_sees_queued_puts(ctx):
    ga = ctx.alloc((6,), jnp.float32)
    ga[3].put_nb(jnp.arange(6, dtype=jnp.float32))   # still queued
    h = ga[3].get_nb()
    assert h.state == "queued"
    np.testing.assert_array_equal(np.asarray(h.value()),
                                  np.arange(6, dtype=np.float32))
    assert h.state == "complete"


# ------------------------------------------------- typed collectives -------

def test_allreduce_broadcast_gather_scatter(ctx):
    ga = ctx.alloc((4,), jnp.float32)
    with ctx.epoch():
        for u in ga.units:
            ga[u].put_nb(jnp.full((4,), float(u + 1)))
    red = ga.allreduce("sum")
    np.testing.assert_array_equal(np.asarray(red), [10.0] * 4)  # 1+2+3+4
    # allreduce replaced every member's block
    np.testing.assert_array_equal(np.asarray(ga[2].get()), [10.0] * 4)

    ga[1].put(jnp.array([7.0, 8.0, 9.0, 10.0]))
    ga.broadcast(1).wait()
    gat = np.asarray(ga.gather())
    assert gat.shape == (4, 4)
    np.testing.assert_array_equal(gat, np.tile([7, 8, 9, 10], (4, 1)))

    vals = np.arange(16, dtype=np.float32).reshape(4, 4)
    ga.scatter(vals)
    for i, u in enumerate(ga.units):
        np.testing.assert_array_equal(np.asarray(ga[u].get()), vals[i])
    with pytest.raises(ValueError):
        ga.scatter(np.zeros((3, 4), np.float32))


def test_collectives_ordered_after_queued_puts(ctx):
    """A typed collective closes the epoch first (RAW ordering)."""
    ga = ctx.alloc((2,), jnp.float32)
    for u in ga.units:
        ga[u].put_nb(jnp.full((2,), float(u)))       # all queued
    gat = np.asarray(ga.gather())
    np.testing.assert_array_equal(gat[:, 0], [0.0, 1.0, 2.0, 3.0])


def test_gather_is_one_dispatch(ctx):
    # shm=False pins the ENGINE contract — the default shm=True alloc
    # goes shm-direct on host-visible arenas (0 dispatches; covered by
    # tests/test_shm_plane.py)
    ga = ctx.alloc((8,), jnp.float32, shm=False)
    ga[0].put(jnp.ones((8,), jnp.float32))     # settle the pool
    d0 = ctx.engine.dispatch_count
    ga.gather()
    assert ctx.engine.dispatch_count - d0 == 1


# ------------------------------------------------- local zero-copy ---------

def test_local_view_zero_copy_zero_dispatch(ctx):
    if not shm_supported(ctx):
        pytest.skip("backend arenas not host-visible")
    ga = ctx.alloc((8,), jnp.float32)
    val = jnp.arange(8, dtype=jnp.float32) * 0.5
    ga[0].put(val)
    d0 = ctx.engine.dispatch_count
    lv = ga.local
    assert ctx.engine.dispatch_count == d0     # zero jitted dispatches
    assert isinstance(lv, np.ndarray) and not lv.flags.writeable
    np.testing.assert_array_equal(lv, np.asarray(val))
    # any member's block via local_view, and RAW ordering over the queue
    ga[2].put_nb(jnp.full((8,), 4.0))
    np.testing.assert_array_equal(ga.local_view(2), [4.0] * 8)


def test_alloc_shm_false_takes_jitted_path(ctx):
    ga = ctx.alloc((8,), jnp.float32, shm=False)
    assert not ga.gptr.is_shm
    ga[0].put(jnp.ones((8,), jnp.float32))
    d0 = ctx.engine.dispatch_count
    out = ga.local                             # falls back to jitted get
    assert ctx.engine.dispatch_count - d0 == 1
    assert np.all(np.asarray(out) == 1.0)


# ------------------------------------------------- property-based ----------

@given(st.integers(2, 6), st.integers(1, 16), st.integers(0, 10),
       st.sampled_from(["float32", "int32", "bfloat16", "uint8"]))
@settings(max_examples=15, deadline=None)
def test_typed_roundtrip_property(n_units, n, start, dtype):
    """put → get identity through the typed layer for random units,
    run offsets, and dtypes — the hand-rolled byte arithmetic the
    typed layer replaces, exercised end to end."""
    ctx = dart_init(n_units=n_units, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    try:
        ga = ctx.alloc((start + n,), dtype)
        unit = ga.units[start % n_units]
        val = (jnp.arange(n) + 1).astype(dtype)
        ga.at[unit, start:start + n].put(val)
        out = ga.at[unit, start:start + n].get()
        assert np.asarray(out).tobytes() == np.asarray(val).tobytes()
    finally:
        dart_exit(ctx)


@given(st.integers(2, 8))
@settings(max_examples=8, deadline=None)
def test_typed_coalesce_property(k):
    """k typed put_nb to k distinct slots of one unit: one dispatch."""
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=8192, team_pool_bytes=8192))
    try:
        ga = ctx.alloc((8 * k,), jnp.float32)
        d0 = ctx.engine.dispatch_count
        with ctx.epoch():
            for i in range(k):
                ga.at[1, 8 * i:8 * (i + 1)].put_nb(
                    jnp.full((8,), float(i)))
        assert ctx.engine.dispatch_count - d0 == 1
        for i in range(k):
            assert np.all(np.asarray(
                ga.at[1, 8 * i:8 * (i + 1)].get()) == float(i))
    finally:
        dart_exit(ctx)
