"""Integration tests: runtime context + one-sided ops (paper §IV.B.3-5)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (DART_TEAM_ALL, DartConfig, GlobalPtr, dart_exit,
                        dart_get, dart_get_blocking, dart_init,
                        dart_memalloc, dart_memfree, dart_put,
                        dart_put_blocking, dart_team_create,
                        dart_team_destroy, dart_team_memalloc_aligned,
                        dart_team_myid, dart_team_size, dart_testall,
                        dart_waitall, group_from_units)
from repro.core import dart_allreduce, dart_barrier, dart_bcast


@pytest.fixture()
def ctx():
    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    yield c
    dart_exit(c)


def test_init_creates_team_all(ctx):
    assert dart_team_size(ctx, DART_TEAM_ALL) == 4
    assert dart_team_myid(ctx, DART_TEAM_ALL, 2) == 2


def test_noncollective_put_get_roundtrip(ctx):
    g = dart_memalloc(ctx, 256, unit=2)
    assert not g.is_collective and g.unitid == 2
    val = jnp.arange(16, dtype=jnp.float32)
    dart_put_blocking(ctx, g, val)
    out = dart_get_blocking(ctx, g, (16,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(val))


def test_noncollective_isolation_between_units(ctx):
    """Same offset on different units are distinct locations (Fig. 4)."""
    g0 = dart_memalloc(ctx, 64, unit=0)
    g3 = dart_memalloc(ctx, 64, unit=3)
    assert g0.addr == g3.addr == 0
    dart_put_blocking(ctx, g0, jnp.full((16,), 7, jnp.int32))
    dart_put_blocking(ctx, g3, jnp.full((16,), 9, jnp.int32))
    assert np.asarray(dart_get_blocking(ctx, g0, (16,), jnp.int32))[0] == 7
    assert np.asarray(dart_get_blocking(ctx, g3, (16,), jnp.int32))[0] == 9


def test_collective_alloc_aligned_symmetric(ctx):
    """Any member can address any member's portion at the same offset."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    assert g.is_collective
    for u in range(4):
        dart_put_blocking(ctx, g.setunit(u),
                          jnp.full((8,), u, jnp.float32))
    for u in range(4):
        out = dart_get_blocking(ctx, g.setunit(u), (8,), jnp.float32)
        assert np.all(np.asarray(out) == u)


def test_collective_second_alloc_offset_identical(ctx):
    g1 = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    g2 = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    assert g2.addr == g1.addr + 128     # shared cursor: same offset for all


def test_subteam_translation_and_pools(ctx):
    sub = dart_team_create(ctx, DART_TEAM_ALL, group_from_units([1, 3]))
    assert dart_team_size(ctx, sub) == 2
    assert dart_team_myid(ctx, sub, 3) == 1      # abs -> rel translation
    g = dart_team_memalloc_aligned(ctx, sub, 64)
    dart_put_blocking(ctx, g.setunit(3), jnp.arange(4, dtype=jnp.int32))
    out = dart_get_blocking(ctx, g.setunit(3), (4,), jnp.int32)
    np.testing.assert_array_equal(np.asarray(out), [0, 1, 2, 3])
    with pytest.raises(KeyError):
        # unit 0 is not a member of the sub-team
        dart_get_blocking(ctx, g.setunit(0), (4,), jnp.int32)
    dart_team_destroy(ctx, sub)


def test_team_destroy_recycles_slot(ctx):
    """Paper §IV.B.2: teamlist slots are reused after destroy."""
    t1 = dart_team_create(ctx, DART_TEAM_ALL, group_from_units([0, 1]))
    slot1 = ctx.teams[t1].slot
    dart_team_destroy(ctx, t1)
    t2 = dart_team_create(ctx, DART_TEAM_ALL, group_from_units([2, 3]))
    assert ctx.teams[t2].slot == slot1
    assert t2 != t1                      # teamIDs themselves never reused


def test_nonblocking_put_get_handles(ctx):
    g = dart_memalloc(ctx, 1024, unit=1)
    hs = []
    for k in range(4):
        hs.append(dart_put(ctx, g + 128 * k,
                           jnp.full((32,), k, jnp.float32)))
    dart_waitall(hs)
    vals = []
    gets = []
    for k in range(4):
        v, h = dart_get(ctx, g + 128 * k, (32,), jnp.float32)
        vals.append(v); gets.append(h)
    dart_waitall(gets)
    assert dart_testall(gets)
    for k, v in enumerate(vals):
        assert np.all(np.asarray(v) == k)


def test_put_get_bounds_checked(ctx):
    g = dart_memalloc(ctx, 128, unit=0)
    near_end = GlobalPtr(unitid=0, segid=g.segid, flags=g.flags,
                         addr=ctx.config.non_collective_pool_bytes - 4)
    with pytest.raises(ValueError):
        dart_put_blocking(ctx, near_end, jnp.zeros(16, jnp.float32))
    with pytest.raises(ValueError):
        dart_get_blocking(ctx, near_end, (16,), jnp.float32)


def test_memfree_reuse(ctx):
    g1 = dart_memalloc(ctx, 256, unit=0)
    dart_memfree(ctx, g1)
    g2 = dart_memalloc(ctx, 128, unit=0)
    assert g2.addr == g1.addr


def test_bcast_and_allreduce(ctx):
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    for u in range(4):
        dart_put_blocking(ctx, g.setunit(u),
                          jnp.full((4,), float(u + 1), jnp.float32))
    red = dart_allreduce(ctx, g, (4,), jnp.float32, op="sum")
    assert np.all(np.asarray(red) == 1 + 2 + 3 + 4)
    # after allreduce every member holds the reduced value
    for u in range(4):
        out = dart_get_blocking(ctx, g.setunit(u), (4,), jnp.float32)
        assert np.all(np.asarray(out) == 10.0)
    # bcast root's bytes
    dart_put_blocking(ctx, g.setunit(2), jnp.full((4,), 42.0, jnp.float32))
    dart_bcast(ctx, g.setunit(2), 16)
    for u in range(4):
        out = dart_get_blocking(ctx, g.setunit(u), (4,), jnp.float32)
        assert np.all(np.asarray(out) == 42.0)
    dart_barrier(ctx)


@given(st.integers(0, 3), st.integers(0, 24),
       st.sampled_from(["float32", "int32", "bfloat16"]),
       st.integers(1, 16))
@settings(max_examples=25, deadline=None)
def test_put_get_property(unit, word_off, dtype, n):
    """What you put at (unit, offset) is exactly what you get back."""
    ctx = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    try:
        g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 2048)
        ptr = g.setunit(unit) + word_off * 4
        val = (jnp.arange(n) + 1).astype(dtype)
        dart_put_blocking(ctx, ptr, val)
        out = dart_get_blocking(ctx, ptr, (n,), dtype)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(val))
    finally:
        dart_exit(ctx)
