"""Tests for the symmetric heap + allocators (paper §IV.B.3)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (ALIGNMENT, BlockAllocator, OutOfGlobalMemory,
                        SymmetricHeap, align_up, from_bytes, nbytes_of,
                        to_bytes)


# ------------------------------------------------------- block allocator ----

def test_block_allocator_first_fit_and_free():
    a = BlockAllocator(1024)
    o1 = a.alloc(100)            # -> 0, rounded to 128
    o2 = a.alloc(100)            # -> 128
    assert (o1, o2) == (0, 128)
    a.free(o1)
    assert a.alloc(50) == 0      # first fit reuses the hole
    with pytest.raises(OutOfGlobalMemory):
        a.alloc(2048)


def test_block_allocator_coalescing():
    a = BlockAllocator(512)
    offs = [a.alloc(128) for _ in range(4)]   # exhausts the pool
    with pytest.raises(OutOfGlobalMemory):
        a.alloc(1)
    for o in offs:
        a.free(o)
    assert a.alloc(512) == 0     # holes coalesced back into one block


@given(st.lists(st.integers(1, 300), min_size=1, max_size=30))
@settings(max_examples=50)
def test_block_allocator_no_overlap_property(sizes):
    """Live allocations never overlap and stay in-bounds."""
    a = BlockAllocator(1 << 16)
    live = []
    for i, s in enumerate(sizes):
        try:
            off = a.alloc(s)
        except OutOfGlobalMemory:
            continue
        live.append((off, align_up(s)))
        if i % 4 == 3 and live:
            o, _ = live.pop(0)
            a.free(o)
    live.sort()
    for (o1, l1), (o2, _) in zip(live, live[1:]):
        assert o1 + l1 <= o2
    for o, l in live:
        assert 0 <= o and o + l <= (1 << 16)
        assert o % ALIGNMENT == 0


# ------------------------------------------------------- heap + pools -------

def test_symmetric_heap_pools():
    h = SymmetricHeap(n_units=4)
    world = h.reserve_pool(n_rows=4, pool_bytes=1024, collective=False)
    team = h.reserve_pool(n_rows=4, pool_bytes=1024, collective=True)
    # non-collective: per-unit independent cursors (paper Fig. 4)
    o_u0 = h.memalloc_local(world, 0, 100)
    o_u1 = h.memalloc_local(world, 1, 300)
    o_u0b = h.memalloc_local(world, 0, 100)
    assert o_u0 == 0 and o_u1 == 0       # each unit starts at its own base
    assert o_u0b == 128
    # collective: one shared cursor -> aligned & symmetric (paper Fig. 5)
    c1 = h.memalloc_aligned(team, 256)
    c2 = h.memalloc_aligned(team, 256)
    assert (c1, c2) == (0, 256)
    assert len(team.table) == 2
    rec = team.table.query(c2 + 10)      # address inside second alloc
    assert rec.offset == c2
    h.memfree_aligned(team, c1)
    assert len(team.table) == 1
    assert h.memalloc_aligned(team, 128) == 0   # slot recycled


def test_block_allocator_free_introspection():
    a = BlockAllocator(1024)
    o1, o2 = a.alloc(128), a.alloc(128)
    o3 = a.alloc(128)                          # live: [0,128,256), tail free
    assert a.bytes_live() == 384
    assert a.bytes_free() == 640
    assert a.largest_free() == 640             # the tail block
    a.free(o1)
    assert a.bytes_free() == 768
    assert a.largest_free() == 640             # hole at 0 is not adjacent
    a.free(o2)
    assert a.bytes_free() == 896
    assert a.largest_free() == 640             # [0,256) still split by o3
    a.free(o3)
    assert a.bytes_free() == 1024
    assert a.largest_free() == 1024            # everything coalesced


def test_team_memfree_then_realloc_returns_coalesced_block():
    """Runtime-level allocator reuse: dart_team_memfree returns blocks
    to the team pool, adjacent holes coalesce, and a re-alloc spanning
    the combined extent succeeds at the original offset."""
    from repro.core import (DART_TEAM_ALL, DartConfig, dart_exit, dart_init,
                            dart_team_memalloc_aligned, dart_team_memfree)
    ctx = dart_init(n_units=2, config=DartConfig(team_pool_bytes=4096))
    try:
        g1 = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 1024)
        g2 = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 1024)
        assert (g1.addr, g2.addr) == (0, 1024)
        alloc = ctx.heap.windows.lookup(DART_TEAM_ALL).shared_alloc
        dart_team_memfree(ctx, DART_TEAM_ALL, g1)
        dart_team_memfree(ctx, DART_TEAM_ALL, g2)
        assert alloc.bytes_live() == 0
        assert alloc.largest_free() == 4096    # holes coalesced
        g3 = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 2048)
        assert g3.addr == 0                    # spans both former blocks
        # and the translation table tracks only the live allocation
        assert len(ctx.heap.windows.lookup(DART_TEAM_ALL).table) == 1
    finally:
        dart_exit(ctx)


def test_global_array_request_overflowing_team_pool_raises():
    """A GlobalArray-sized request larger than team_pool_bytes must
    surface OutOfGlobalMemory from the pool allocator."""
    import jax.numpy as jnp
    from repro.core import DartConfig, dart_exit, dart_init
    ctx = dart_init(n_units=2, config=DartConfig(team_pool_bytes=2048))
    try:
        ctx.alloc((256,), jnp.float32)         # 1 KiB fits
        with pytest.raises(OutOfGlobalMemory):
            ctx.alloc((512,), jnp.float32)     # 2 KiB > remaining 1 KiB
        with pytest.raises(OutOfGlobalMemory):
            ctx.alloc((4096,), jnp.float64)    # 32 KiB > whole pool
    finally:
        dart_exit(ctx)


def test_translation_table_query_miss():
    h = SymmetricHeap(n_units=2)
    team = h.reserve_pool(n_rows=2, pool_bytes=512, collective=True)
    h.memalloc_aligned(team, 128)
    with pytest.raises(KeyError):
        team.table.query(500)


def test_heap_state_shapes():
    h = SymmetricHeap(n_units=3)
    h.reserve_pool(n_rows=3, pool_bytes=100, collective=False)  # rounds up
    state = h.init_state()
    assert state[0].shape == (3, 128)
    assert state[0].dtype == jnp.uint8


# ------------------------------------------------- byte conversion ----------

DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32, jnp.uint8, jnp.float16,
          jnp.int8]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(7,), (3, 5), (2, 3, 4), ()])
def test_bytes_roundtrip(dtype, shape):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape) * 3, dtype=dtype)
    b = to_bytes(x)
    assert b.dtype == jnp.uint8
    assert b.size == nbytes_of(shape, dtype)
    y = from_bytes(b, shape, dtype)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@given(st.integers(1, 64), st.sampled_from(["float32", "int32", "bfloat16"]))
@settings(max_examples=30, deadline=None)
def test_bytes_roundtrip_property(n, dtype):
    x = jnp.arange(n).astype(dtype)
    assert np.array_equal(np.asarray(from_bytes(to_bytes(x), (n,), dtype)),
                          np.asarray(x))
