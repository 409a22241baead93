"""Tests for the shape-stable flush substrate (DispatchPlan layer):
plan-cache retrace behavior, bucketed/padded dispatch equivalence,
the Pallas segmented-copy fast path, collectives donation semantics,
and the waitall lane-error fix."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (DART_TEAM_ALL, DartConfig, dart_exit, dart_flush,
                        dart_get_blocking, dart_get_nb, dart_init,
                        dart_memalloc, dart_put, dart_put_blocking,
                        dart_team_memalloc_aligned, dart_waitall)
from repro.core import collectives as _coll
from repro.core import onesided as _os
from repro.kernels import segmented_copy as sc


@pytest.fixture()
def ctx(engine_impl):
    # engine-impl parametrization (conftest.py): every ctx-based test
    # in this module runs under both impl='ref' and impl='pallas'
    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=8192, team_pool_bytes=8192))
    c.engine.impl = engine_impl
    yield c
    dart_exit(c)


# ------------------------------------------------------- bucket mechanics --

def test_bucket_pow2():
    assert sc.bucket_pow2(0) == 1
    assert sc.bucket_pow2(1) == 1
    assert sc.bucket_pow2(5) == 8
    assert sc.bucket_pow2(8) == 8
    assert sc.bucket_pow2(9) == 16
    assert sc.bucket_pow2(3, floor=16) == 16


def test_pack_descriptors_pads_with_noops():
    desc, flat, seg = sc.pack_descriptors(
        [1, 2, 0], [10, 20, 30], [3, 5, 2],
        [np.full(3, 7, np.uint8), np.full(5, 8, np.uint8),
         np.full(2, 9, np.uint8)])
    assert desc.shape == (4, 6)                  # k=3 → bucket 4
    assert desc[3, sc.LEN] == 0                  # padding is a no-op
    assert seg == sc.SEG_FLOOR
    assert flat.shape[0] >= 10 + seg             # payload + window margin
    np.testing.assert_array_equal(desc[:3, sc.START], [0, 3, 8])
    assert list(flat[:10]) == [7] * 3 + [8] * 5 + [9] * 2


def test_padding_descriptors_do_not_touch_arena():
    """len=0 descriptors (bucket padding) must leave every arena byte
    untouched — masked lanes are dropped, not clamped to offset 0."""
    arena = jnp.arange(2 * 32, dtype=jnp.uint8).reshape(2, 32)
    before = np.asarray(arena).copy()
    desc, flat, seg = sc.pack_descriptors([1], [30], [2],
                                          [np.array([255, 254], np.uint8)])
    fn, _ = sc.scatter_plan(arena.shape, desc.shape[0], seg, flat.shape[0],
                            ordered=False, impl="ref", donate=False)
    out = np.asarray(fn(arena, desc, flat)).copy()
    assert list(out[1, 30:]) == [255, 254]
    out[1, 30:] = before[1, 30:]
    np.testing.assert_array_equal(out, before)   # nothing else moved


def test_pack_acc_descriptors_identity_padded():
    """Accumulate staging: the descriptor gains the op column, every
    payload owns a seg-aligned slot, and ALL padding bytes — short-
    payload tails and whole bucket-padding slots — decode to the op's
    identity element (true no-ops by value)."""
    pays = [np.asarray([3.0], np.float32).view(np.uint8),
            np.asarray([2.0, 4.0], np.float32).view(np.uint8)]
    desc, flat, seg = sc.pack_acc_descriptors(
        [0, 1], [32, 64], [4, 8], pays, "prod", jnp.float32)
    assert desc.shape == (4, 7)                    # k=2 → bucket 4, +op col
    assert list(desc[:, sc.OPCODE]) == [sc.REDUCE_OPS["prod"]] * 4
    np.testing.assert_array_equal(desc[:2, sc.LEN], [4, 8])
    np.testing.assert_array_equal(desc[:, sc.START],
                                  [0, seg, 2 * seg, 3 * seg])
    vals = flat.view(np.float32)
    assert vals[0] == 3.0 and list(vals[seg // 4:seg // 4 + 2]) == [2., 4.]
    # every byte not covered by a payload is the identity (1.0)
    mask = np.ones(flat.size, bool)
    mask[:4] = mask[seg:seg + 8] = False
    assert np.all(flat.view(np.float32)[mask.reshape(-1, 4).all(1)] == 1.0)


def test_op_identity_table():
    assert sc.op_identity("sum", jnp.float32) == 0.0
    assert sc.op_identity("prod", jnp.int32) == 1
    assert sc.op_identity("min", jnp.float32) == np.inf
    assert sc.op_identity("max", jnp.float32) == -np.inf
    assert sc.op_identity("min", jnp.int32) == np.iinfo(np.int32).max
    assert sc.op_identity("max", jnp.uint8) == 0
    with pytest.raises(ValueError):
        sc.op_identity("xor", jnp.int32)


def test_accumulate_padding_descriptors_do_not_touch_arena():
    """len=0 accumulate descriptors (bucket padding) must leave every
    arena byte untouched under both impls — masked lanes are dropped
    (ref) or keep the window (pallas), and their payload is the
    identity anyway."""
    base = np.arange(2 * 64, dtype=np.uint8).reshape(2, 64)
    desc, flat, seg = sc.pack_acc_descriptors(
        [1], [32], [8], [np.asarray([5, 5], np.int32).view(np.uint8)],
        "sum", jnp.int32)
    for impl in ("ref", "pallas"):
        fn, _ = sc.accumulate_plan((2, 64), desc.shape[0], seg,
                                   flat.shape[0], op="sum",
                                   dtype=jnp.int32, fetch=False,
                                   impl=impl, donate=False)
        out = np.asarray(fn(jnp.asarray(base), desc, flat)).copy()
        got = out[1, 32:40].view(np.int32).copy()
        expect = base[1, 32:40].view(np.int32) + 5
        np.testing.assert_array_equal(got, expect)
        out[1, 32:40] = base[1, 32:40]
        np.testing.assert_array_equal(out, base)   # nothing else moved


# ------------------------------------------------------ retrace behavior ---

def test_warm_flushes_zero_recompiles_within_buckets(ctx):
    """The acceptance criterion: after warmup, a steady-state loop of
    epochs with VARYING run lengths and payload sizes (within the
    pow2 buckets) performs ZERO plan-cache misses — every flush hits a
    cached compiled kernel."""
    g = dart_memalloc(ctx, 8192, unit=0)

    def epoch(k, n_floats):
        hs = [dart_put(ctx, g + 512 * i,
                       jnp.full((n_floats,), float(i + 1), jnp.float32))
              for i in range(k)]
        dart_flush(ctx)
        dart_waitall(hs)

    epoch(8, 16)                                 # warm the (8, 64B) plan
    epoch(8, 16)
    c0, h0 = ctx.engine.compile_count, ctx.engine.plan_cache_hits
    for k, n in [(5, 16), (7, 9), (8, 12), (6, 10), (5, 16), (8, 13)]:
        epoch(k, n)                              # k≤8, 33..64B: same bucket
    assert ctx.engine.compile_count == c0, \
        "varying-size warm epochs must not recompile"
    assert ctx.engine.plan_cache_hits > h0


def test_get_runs_share_plans_across_sizes(ctx):
    g = dart_memalloc(ctx, 4096, unit=1)
    for i in range(8):
        dart_put_blocking(ctx, g + 128 * i,
                          jnp.full((16,), float(i), jnp.float32))

    def gets(sizes):
        hs = [dart_get_nb(ctx, g + 128 * i, (n,), jnp.float32)
              for i, n in enumerate(sizes)]
        dart_flush(ctx)
        return [np.asarray(h.value()) for h in hs]

    gets([16, 9, 12])                            # warm the bucket
    c0 = ctx.engine.compile_count
    for sizes in ([12, 16, 10], [9, 9], [16, 11, 13]):
        vals = gets(sizes)
        for i, (n, v) in enumerate(zip(sizes, vals)):
            assert np.all(v == float(i)) and v.shape == (n,)
    assert ctx.engine.compile_count == c0


# -------------------------------------------- bucketed dispatch oracle -----

def _apply_blocking(ops):
    """Oracle: the same ops as a strict blocking sequence."""
    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    try:
        g = dart_memalloc(c, 1024, unit=0)
        for row, off, payload in ops:
            dart_put_blocking(c, g.setunit(row) + off, payload)
        return np.asarray(c.state[_os.WORLD_POOLID]).copy()
    finally:
        dart_exit(c)


@given(st.lists(st.tuples(st.integers(0, 3),      # row
                          st.integers(0, 1020),   # offset
                          st.integers(1, 64)),    # payload bytes
                min_size=1, max_size=12),
       st.booleans())
@settings(max_examples=20, deadline=None)
def test_bucketed_dispatch_byte_identical_to_blocking(op_specs, use_pallas):
    """Property: one coalesced bucketed/padded flush produces bytes
    identical to the equivalent blocking sequence — overlapping runs,
    mixed sizes, and ops hard against the pool end included."""
    pool = 1024
    ops = []
    for row, off, nbytes in op_specs:
        off = min(off, pool - nbytes)            # headroom edge: off+n≤pool
        payload = (np.arange(nbytes, dtype=np.int64) * 37 + off + row
                   ).astype(np.uint8)
        ops.append((row, off, payload))
    expected = _apply_blocking(ops)

    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=pool, team_pool_bytes=pool))
    try:
        c.engine.impl = "pallas" if use_pallas else "ref"
        g = dart_memalloc(c, pool, unit=0)
        hs = [dart_put(c, g.setunit(row) + off, payload)
              for row, off, payload in ops]
        dart_flush(c)
        dart_waitall(hs)
        got = np.asarray(c.state[_os.WORLD_POOLID])
        np.testing.assert_array_equal(got, expected)
    finally:
        dart_exit(c)


def test_pallas_gather_matches_ref(ctx):
    g = dart_memalloc(ctx, 2048, unit=2)
    sizes = [4, 17, 8, 1]
    for i, n in enumerate(sizes):
        dart_put_blocking(ctx, g + 256 * i,
                          (np.arange(n) + 5 * i).astype(np.uint8))
    for impl in ("ref", "pallas"):
        ctx.engine.impl = impl
        hs = [dart_get_nb(ctx, g + 256 * i, (n,), jnp.uint8)
              for i, n in enumerate(sizes)]
        d0 = ctx.engine.dispatch_count
        dart_flush(ctx)
        assert ctx.engine.dispatch_count - d0 == 1
        for i, (n, h) in enumerate(zip(sizes, hs)):
            np.testing.assert_array_equal(
                np.asarray(h.value()), np.arange(n, dtype=np.uint8) + 5 * i)


def test_pallas_window_fallback_is_counted():
    """A run that fails the Pallas window precondition is served by ref
    and counted as a fallback — never a silent kernel switch."""
    c = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    try:
        c.engine.impl = "pallas"
        g = dart_memalloc(c, 1024, unit=1)
        dart_put_blocking(c, g, np.arange(8, dtype=np.uint8))
        stats = c.engine.dispatch_stats()
        assert stats["dispatches"] == {"ref": 0, "pallas": 1}
        assert stats["impl_fallbacks"] == 0
        # 3 B at the pool's last bytes: the 16 B padded window overruns
        tail = g + (1024 - 3)
        dart_put_blocking(c, tail, np.array([7, 8, 9], np.uint8))
        stats = c.engine.dispatch_stats()
        assert stats["dispatches"] == {"ref": 1, "pallas": 1}
        assert stats["impl_fallbacks"] == 1
        np.testing.assert_array_equal(
            np.asarray(dart_get_blocking(c, tail, (3,), jnp.uint8)),
            [7, 8, 9])
        assert c.engine.dispatch_stats()["impl_fallbacks"] == 2
    finally:
        dart_exit(c)


# ------------------------------------- mixed get run: one counted dispatch -

def test_mixed_get_run_is_one_dispatch_including_decode(ctx):
    """The per-op typed decode must ride inside the single counted
    dispatch (host-side, from one shared device→host copy) — no
    trailing per-op device launches after the gather."""
    g = dart_memalloc(ctx, 2048, unit=0)
    sizes = [(3,), (7,), (2, 4)]
    dtypes = [jnp.float32, jnp.int32, jnp.uint8]
    for i, (shp, dt) in enumerate(zip(sizes, dtypes)):
        dart_put_blocking(ctx, g + 256 * i,
                          (jnp.arange(int(np.prod(shp))) + i).astype(dt
                                                                     ).reshape(shp))
    hs = [dart_get_nb(ctx, g + 256 * i, shp, dt)
          for i, (shp, dt) in enumerate(zip(sizes, dtypes))]
    d0 = ctx.engine.dispatch_count
    dart_flush(ctx)
    vals = [h.value() for h in hs]               # decode: zero dispatches
    assert ctx.engine.dispatch_count - d0 == 1
    for i, (shp, dt, v) in enumerate(zip(sizes, dtypes, vals)):
        assert v.shape == shp and v.dtype == jnp.dtype(dt)
        np.testing.assert_array_equal(
            np.asarray(v).reshape(-1),
            (np.arange(int(np.prod(shp))) + i).astype(np.asarray(v).dtype))


# ----------------------------------------------------- waitall lane error --

def test_waitall_cleared_engine_names_the_dropped_lane():
    """A queued op silently dropped by engine.clear() must surface an
    error naming ITS OWN (pool, row) lane — and handles on other, live
    engines in the same waitall must still complete."""
    ctx_dead = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    ctx_live = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    try:
        gd = dart_memalloc(ctx_dead, 256, unit=1)
        gl = dart_memalloc(ctx_live, 256, unit=0)
        h_dead = dart_put(ctx_dead, gd, jnp.ones((4,), jnp.int32))
        h_live = dart_put(ctx_live, gl, jnp.full((4,), 5, jnp.int32))
        dart_exit(ctx_dead)                      # clears its engine
        with pytest.raises(RuntimeError) as exc:
            dart_waitall([h_live, h_dead])
        # the error names the dropped op's lane, not a generic/wrong op
        assert f"pool {h_dead.poolid}, row {h_dead.row}" in str(exc.value)
        assert h_live.state in ("issued", "complete")   # live op dispatched
        out = dart_get_blocking(ctx_live, gl, (4,), jnp.int32)
        assert np.all(np.asarray(out) == 5)
    finally:
        dart_exit(ctx_live)


# ------------------------------------------------- collectives donation ----

def test_functional_collectives_do_not_donate_snapshot():
    """engine=None is the purely functional contract: the caller's
    retained heap snapshot must stay alive and unchanged after
    bcast/scatter/scatter_typed (previously those three donated the
    arena and deleted the snapshot)."""
    ctx = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    try:
        g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
        dart_put_blocking(ctx, g, jnp.full((8,), 3, jnp.int32))
        snap = dict(ctx.state)
        poolid = ctx.teams[DART_TEAM_ALL].poolid
        before = np.asarray(snap[poolid]).copy()

        s1, _ = _coll.dart_bcast(snap, ctx.heap, ctx.teams_by_slot, g,
                                 32, engine=None)
        s2, _ = _coll.dart_scatter(
            snap, ctx.heap, ctx.teams_by_slot, g,
            np.arange(4 * 16, dtype=np.uint8).reshape(4, 16), engine=None)
        s3, _ = _coll.dart_scatter_typed(
            snap, ctx.heap, ctx.teams_by_slot, g,
            jnp.arange(8, dtype=jnp.int32).reshape(4, 2), engine=None)
        s4, red = _coll.dart_allreduce(snap, ctx.heap, ctx.teams_by_slot,
                                       g, (8,), jnp.int32, "sum",
                                       engine=None)
        assert np.all(np.asarray(red) == 3)        # only row 0 holds 3s
        s5, _ = _coll.dart_reduce(snap, ctx.heap, ctx.teams_by_slot, g,
                                  (8,), jnp.int32, "sum", 0, engine=None)
        for new_state in (s1, s2, s3, s4, s5):
            assert not new_state[poolid].is_deleted()
        # the snapshot arena was neither deleted nor mutated
        assert not snap[poolid].is_deleted()
        np.testing.assert_array_equal(np.asarray(snap[poolid]), before)
    finally:
        dart_exit(ctx)


def test_scatter_typed_canonicalizes_wide_dtypes(ctx):
    """int64/float64 inputs canonicalize to 32-bit inside the jit; the
    kernel's byte mask must be computed from the canonical dtype or
    the bucket padding zeroes the 4 bytes after each row's segment."""
    from repro.core import runtime as rt
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
    sentinel = jnp.full((4,), 0xAB, jnp.uint8)
    for u in range(4):
        dart_put_blocking(ctx, g.setunit(u) + 12, sentinel)
    rt.dart_scatter_typed(ctx, g,
                          np.arange(12, dtype=np.int64).reshape(4, 3))
    vals, _ = rt.dart_gather_typed(ctx, g, (3,), jnp.int32)
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.arange(12).reshape(4, 3))
    for u in range(4):                   # bytes past the segment intact
        tail = dart_get_blocking(ctx, g.setunit(u) + 12, (4,), jnp.uint8)
        assert np.all(np.asarray(tail) == 0xAB)


def test_oversize_arena_refused_loudly():
    """Arenas beyond the flat int32 addressing range must raise, not
    silently drop writes."""
    with pytest.raises(NotImplementedError):
        sc.check_flat_addressable((4, 1 << 30))
    sc.check_flat_addressable((4, 1 << 20))      # normal pools fine


def test_collective_sizes_share_bucketed_plans(ctx):
    """Varying collective sizes within a bucket reuse cached kernels."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 512)
    from repro.core import runtime as rt
    rt.dart_bcast(ctx, g, 40)                    # warm the 64B bucket
    c0 = ctx.engine.compile_count
    for nbytes in (33, 64, 57, 48):
        rt.dart_bcast(ctx, g, nbytes)
    assert ctx.engine.compile_count == c0
    rt.dart_gather_typed(ctx, g, (9,), jnp.float32)   # warm 16-elem bucket
    c0 = ctx.engine.compile_count
    for n in (10, 16, 12):
        vals, _ = rt.dart_gather_typed(ctx, g, (n,), jnp.float32)
        assert vals.shape == (4, n)
    assert ctx.engine.compile_count == c0
