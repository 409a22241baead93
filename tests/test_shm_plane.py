"""Shared-memory plane: zero-copy writes, shm-direct collectives, and
the routing-correctness regressions (ISSUE 10).

Covers, per the satellite list:

* the per-pool ``shm_supported`` cache (mixed-visibility pools must not
  poison each other; invalidation on destroy/exit);
* the headroom bounds check (typed :class:`ShmBoundsError` carrying
  (poolid, row, off, nbytes) instead of a truncated-slice reshape
  crash);
* the hoisted hot-path classifier (ONE top-level engine-lock
  acquisition per routed get; zero dlpack probes in the steady state);
* shm-put vs jitted-put byte identity under random interleavings, both
  engine impls, with the ProgressPlane daemon live — plus chaos-marked
  runs proving the fault plane's failed-lane semantics hold on the shm
  write path;
* shm-direct collective equivalence vs the engine collectives at zero
  jitted dispatches.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DART_TEAM_ALL, DartConfig, DartError, DartGroup,
                        Locality, ShmBoundsError, UnitFailedError,
                        classify_locality, dart_exit,
                        dart_get_nb, dart_init, dart_put,
                        dart_put_blocking, dart_shm_view,
                        dart_team_create, dart_team_destroy,
                        dart_team_memalloc_shared, invalidate_shm_cache,
                        shm_supported, shm_writable)
from repro.core import onesided as _os
from repro.core import runtime as rt

POOL_BYTES = 8192


@pytest.fixture()
def ctx(engine_impl):
    c = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=POOL_BYTES, team_pool_bytes=POOL_BYTES))
    c.engine.impl = engine_impl
    yield c
    dart_exit(c)


def _require_shm(ctx):
    if not shm_writable(ctx):
        pytest.skip("backend arenas not host-writable")


def _lane_of(ctx, gptr):
    return _os.deref(ctx.heap, ctx.teams_by_slot, gptr)


# ------------------------------------------------ per-pool cache ----------

def test_mixed_visibility_cache_is_per_pool(ctx):
    """Regression: the support cache was one boolean per *context*, so
    the first probed pool's answer misrouted every other pool under
    mixed visibility.  A device-only pool (simulated by an arena whose
    dlpack probe fails) must cache False for ITSELF only."""
    _require_shm(ctx)
    teamid = dart_team_create(ctx, DART_TEAM_ALL, DartGroup((0, 1)))
    g_bad = rt.dart_team_memalloc_aligned(ctx, teamid, 64)
    pool_bad, _, _ = _lane_of(ctx, g_bad)

    real = ctx.state[pool_bad]
    ctx.state[pool_bad] = object()          # dlpack probe fails
    try:
        assert shm_supported(ctx, pool_bad) is False
        # the negative answer must NOT have poisoned the other pools
        g_good = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
        pool_good, _, _ = _lane_of(ctx, g_good)
        assert shm_supported(ctx, pool_good) is True
        assert shm_writable(ctx, pool_good) is True
    finally:
        ctx.state[pool_bad] = real
    # the False is CACHED (same pool, arena now probe-able again) ...
    assert shm_supported(ctx, pool_bad) is False
    # ... until explicitly invalidated
    invalidate_shm_cache(ctx, pool_bad)
    assert shm_supported(ctx, pool_bad) is True
    # destroy drops the pool's cache entry; exit clears the whole cache
    dart_team_destroy(ctx, teamid)
    assert pool_bad not in ctx._shm_cache
    assert shm_supported(ctx, pool_bad) is False       # pool is gone


class _TpuArena:
    """An arena stand-in that lives on a TPU: any host probe of it is a
    bug, because its buffer pointer is a device address."""

    def devices(self):
        class _Dev:
            platform = "tpu"
        return {_Dev()}

    def __dlpack__(self, *a, **k):
        raise AssertionError("dlpack probe of a device arena")

    def unsafe_buffer_pointer(self):
        raise AssertionError("pointer probe of a device arena")


def test_device_arena_is_never_host_visible(ctx):
    """An arena on an accelerator probes as (False, False) before any
    dlpack or pointer probe, and its pointers route to the engine."""
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    poolid, _, _ = _lane_of(ctx, g)
    real = ctx.state[poolid]
    invalidate_shm_cache(ctx, poolid)
    ctx.state[poolid] = _TpuArena()
    try:
        assert shm_supported(ctx, poolid) is False
        assert shm_writable(ctx, poolid) is False
        assert classify_locality(ctx, g) is Locality.REMOTE
    finally:
        ctx.state[poolid] = real
        invalidate_shm_cache(ctx, poolid)


def test_cache_cleared_on_exit():
    c = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=1024, team_pool_bytes=1024))
    if not shm_writable(c):
        dart_exit(c)
        pytest.skip("backend arenas not host-writable")
    assert c._shm_cache            # probe populated it
    dart_exit(c)
    assert c._shm_cache == {}
    assert shm_supported(c) is False


# ------------------------------------------------ headroom check ----------

def test_shm_view_headroom_typed_error(ctx):
    """An overrunning span raises ShmBoundsError (typed, lane-
    addressed) instead of silently truncating the slice."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    bad = g.incaddr(POOL_BYTES - 8)         # 8 B of headroom left
    with pytest.raises(ShmBoundsError) as ei:
        dart_shm_view(ctx, bad, (4,), jnp.float32)      # needs 16 B
    err = ei.value
    poolid, row, off = _lane_of(ctx, bad)
    assert err.poolid == poolid
    assert err.row == row
    assert err.off == off
    assert err.nbytes == 16
    # part of the DartError ladder AND a ValueError (legacy symptom)
    assert isinstance(err, DartError) and isinstance(err, ValueError)


def test_shm_put_overrun_matches_engine_error(ctx):
    """The write side keeps the ENGINE's geometry error verbatim — an
    overrunning blocking put raises the same ValueError whether it
    would have routed shm or not."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    bad = g.incaddr(POOL_BYTES - 8)
    with pytest.raises(ValueError, match="overruns"):
        dart_put_blocking(ctx, bad, jnp.zeros((4,), jnp.float32))


# ---------------------------------------- hoisted hot-path classifier -----

class _CountingLock:
    """RLock proxy counting TOP-LEVEL acquisitions (depth 0 → 1);
    nested re-entries (e.g. the ordering flush inside a routed get) are
    free under an RLock and don't count."""

    def __init__(self, inner):
        self._inner = inner
        self._depth = 0
        self.toplevel = 0

    def __enter__(self):
        self._inner.acquire()
        if self._depth == 0:
            self.toplevel += 1
        self._depth += 1
        return self

    def __exit__(self, *exc):
        self._depth -= 1
        self._inner.release()
        return False

    def acquire(self, *a, **kw):
        ok = self._inner.acquire(*a, **kw)
        if ok:
            if self._depth == 0:
                self.toplevel += 1
            self._depth += 1
        return ok

    def release(self):
        self._depth -= 1
        self._inner.release()


def test_routed_get_single_lock_acquisition_no_steady_probes(ctx):
    """Satellite 3: a routed get takes the engine lock ONCE at top
    level (deref + cached probe + flush + view under one hold) and
    never re-probes dlpack support per deref."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 256)
    dart_put_blocking(ctx, g.setunit(1), jnp.arange(8, dtype=jnp.float32))
    rt.dart_get_blocking(ctx, g.setunit(1), (8,), jnp.float32)  # warm cache

    real = ctx.engine.lock
    proxy = _CountingLock(real)
    ctx.engine.lock = proxy
    try:
        probes0 = ctx._shm_probe_count
        for _ in range(10):
            before = proxy.toplevel
            v = rt.dart_get_blocking(ctx, g.setunit(1), (8,), jnp.float32)
            assert proxy.toplevel - before == 1
            np.testing.assert_array_equal(np.asarray(v),
                                          np.arange(8, dtype=np.float32))
        assert ctx._shm_probe_count - probes0 == 0
    finally:
        ctx.engine.lock = real


# ----------------------------------- shm put: routing + byte identity -----

def test_shm_put_zero_dispatch_roundtrip(ctx):
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 256)
    d0, p0 = ctx.engine.dispatch_count, ctx.engine.shm_puts
    dart_put_blocking(ctx, g.setunit(3), jnp.arange(16, dtype=jnp.int32))
    assert ctx.engine.dispatch_count == d0      # zero jitted dispatches
    assert ctx.engine.shm_puts == p0 + 1
    got = rt.dart_get_blocking(ctx, g.setunit(3), (16,), jnp.int32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.arange(16, dtype=np.int32))


def test_shm_put_strided(ctx):
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 512)
    payload = jnp.arange(16, dtype=jnp.float32)      # 4 segs × 16 B
    dart_put_blocking(ctx, g.setunit(0), payload, stride=64, count=4)
    for i in range(4):
        seg = rt.dart_get_blocking(ctx, g.setunit(0).incaddr(64 * i),
                                   (4,), jnp.float32)
        np.testing.assert_array_equal(np.asarray(seg),
                                      np.arange(4 * i, 4 * i + 4,
                                                dtype=np.float32))


def test_shm_put_ordered_after_queued_ops(ctx):
    """Program order vs queued epochs: a queued engine put to the same
    lane lands BEFORE the shm put (ordering flush), and a queued get
    dispatched before the shm put reads the PRE-put bytes (read
    fence)."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    t = g.setunit(2)
    dart_put(ctx, t, jnp.full((4,), 1.0, jnp.float32))   # queued
    h = dart_get_nb(ctx, t, (4,), jnp.float32)           # queued after
    ctx.engine.flush(h.poolid, h.row)                    # get dispatched
    dart_put_blocking(ctx, t, jnp.full((4,), 2.0, jnp.float32))  # shm
    # the get was ordered before the shm write: it sees the 1.0 epoch
    np.testing.assert_array_equal(np.asarray(h.value()),
                                  np.full(4, 1.0, np.float32))
    got = rt.dart_get_blocking(ctx, t, (4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full(4, 2.0, np.float32))


def test_put_nb_stays_on_engine(ctx):
    """Non-blocking puts never shm-route — their contract is queued
    coalescing (1 batched dispatch per epoch close)."""
    _require_shm(ctx)
    ga = ctx.alloc((4,), jnp.float32)
    p0 = ctx.engine.shm_puts
    with ctx.epoch():
        for u in ga.units:
            ga[u].put_nb(jnp.full((4,), float(u)))
    assert ctx.engine.shm_puts == p0
    np.testing.assert_array_equal(np.asarray(ga.gather())[:, 0],
                                  [0.0, 1.0, 2.0, 3.0])


def test_shm_put_byte_identity_differential(ctx):
    """The acceptance differential: random interleavings of blocking
    puts / queued puts / queued gets on a default-shm array vs the
    identical program on a shm=False oracle (pure engine path), with
    the ProgressPlane daemon live on the subject.  Final heap bytes
    and every get's bytes must be identical."""
    _require_shm(ctx)
    oracle = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=POOL_BYTES, team_pool_bytes=POOL_BYTES))
    oracle.engine.impl = ctx.engine.impl
    try:
        ga_s = ctx.alloc((8,), jnp.float32)              # shm-routed
        ga_o = oracle.alloc((8,), jnp.float32, shm=False)
        ctx.start_progress(watermark_ops=2, idle_s=0.001)

        rng = np.random.default_rng(1234)
        pending = []
        for _ in range(60):
            u = int(rng.integers(0, 4))
            op = rng.choice(["put", "put_nb", "get"])
            if op == "put":
                val = rng.random(8, dtype=np.float32)
                ga_s[u].put(val)
                ga_o[u].put(val)
            elif op == "put_nb":
                val = rng.random(8, dtype=np.float32)
                pending.append((ga_s[u].put_nb(val),
                                ga_o[u].put_nb(val)))
            else:
                np.testing.assert_array_equal(np.asarray(ga_s[u].get()),
                                              np.asarray(ga_o[u].get()))
        for hs, ho in pending:
            hs.wait()
            ho.wait()
        np.testing.assert_array_equal(np.asarray(ga_s.gather()),
                                      np.asarray(ga_o.gather()))
        assert ctx.engine.shm_puts > 0          # the route was exercised
        assert oracle.engine.shm_puts == 0      # ... and only on subject
    finally:
        dart_exit(oracle)


# ----------------------------------------- chaos: fault-plane parity ------

@pytest.mark.chaos
def test_shm_put_rejected_on_poisoned_lane(ctx):
    """Enqueue-boundary parity: a poisoned lane rejects the shm write
    with the same typed error as an engine enqueue — and the bytes
    must NOT land."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    t = g.setunit(1)
    dart_put_blocking(ctx, t, jnp.full((4,), 7.0, jnp.float32))
    poolid, row, _ = _lane_of(ctx, t)
    plane = ctx.attach_faults(seed=0)
    plane.schedule(kind="poison", poolid=poolid, row=row, after=0)
    with pytest.raises(DartError, match="poisoned"):
        dart_put_blocking(ctx, t, jnp.full((4,), 9.0, jnp.float32))
    assert ctx.engine.clear_lane(poolid, row) is not None
    ctx.engine.attach_faults(None)
    got = rt.dart_get_blocking(ctx, t, (4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full(4, 7.0, np.float32))


@pytest.mark.chaos
def test_shm_put_fail_fast_on_dead_unit(ctx):
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    ctx.engine.mark_unit_dead(2, reason="test death")
    with pytest.raises(UnitFailedError) as ei:
        dart_put_blocking(ctx, g.setunit(2), jnp.zeros((4,), jnp.float32))
    assert ei.value.unit == 2
    # survivors unaffected
    dart_put_blocking(ctx, g.setunit(1), jnp.ones((4,), jnp.float32))
    assert ctx.engine.shm_puts >= 1


@pytest.mark.chaos
def test_shm_put_blocked_by_lane_failed_during_ordering_flush(ctx):
    """A queued op that exhausts retries during the shm put's own
    ordering flush fails the lane — the host write is ordered AFTER
    the hole and must not apply."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    t = g.setunit(1)
    dart_put_blocking(ctx, t, jnp.full((4,), 5.0, jnp.float32))
    poolid, row, _ = _lane_of(ctx, t)
    plane = ctx.attach_faults(seed=0)
    plane.schedule(kind="fail", poolid=poolid, row=row, times=0)
    dart_put(ctx, t, jnp.full((4,), 6.0, jnp.float32))   # queued, doomed
    with pytest.raises(DartError):
        dart_put_blocking(ctx, t, jnp.full((4,), 8.0, jnp.float32))
    assert ctx.engine.clear_lane(poolid, row) is not None
    ctx.engine.attach_faults(None)
    got = rt.dart_get_blocking(ctx, t, (4,), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.full(4, 5.0, np.float32))   # 8 never landed


# --------------------------------------- shm-direct collectives -----------

def test_shm_collectives_zero_dispatch_equivalence(ctx):
    """bcast/gather/scatter (+typed) on a default-shm array are served
    shm-direct — ZERO jitted dispatches — and byte-identical to the
    engine collectives on a shm=False oracle."""
    _require_shm(ctx)
    oracle = dart_init(n_units=4, config=DartConfig(
        non_collective_pool_bytes=POOL_BYTES, team_pool_bytes=POOL_BYTES))
    oracle.engine.impl = ctx.engine.impl
    try:
        for dtype in (jnp.float32, jnp.int32, jnp.bfloat16):
            ga_s = ctx.alloc((4,), dtype)
            ga_o = oracle.alloc((4,), dtype, shm=False)
            vals = (jnp.arange(16).reshape(4, 4) + 1).astype(dtype)

            ga_s.scatter(vals)
            ga_o.scatter(vals)
            d0, c0 = ctx.engine.dispatch_count, ctx.engine.shm_collective_ops
            got_s = ga_s.gather()
            assert ctx.engine.dispatch_count == d0     # shm-direct gather
            np.testing.assert_array_equal(np.asarray(got_s),
                                          np.asarray(ga_o.gather()))

            ga_s.broadcast(1).wait()
            ga_o.broadcast(1).wait()
            assert ctx.engine.dispatch_count == d0     # shm-direct bcast
            assert ctx.engine.shm_collective_ops > c0
            np.testing.assert_array_equal(np.asarray(ga_s.gather()),
                                          np.asarray(ga_o.gather()))
    finally:
        dart_exit(oracle)


def test_shm_byte_collectives_equivalence(ctx):
    """The raw byte-plane dart_gather/dart_scatter also route."""
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    vals = np.arange(4 * 16, dtype=np.uint8).reshape(4, 16)
    d0 = ctx.engine.dispatch_count
    rt.dart_scatter(ctx, g, vals).wait()
    out, h = rt.dart_gather(ctx, g, 16)
    h.wait()
    assert ctx.engine.dispatch_count == d0
    np.testing.assert_array_equal(np.asarray(out), vals)


def test_shm_collectives_ordered_after_queued_puts(ctx):
    """Epoch ordering parity with the engine collectives: queued
    one-sided puts land before the shm-direct collective reads."""
    _require_shm(ctx)
    ga = ctx.alloc((2,), jnp.float32)
    for u in ga.units:
        ga[u].put_nb(jnp.full((2,), float(u)))          # all queued
    gat = np.asarray(ga.gather())                       # shm-direct
    np.testing.assert_array_equal(gat[:, 0], [0.0, 1.0, 2.0, 3.0])


def test_shm_collective_fallback_on_non_writable_pool(ctx):
    """A pool whose arena is not host-writable falls back to the
    engine collective (per-pool fallback) instead of failing."""
    _require_shm(ctx)
    ga = ctx.alloc((4,), jnp.float32)
    poolid, _, _ = _lane_of(ctx, ga.gptr)
    ga[0].put(jnp.ones((4,), jnp.float32))              # settle pool
    # force the cached probe to "readable but not writable"
    ctx._shm_cache[poolid] = (True, False)
    try:
        d0 = ctx.engine.dispatch_count
        ga.broadcast(0).wait()
        assert ctx.engine.dispatch_count > d0           # engine path
    finally:
        invalidate_shm_cache(ctx, poolid)
    np.testing.assert_array_equal(np.asarray(ga.gather()),
                                  np.ones((4, 4), np.float32))


# --------------------------------------------------- live windows ---------

def test_view_is_live_window_across_shm_puts(ctx):
    _require_shm(ctx)
    g = dart_team_memalloc_shared(ctx, DART_TEAM_ALL, 64)
    dart_put_blocking(ctx, g, jnp.zeros((4,), jnp.float32))
    view = dart_shm_view(ctx, g, (4,), jnp.float32)
    assert not view.flags.writeable
    dart_put_blocking(ctx, g, jnp.full((4,), 3.0, jnp.float32))
    np.testing.assert_array_equal(np.asarray(view),
                                  np.full(4, 3.0, np.float32))


def test_shm_put_threaded_with_progress_daemon(ctx):
    """Thread-safety: concurrent shm puts + queued engine traffic +
    the background drain loop; every unit's block must end at one of
    the two writers' final values with no torn bytes."""
    _require_shm(ctx)
    ctx.start_progress(watermark_ops=2, idle_s=0.001)
    ga = ctx.alloc((16,), jnp.int32)
    stop = threading.Event()
    errors = []

    def writer(base):
        try:
            i = 0
            while not stop.is_set():
                u = i % 4
                ga[u].put(jnp.full((16,), base + i, jnp.int32))
                ga[u].put_nb(jnp.full((16,), base + i, jnp.int32))
                i += 1
        except Exception as e:    # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(b,))
               for b in (1_000, 2_000_000)]
    for t in threads:
        t.start()
    import time
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert not errors
    rows = np.asarray(ga.gather())
    for r in rows:
        assert len(set(r.tolist())) == 1    # no torn block
