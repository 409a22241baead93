"""Differential property suite for the reduction plane (queued
``dart_accumulate`` / ``dart_get_accumulate`` + the op-identity-padded
allreduce/reduce).

The core oracle is a **naive blocking reference**: a host numpy arena
to which every op applies immediately and strictly sequentially.
Random interleaved sequences of put / accumulate / get_accumulate /
get / per-target flush / waitall run on the coalesced engine and must
leave the device arena **byte-identical** to the oracle — including
overlapping accumulates (commutative, so they may share a vectorized
dispatch), mixed-op splits, accumulate-vs-put splits, pool-end
headroom, and ``impl='pallas'`` vs ``'ref'``.

Numeric exactness: payload values are small integers (also when stored
as floats), so every intermediate sum/product is exactly representable
and the commutative reassociation inside a vectorized run is bitwise
equal to the sequential order.
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (DART_TEAM_ALL, DartConfig, dart_accumulate,
                        dart_accumulate_blocking, dart_allreduce,
                        dart_exit, dart_flush, dart_get_accumulate,
                        dart_get_blocking, dart_init, dart_memalloc,
                        dart_put, dart_put_blocking, dart_reduce,
                        dart_team_memalloc_aligned, dart_waitall)
from repro.core import onesided as _os
from repro.core import runtime as rt
from repro.kernels import segmented_copy as sc

POOL = 2048
N_UNITS = 4

OPS = ("sum", "prod", "min", "max")
#: (op, dtype) of the differential loop: each of OPS on int32 and
#: float32, and bxor, which takes int32 and uint32 only
OP_DTYPES = [(op, dt) for op in OPS for dt in ("int32", "float32")] + [
    ("bxor", "int32"), ("bxor", "uint32")]


def _mk_ctx(impl="ref", pool=POOL):
    c = dart_init(n_units=N_UNITS, config=DartConfig(
        non_collective_pool_bytes=pool, team_pool_bytes=pool))
    c.engine.impl = impl
    return c


@pytest.fixture()
def ctx(engine_impl):
    c = _mk_ctx(engine_impl)
    yield c
    dart_exit(c)


class Oracle:
    """The blocking reference: a host arena, ops applied in program
    order, one at a time."""

    def __init__(self, rows: int, pool: int):
        self.arena = np.zeros((rows, pool), np.uint8)

    def put(self, row, off, payload):
        self.arena[row, off:off + payload.size] = payload

    def get(self, row, off, nbytes):
        return self.arena[row, off:off + nbytes].copy()

    def accumulate(self, row, off, vals, op):
        dt = vals.dtype
        n = vals.size * dt.itemsize
        cur = self.arena[row, off:off + n].copy().view(dt)
        if op == "sum":
            new = cur + vals
        elif op == "prod":
            new = cur * vals
        elif op == "min":
            new = np.minimum(cur, vals)
        elif op == "bxor":
            new = cur ^ vals
        else:
            new = np.maximum(cur, vals)
        self.arena[row, off:off + n] = new.astype(dt).view(np.uint8)

    def get_accumulate(self, row, off, vals, op):
        old = self.get(row, off, vals.size * vals.dtype.itemsize)
        self.accumulate(row, off, vals, op)
        return old


def _rand_vals(rng, dtype, n):
    """Small-integer payloads: sums/products stay exactly representable
    so commutative reassociation is bitwise-equal to sequential."""
    return np.asarray([rng.randint(1, 3) for _ in range(n)], dtype)


def _device_arena(ctx):
    return np.asarray(ctx.state[_os.WORLD_POOLID])


# ------------------------------------------- the differential loop --------

@pytest.mark.parametrize("op,dtype", OP_DTYPES)
def test_differential_sequences_vs_blocking_oracle(op, dtype, engine_impl):
    """≥ 200 generated op sequences per op class (100 here × 2 engine
    impls): random interleavings of accumulate (dominant), put,
    get_accumulate, per-target flush, and waitall, checked
    byte-identical against the sequential oracle after every
    sequence."""
    dt = np.dtype(dtype)
    ctx = _mk_ctx(engine_impl)
    oracle = Oracle(N_UNITS, POOL)
    g = dart_memalloc(ctx, POOL, unit=0)
    # string seed: deterministic across processes (str.__hash__ is not)
    rng = random.Random(f"{op}/{dtype}/{engine_impl}")
    try:
        for _ in range(100):
            handles = []
            for _ in range(rng.randint(2, 8)):
                row = rng.randrange(N_UNITS)
                n = rng.randint(1, 12)
                max_e = POOL // dt.itemsize - n
                # bias some ops hard against the pool end (headroom:
                # the padded seg window crosses the pool boundary,
                # which also exercises the pallas→ref fallback)
                e_off = max_e if rng.random() < 0.15 else \
                    rng.randint(0, max_e)
                off = e_off * dt.itemsize
                vals = _rand_vals(rng, dt, n)
                kind = rng.choices(["acc", "put", "gacc", "flush_t"],
                                   weights=[6, 2, 1, 1])[0]
                if kind == "acc":
                    handles.append(dart_accumulate(
                        ctx, g.setunit(row) + off, vals, op))
                    oracle.accumulate(row, off, vals, op)
                elif kind == "put":
                    handles.append(dart_put(
                        ctx, g.setunit(row) + off, vals))
                    oracle.put(row, off,
                               vals.view(np.uint8).reshape(-1))
                elif kind == "gacc":
                    old, h = dart_get_accumulate(
                        ctx, g.setunit(row) + off, vals, op)
                    expect = oracle.get_accumulate(row, off, vals, op)
                    assert np.asarray(old).tobytes() == expect.tobytes()
                    handles.append(h)
                else:
                    dart_flush(ctx, g, target=row)
            if rng.random() < 0.5:
                dart_waitall(handles)
            else:
                dart_flush(ctx)
            np.testing.assert_array_equal(_device_arena(ctx),
                                          oracle.arena)
    finally:
        dart_exit(ctx)


@given(st.lists(st.tuples(st.sampled_from(["acc", "put", "get"]),
                          st.sampled_from(OPS),
                          st.integers(0, N_UNITS - 1),   # row
                          st.integers(0, POOL // 4 - 8), # element offset
                          st.integers(1, 8)),            # elements
                min_size=1, max_size=10),
       st.booleans())
@settings(max_examples=25, deadline=None)
def test_interleaved_ops_byte_identical(op_specs, use_pallas):
    """Property (hypothesis): any
    interleaving of mixed-op accumulates, puts, and reads matches the
    sequential oracle — mixed-op overlap splits runs, reads flush
    their lane first."""
    ctx = _mk_ctx("pallas" if use_pallas else "ref")
    oracle = Oracle(N_UNITS, POOL)
    g = dart_memalloc(ctx, POOL, unit=0)
    try:
        for i, (kind, op, row, e_off, n) in enumerate(op_specs):
            off = e_off * 4
            vals = (np.arange(n, dtype=np.int32) % 3) + 1 + (i % 2)
            ptr = g.setunit(row) + off
            if kind == "acc":
                dart_accumulate(ctx, ptr, vals, op)
                oracle.accumulate(row, off, vals, op)
            elif kind == "put":
                dart_put(ctx, ptr, vals)
                oracle.put(row, off, vals.view(np.uint8).reshape(-1))
            else:
                got = np.asarray(dart_get_blocking(
                    ctx, ptr, (n,), jnp.int32))
                expect = oracle.get(row, off, n * 4).view(np.int32)
                np.testing.assert_array_equal(got, expect)
        dart_flush(ctx)
        np.testing.assert_array_equal(_device_arena(ctx), oracle.arena)
    finally:
        dart_exit(ctx)


# --------------------------------------------- coalescing + run splits ----

def test_same_op_accumulates_one_dispatch(ctx):
    """Acceptance criterion: N same-op accumulates to one pool flush
    as ONE counted dispatch — even with overlapping ranges."""
    g = dart_memalloc(ctx, 1024, unit=0)
    d0 = ctx.engine.dispatch_count
    hs = [dart_accumulate(ctx, g + 8 * (i % 3),
                          jnp.full((4,), 1, jnp.int32))
          for i in range(8)]
    dart_flush(ctx)
    assert ctx.engine.dispatch_count - d0 == 1
    dart_waitall(hs)
    out = np.asarray(dart_get_blocking(ctx, g, (10,), jnp.int32))
    # 8 ops striped over offsets 0/8/16: elem 0,1 get ops@0 (3); elem
    # 2,3 get ops@0+ops@8 (3+3); elem 4,5 ops@8+@16 (3+2); elem 6,7 @16
    np.testing.assert_array_equal(out, [3, 3, 6, 6, 5, 5, 2, 2, 0, 0])


def test_mixed_op_overlap_splits_runs(ctx):
    g = dart_memalloc(ctx, 512, unit=1)
    dart_put_blocking(ctx, g, jnp.full((4,), 2, jnp.int32))
    d0 = ctx.engine.dispatch_count
    dart_accumulate(ctx, g, jnp.full((4,), 3, jnp.int32), "sum")
    dart_accumulate(ctx, g, jnp.full((4,), 4, jnp.int32), "prod")
    dart_accumulate(ctx, g, jnp.full((4,), 10, jnp.int32), "min")
    dart_flush(ctx)
    assert ctx.engine.dispatch_count - d0 == 3   # one per op class
    out = np.asarray(dart_get_blocking(ctx, g, (4,), jnp.int32))
    np.testing.assert_array_equal(out, [10, 10, 10, 10])  # min(20, 10)


def test_accumulate_vs_put_overlap_splits(ctx):
    """put → acc → put on one cell must resolve exactly sequentially
    (the accumulate reads the first put's value, the last put wins)."""
    g = dart_memalloc(ctx, 256, unit=2)
    dart_put(ctx, g, jnp.full((4,), 5, jnp.int32))
    dart_accumulate(ctx, g, jnp.full((4,), 1, jnp.int32), "sum")
    dart_put(ctx, g + 8, jnp.full((2,), 9, jnp.int32))
    dart_flush(ctx)
    out = np.asarray(dart_get_blocking(ctx, g, (4,), jnp.int32))
    np.testing.assert_array_equal(out, [6, 6, 9, 9])


def test_mixed_dtype_accumulates_split(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    d0 = ctx.engine.dispatch_count
    dart_accumulate(ctx, g, jnp.full((2,), 1, jnp.int32), "sum")
    dart_accumulate(ctx, g + 64, jnp.full((2,), 1.5, jnp.float32), "sum")
    dart_flush(ctx)
    assert ctx.engine.dispatch_count - d0 == 2
    assert list(np.asarray(dart_get_blocking(
        ctx, g, (2,), jnp.int32))) == [1, 1]
    assert list(np.asarray(dart_get_blocking(
        ctx, g + 64, (2,), jnp.float32))) == [1.5, 1.5]


def test_get_accumulate_overlap_splits_and_orders(ctx):
    """Two overlapping fetch-accumulates must each see the sequential
    pre-value (the second observes the first's effect)."""
    g = dart_memalloc(ctx, 256, unit=3)
    dart_put_blocking(ctx, g, jnp.full((4,), 10, jnp.int32))
    h1 = ctx.engine.get_accumulate(ctx.heap, ctx.teams_by_slot, g,
                                   np.full((4,), 1, np.int32), "sum")
    h2 = ctx.engine.get_accumulate(ctx.heap, ctx.teams_by_slot, g,
                                   np.full((4,), 2, np.int32), "sum")
    d0 = ctx.engine.dispatch_count
    dart_flush(ctx)
    assert ctx.engine.dispatch_count - d0 == 2     # overlap split
    assert list(np.asarray(h1.value())) == [10] * 4
    assert list(np.asarray(h2.value())) == [11] * 4
    out = np.asarray(dart_get_blocking(ctx, g, (4,), jnp.int32))
    np.testing.assert_array_equal(out, [13] * 4)


def test_disjoint_get_accumulates_share_one_dispatch(ctx):
    g = dart_memalloc(ctx, 512, unit=0)
    for i in range(4):
        dart_put_blocking(ctx, g + 32 * i,
                          jnp.full((4,), i + 1, jnp.int32))
    hs = [ctx.engine.get_accumulate(
            ctx.heap, ctx.teams_by_slot, g + 32 * i,
            np.full((4,), 10, np.int32), "sum") for i in range(4)]
    d0 = ctx.engine.dispatch_count
    dart_flush(ctx)
    assert ctx.engine.dispatch_count - d0 == 1
    for i, h in enumerate(hs):
        assert list(np.asarray(h.value())) == [i + 1] * 4
        assert list(np.asarray(dart_get_blocking(
            ctx, g + 32 * i, (4,), jnp.int32))) == [i + 11] * 4


def test_accumulate_pool_end_headroom(ctx):
    """An accumulate hard against the pool end: the padded seg window
    would cross the boundary (pallas falls back to ref), bytes outside
    the op's exact range stay untouched."""
    pool = ctx.config.non_collective_pool_bytes
    g = dart_memalloc(ctx, pool, unit=1)
    sentinel = jnp.full((4,), 0xCD, jnp.uint8)
    dart_put_blocking(ctx, g + pool - 16, sentinel)
    dart_accumulate_blocking(ctx, g + pool - 12,
                             jnp.full((3,), 7, jnp.int32), "sum")
    tail = np.asarray(dart_get_blocking(ctx, g + pool - 16, (4,),
                                        jnp.uint8))
    np.testing.assert_array_equal(tail, [0xCD] * 4)
    out = np.asarray(dart_get_blocking(ctx, g + pool - 12, (3,),
                                       jnp.int32))
    np.testing.assert_array_equal(out, [7, 7, 7])


# ------------------------------------------------ initiation checks -------

def test_unknown_op_rejected_at_initiation(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    with pytest.raises(ValueError):
        dart_accumulate(ctx, g, jnp.ones((2,), jnp.int32), "xor")
    assert ctx.engine.pending_ops() == 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int16"])
def test_bxor_refused_at_initiation_off_int32_and_uint32(ctx, dtype):
    """MPI defines BXOR for integers only, and the reduction plane takes
    it on int32 and uint32: any other dtype is a TypeError when the op
    is issued, through the raw call and the typed front end alike, and
    nothing is queued."""
    g = dart_memalloc(ctx, 256, unit=0)
    with pytest.raises(TypeError, match="bxor"):
        dart_accumulate(ctx, g, jnp.ones((2,), dtype), "bxor")
    ga = ctx.alloc((4,), jnp.dtype(dtype), shm=False)
    with pytest.raises(TypeError, match="bxor"):
        ga.at[1, 0:2].accumulate(jnp.ones((2,), dtype), "bxor")
    assert ctx.engine.pending_ops() == 0


def test_misaligned_accumulate_rejected(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    with pytest.raises(ValueError):
        dart_accumulate(ctx, g + 2, jnp.ones((2,), jnp.int32))
    assert ctx.engine.pending_ops() == 0


def test_accumulate_bounds_checked_at_initiation(ctx):
    pool = ctx.config.non_collective_pool_bytes
    g = dart_memalloc(ctx, 128, unit=0)
    with pytest.raises(ValueError):
        dart_accumulate(ctx, g + (pool - 4 - g.addr),
                        jnp.zeros(4, jnp.int32))
    assert ctx.engine.pending_ops() == 0


# --------------------------------------- allreduce / reduce correctness ---

def test_allreduce_identity_padding_all_ops(ctx):
    """min/max/prod need true identities (±inf / 1) in the padded
    lanes — negative values and non-pow2 element counts exercise it."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
    vals = {0: [-5, 2, 7], 1: [4, -9, 1], 2: [0, 3, -2], 3: [8, 8, 8]}
    expect = {"sum": [7, 4, 14], "prod": [0, -432, -112],
              "min": [-5, -9, -2], "max": [8, 8, 8]}
    for op in OPS:
        for u, v in vals.items():
            dart_put_blocking(ctx, g.setunit(u),
                              jnp.asarray(v, jnp.float32))
        red = np.asarray(dart_allreduce(ctx, g, (3,), jnp.float32, op))
        np.testing.assert_array_equal(red, expect[op])
        for u in range(N_UNITS):
            got = np.asarray(dart_get_blocking(
                ctx, g.setunit(u), (3,), jnp.float32))
            np.testing.assert_array_equal(got, expect[op])


def test_reduce_lands_on_root_only(ctx):
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
    for u in range(N_UNITS):
        dart_put_blocking(ctx, g.setunit(u),
                          jnp.full((5,), u + 1, jnp.int32))
    red = np.asarray(dart_reduce(ctx, g, (5,), jnp.int32, "sum", root=2))
    np.testing.assert_array_equal(red, [10] * 5)
    for u in range(N_UNITS):
        got = np.asarray(dart_get_blocking(ctx, g.setunit(u), (5,),
                                           jnp.int32))
        np.testing.assert_array_equal(got,
                                      [10 if u == 2 else u + 1] * 5)


def test_allreduce_does_not_touch_adjacent_bytes(ctx):
    """The padded reduce write-back is masked to the true byte length:
    a sentinel right after the reduced segment must survive."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
    for u in range(N_UNITS):
        dart_put_blocking(ctx, g.setunit(u), jnp.full((3,), u, jnp.int32))
        dart_put_blocking(ctx, g.setunit(u) + 12,
                          jnp.full((4,), 0xEE, jnp.uint8))
    dart_allreduce(ctx, g, (3,), jnp.int32, "sum")
    for u in range(N_UNITS):
        tail = np.asarray(dart_get_blocking(ctx, g.setunit(u) + 12,
                                            (4,), jnp.uint8))
        np.testing.assert_array_equal(tail, [0xEE] * 4)


def test_allreduce_sees_queued_puts(ctx):
    """Collectives close the pool's epoch first: queued puts are
    ordered before the reduction."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    for u in range(N_UNITS):
        dart_put(ctx, g.setunit(u), jnp.full((2,), u + 1, jnp.float32))
    red = np.asarray(dart_allreduce(ctx, g, (2,), jnp.float32, "sum"))
    np.testing.assert_array_equal(red, [10.0, 10.0])


@pytest.mark.parametrize("dtype", ["int32", "uint32"])
@pytest.mark.parametrize("root", [None, 1])
def test_bxor_allreduce_and_reduce_match_numpy(ctx, dtype, root):
    """``bxor`` reduces across rows as numpy's ``^`` does, bit for bit,
    over a non-pow2 element count (the padded lanes read 0)."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
    rng = np.random.default_rng(5)
    vals = rng.integers(np.iinfo(dtype).min, np.iinfo(dtype).max,
                        size=(N_UNITS, 5), dtype=dtype, endpoint=True)
    for u in range(N_UNITS):
        dart_put_blocking(ctx, g.setunit(u), jnp.asarray(vals[u]))
    expect = np.bitwise_xor.reduce(vals, axis=0)
    if root is None:
        red = dart_allreduce(ctx, g, (5,), dtype, "bxor")
    else:
        red = dart_reduce(ctx, g, (5,), dtype, "bxor", root=root)
    np.testing.assert_array_equal(np.asarray(red), expect)
    for u in range(N_UNITS):
        got = np.asarray(dart_get_blocking(ctx, g.setunit(u), (5,),
                                           dtype))
        want = expect if root in (None, u) else vals[u]
        np.testing.assert_array_equal(got, want)


def test_bxor_allreduce_refuses_a_float_before_flushing(ctx):
    """The op/dtype check comes before the collective's flush: a
    refused allreduce leaves the queue and the dispatch count as they
    were."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 128)
    dart_put(ctx, g.setunit(0), jnp.ones((2,), jnp.float32))
    d0 = ctx.engine.dispatch_count
    with pytest.raises(TypeError):
        dart_allreduce(ctx, g, (2,), jnp.float32, "bxor")
    with pytest.raises(ValueError):
        dart_allreduce(ctx, g, (2,), jnp.float32, "land")
    assert ctx.engine.dispatch_count == d0
    assert ctx.engine.pending_ops() == 1


def test_scalar_allreduce(ctx):
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 64)
    for u in range(N_UNITS):
        dart_put_blocking(ctx, g.setunit(u),
                          jnp.asarray(float(u + 1), jnp.float32))
    red = dart_allreduce(ctx, g, (), jnp.float32, "max")
    assert np.asarray(red).shape == ()
    assert float(np.asarray(red)) == 4.0


# -------------------------------------------- zero-recompile regression ---

def test_allreduce_zero_recompiles_steady_state(ctx):
    """The assertable form of the closed ROADMAP item: a steady-state
    loop over varying (shape, dtype, op) allreduces performs ZERO plan
    compiles after warmup — the op-identity padding buckets the
    element count, so the exact shape never keys a kernel."""
    g = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 512)
    combos = [((5,), jnp.float32, "sum"), ((7,), jnp.float32, "min"),
              ((6,), jnp.int32, "sum"), ((8,), jnp.int32, "max"),
              ((2, 3), jnp.float32, "prod")]
    for shape, dt, op in combos:                  # warm every bucket
        dart_allreduce(ctx, g, shape, dt, op)
    c0 = ctx.engine.compile_count
    for shape, dt, op in [((6,), jnp.float32, "sum"),
                          ((8,), jnp.float32, "min"),
                          ((5,), jnp.int32, "sum"),
                          ((7,), jnp.int32, "max"),
                          ((3, 2), jnp.float32, "prod"),
                          ((8,), jnp.float32, "sum")]:
        red = dart_allreduce(ctx, g, shape, dt, op)
        assert np.asarray(red).shape == shape
    assert ctx.engine.compile_count == c0, \
        "varying-shape allreduce recompiled in steady state"
    assert ctx.engine.plan_cache_hits > 0


def test_accumulate_zero_recompiles_steady_state(ctx):
    g = dart_memalloc(ctx, 2048, unit=0)

    def epoch(k, n):
        hs = [dart_accumulate(ctx, g + 64 * i,
                              jnp.full((n,), 1, jnp.int32))
              for i in range(k)]
        dart_flush(ctx)
        dart_waitall(hs)

    epoch(8, 16)                                  # warm (8, 64B) bucket
    epoch(4, 16)                                  # and (4, 64B)
    c0 = ctx.engine.compile_count
    for k, n in [(5, 16), (7, 9), (8, 12), (6, 10), (4, 16), (8, 13)]:
        epoch(k, n)
    assert ctx.engine.compile_count == c0, \
        "varying-size accumulate epochs recompiled in steady state"


@pytest.mark.parametrize("placement", ["disjoint", "overlapping",
                                       "duplicate"])
@pytest.mark.parametrize("dtype", ["int32", "uint32"])
def test_bxor_matches_numpy_in_one_dispatch_per_epoch(ctx, dtype,
                                                      placement):
    """``accumulate(v, "bxor")`` on full-width words equals numpy's
    ``^`` bit for bit: on disjoint ranges, on overlapping ones (the
    ordered path: the window loop in queue order, or the Pallas grid)
    and on one 64-bit word hit many times in an epoch.  Every epoch is one dispatch, and once warm a loop of
    epochs compiles nothing."""
    dt = np.dtype(dtype)
    n_elems = 256
    ga = ctx.alloc((n_elems,), jnp.dtype(dt), shm=False)
    rng = np.random.default_rng(sum(map(ord, dtype + placement)))
    want = np.zeros((N_UNITS, n_elems), dt)

    def ranges():
        if placement == "disjoint":
            slots = rng.choice(N_UNITS * n_elems // 4, 16, replace=False)
            return [(int(s) // (n_elems // 4), int(s) % (n_elems // 4) * 4,
                     int(rng.integers(1, 5))) for s in slots]
        if placement == "overlapping":
            return [(int(rng.integers(2)), int(rng.integers(0, 8)),
                     int(rng.integers(1, 5))) for _ in range(16)]
        words = [(int(rng.integers(N_UNITS)),
                  2 * int(rng.integers(n_elems // 2))) for _ in range(3)]
        return [words[i % 3] + (2,) for i in range(16)]

    def epoch():
        hs = []
        for u, lo, n in ranges():
            v = rng.integers(0, 2**32, n, dtype=np.uint64).astype(
                np.uint32).view(dt)
            hs.append(ga.at[u, lo:lo + n].accumulate(v, "bxor"))
            want[u, lo:lo + n] ^= v
        d0 = ctx.engine.dispatch_count
        ga.flush()
        assert ctx.engine.dispatch_count - d0 == 1
        dart_waitall(hs)

    epoch()
    c0 = ctx.engine.compile_count
    for _ in range(4):
        epoch()
    assert ctx.engine.compile_count == c0
    got = np.stack([np.asarray(ga.at[u, 0:n_elems].get())
                    for u in range(N_UNITS)])
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


# ---------------------------------------------------- typed front-end -----

def test_typed_accumulate_coalesces_in_epoch(ctx):
    ga = ctx.alloc((8,), jnp.int32)
    ga.scatter(np.zeros((N_UNITS, 8), np.int32))
    d0 = ctx.engine.dispatch_count
    with ga.epoch():
        for u in range(N_UNITS):
            ga.at[u, 2:6].add(jnp.full((4,), u + 1, jnp.int32))
    assert ctx.engine.dispatch_count - d0 == 1
    for u in range(N_UNITS):
        got = np.asarray(ga[u].get())
        np.testing.assert_array_equal(
            got, [0, 0] + [u + 1] * 4 + [0, 0])


def test_typed_accumulate_ops_and_get_accumulate(ctx):
    ga = ctx.alloc((4,), jnp.float32)
    ga.scatter(np.tile(np.asarray([2., 4., 6., 8.], np.float32),
                       (N_UNITS, 1)))
    ga.at[1, :].mul(jnp.full((4,), 2.0, jnp.float32)).wait()
    np.testing.assert_array_equal(np.asarray(ga[1].get()),
                                  [4., 8., 12., 16.])
    ga.at[1, 1:3].min(jnp.full((2,), 5.0, jnp.float32)).wait()
    np.testing.assert_array_equal(np.asarray(ga[1].get()),
                                  [4., 5., 5., 16.])
    old = ga.at[1, 0:2].get_accumulate(
        jnp.full((2,), 100.0, jnp.float32), "max")
    np.testing.assert_array_equal(np.asarray(old), [4., 5.])
    np.testing.assert_array_equal(np.asarray(ga[1].get()),
                                  [100., 100., 5., 16.])
    h = ga.accumulate(2, slice(0, 2), jnp.full((2,), 1.0, jnp.float32))
    h.wait()
    np.testing.assert_array_equal(np.asarray(ga[2].get()),
                                  [3., 5., 6., 8.])


def test_typed_reduce_and_allreduce(ctx):
    ga = ctx.alloc((3,), jnp.int32)
    ga.scatter(np.arange(N_UNITS * 3, dtype=np.int32).reshape(
        N_UNITS, 3))
    red = np.asarray(ga.reduce("max", root=1))
    np.testing.assert_array_equal(red, [9, 10, 11])
    np.testing.assert_array_equal(np.asarray(ga[1].get()), [9, 10, 11])
    np.testing.assert_array_equal(np.asarray(ga[0].get()), [0, 1, 2])


# ------------------------------------------------- lifecycle / teardown ---

def test_queued_accumulate_dropped_by_destroy_fails_handle(ctx):
    from repro.core import dart_team_create, dart_team_destroy
    from repro.core.group import DartGroup
    tid = dart_team_create(ctx, DART_TEAM_ALL, DartGroup((0, 1)))
    gt = dart_team_memalloc_aligned(ctx, tid, 128)
    h = dart_accumulate(ctx, gt, jnp.ones((2,), jnp.int32))
    dart_team_destroy(ctx, tid)
    with pytest.raises(RuntimeError, match="window destroyed"):
        h.wait()


def test_accumulate_handle_state_machine(ctx):
    g = dart_memalloc(ctx, 256, unit=0)
    h = dart_accumulate(ctx, g, jnp.ones((4,), jnp.int32))
    assert h.state == "queued" and not h.test()
    dart_flush(ctx)
    assert h.state in ("issued", "complete")
    h.wait()
    assert h.state == "complete"
