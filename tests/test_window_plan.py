"""Window plans: contiguous puts, gets and plain accumulates moved as
``(1, seg)`` windows of the 2-D arena (``_win_scatter``, with an op for
accumulates, and ``_win_gather``), held byte for byte to the flat lane
plans and to a numpy model, and the engine's choice between the two
paths."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DartConfig, dart_exit, dart_init
from repro.core import onesided as _os
from repro.core import tracing
from repro.kernels import segmented_copy as sc

#: arena of the plan-level tests: 3 rows of 256 bytes
R, P = 3, 256


def _arena(rng):
    return rng.integers(0, 256, (R, P)).astype(np.uint8)


def _payloads(rng, lens):
    return [rng.integers(0, 256, int(n)).astype(np.uint8) for n in lens]


def _numpy_put(arena, rows, offs, pays):
    out = arena.copy()
    for r, o, p in zip(rows, offs, pays):
        out[r, o:o + p.size] = p
    return out


def _scatter(arena, desc, flat, seg, *, window, ordered=False):
    fn, _ = sc.scatter_plan(arena.shape, desc.shape[0], seg, flat.shape[0],
                            ordered=ordered, impl="ref", donate=False,
                            window=window)
    return np.asarray(fn(jnp.asarray(arena), desc, flat))


def _gather(arena, desc, seg, *, window):
    fn, _ = sc.gather_plan(arena.shape, desc.shape[0], seg, impl="ref",
                           window=window)
    return np.asarray(fn(jnp.asarray(arena), desc))


def _check_run(arena, rows, offs, pays, *, ordered):
    """Window scatter == lane scatter == numpy; window gather rows ==
    lane gather rows, and each row decodes to the bytes put."""
    desc, flat, seg = sc.pack_descriptors(rows, offs,
                                          [p.size for p in pays], pays)
    want = _numpy_put(arena, rows, offs, pays)
    win = _scatter(arena, desc, flat, seg, window=True)
    lane = _scatter(arena, desc, flat, seg, window=False, ordered=ordered)
    np.testing.assert_array_equal(win, want)
    np.testing.assert_array_equal(lane, want)
    gdesc, _, gseg = sc.pack_descriptors(rows, offs, [p.size for p in pays])
    gw = _gather(want, gdesc, gseg, window=True)
    np.testing.assert_array_equal(gw, _gather(want, gdesc, gseg,
                                              window=False))
    for i, (r, o, p) in enumerate(zip(rows, offs, pays)):
        np.testing.assert_array_equal(gw[i, :p.size], want[r, o:o + p.size])
        assert not gw[i, p.size:].any()          # lanes >= len read zero
    assert not gw[len(pays):].any()              # padding rows read zero


@pytest.mark.parametrize("seed", range(6))
def test_random_disjoint_runs_match_lane_plans_and_numpy(seed):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 12))
    # disjoint ranges: each op owns one 64-byte slot of a row
    slots = rng.choice(R * (P // 64), size=k, replace=False)
    lens = rng.integers(1, 65, k)
    rows = slots // (P // 64)
    offs = (slots % (P // 64)) * 64 + rng.integers(0, 65 - lens)
    _check_run(_arena(rng), rows, offs, _payloads(rng, lens),
               ordered=False)


@pytest.mark.parametrize("seed", range(6))
def test_random_overlapping_runs_last_writer_wins(seed):
    rng = np.random.default_rng(100 + seed)
    k = int(rng.integers(2, 12))
    lens = rng.integers(1, 97, k)
    rows = rng.integers(0, 2, k)                 # two rows: many overlaps
    offs = np.array([rng.integers(0, P - n + 1) for n in lens])
    _check_run(_arena(rng), rows, offs, _payloads(rng, lens), ordered=True)


@pytest.mark.parametrize("case", [
    "row_end_len_1",          # the row's last byte: clamped start
    "row_end_len_seg_minus_1",
    "row_end_len_seg",
    "row_start_len_1",
    "row_start_len_seg",
    "mid_row_len_seg_minus_1",
])
def test_window_edges(case):
    """Windows that end at the row's last byte (``off + seg > P``: the
    start clamps to ``P - seg`` and the bytes shift by ``off - s``) and
    lengths of 1, ``seg - 1`` and ``seg`` bytes."""
    rng = np.random.default_rng(7)
    seg = 64
    n = {"len_1": 1, "len_seg_minus_1": seg - 1, "len_seg": seg}[
        case.split("_", 2)[2]]
    off = {"row_end": P - n, "row_start": 0, "mid_row": 101}[
        case.rsplit("_len", 1)[0]]
    pays = _payloads(rng, [n, 3])
    # a second op on another row keeps the run two descriptors long
    rows, offs = [2, 0], [off, 17]
    _, _, got_seg = sc.pack_descriptors(rows, offs, [n, 3])
    assert got_seg == sc.bucket_pow2(n, sc.SEG_FLOOR)
    if case.startswith("row_end") and n < got_seg:
        assert off + got_seg > P                 # the clamp engages
    _check_run(_arena(rng), rows, offs, pays, ordered=False)


def test_padding_rows_leave_the_arena_untouched():
    """A 1-op run pads to 4 descriptors; the padding rows (``len = 0``,
    row 0, offset 0) write back the window they read."""
    rng = np.random.default_rng(3)
    arena = _arena(rng)
    pay = _payloads(rng, [5])
    desc, flat, seg = sc.pack_descriptors([1], [250], [5], pay)
    assert desc.shape[0] == sc.K_FLOOR and not desc[1:, sc.LEN].any()
    out = _scatter(arena, desc, flat, seg, window=True).copy()
    np.testing.assert_array_equal(out[1, 250:255], pay[0])
    out[1, 250:255] = arena[1, 250:255]
    np.testing.assert_array_equal(out, arena)


def test_window_plans_key_apart_from_lane_plans():
    """The window choice is part of the plan key; an ordered and a
    disjoint window scatter share one plan (both apply in queue
    order)."""
    shape, kb, seg, flat = (2, 4096), 4, 16, 128
    lane, _ = sc.scatter_plan(shape, kb, seg, flat, ordered=False)
    win, _ = sc.scatter_plan(shape, kb, seg, flat, ordered=False,
                             window=True)
    win_ordered, hit = sc.scatter_plan(shape, kb, seg, flat, ordered=True,
                                       window=True)
    assert win is not lane and win_ordered is win and hit
    assert sc.gather_plan(shape, kb, seg, window=True)[0] is not \
        sc.gather_plan(shape, kb, seg)[0]


def test_window_plans_lift_the_flat_index_limit():
    """``check_flat_addressable`` binds only the lane plans: a window
    plan addresses ``(row, off)``, never ``row * P + off``."""
    shape = (4, 1 << 30)
    with pytest.raises(NotImplementedError):
        sc.scatter_plan(shape, 4, 16, 128, ordered=False)
    with pytest.raises(NotImplementedError):
        sc.gather_plan(shape, 4, 16)
    sc.scatter_plan(shape, 4, 16, 128, ordered=False, window=True)
    sc.gather_plan(shape, 4, 16, window=True)


#: every (op, dtype) the reduction plane takes that both accumulate
#: paths can run: each arithmetic op on float32, int32 and uint32, and
#: bxor on the integers it is defined for
ACC_CASES = [(op, dt) for op in ("sum", "prod", "min", "max")
             for dt in ("float32", "int32", "uint32")] + [
    ("bxor", "int32"), ("bxor", "uint32")]


def _accumulate(arena, desc, flat, seg, op, dt, *, window, ordered=False):
    fn, _ = sc.accumulate_plan(arena.shape, desc.shape[0], seg,
                               flat.shape[0], op=op, dtype=dt, fetch=False,
                               ordered=ordered, donate=False, window=window)
    return np.asarray(fn(jnp.asarray(arena), desc, flat))


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("op,dtype", ACC_CASES)
def test_window_accumulate_matches_lane_plans(op, dtype, overlap):
    """The window accumulate plan leaves the arena byte-equal to the
    lane plans (vectorized on disjoint runs, ordered on overlapping
    ones), one descriptor always hard against a row's end (the clamped
    window).  Values are small integers, so every sum and product is
    exact in float32 and the order of the lane plan's vectorized
    combine cannot show."""
    dt = np.dtype(dtype)
    rng = np.random.default_rng(sum(map(ord, op + dtype)) + overlap)
    low = 0 if dt.kind == "u" else -3
    arena = rng.integers(low, 4, (R, P // 4)).astype(dt).view(np.uint8)
    k = int(rng.integers(2, 12))
    lens = rng.integers(1, 17, k)                 # elements
    if overlap:
        rows = rng.integers(0, 2, k)
        offs = np.array([rng.integers(0, P // 4 - n + 1) for n in lens])
    else:                  # one 64-byte slot each, the first a row's last
        per_row = P // 64
        last = int(rng.integers(R)) * per_row + per_row - 1
        others = np.setdiff1d(np.arange(R * per_row), [last])
        slots = np.concatenate([[last], rng.choice(others, size=k - 1,
                                                   replace=False)])
        rows = slots // per_row
        offs = (slots % per_row) * 16 + rng.integers(0, 17 - lens)
    offs[0] = P // 4 - lens[0]
    pays = [rng.integers(low, 4, int(n)).astype(dt).view(np.uint8)
            for n in lens]
    desc, flat, seg = sc.pack_acc_descriptors(
        rows, offs * 4, lens * 4, pays, op, dt)
    win = _accumulate(arena, desc, flat, seg, op, dt, window=True,
                      ordered=overlap)
    lane = _accumulate(arena, desc, flat, seg, op, dt, window=False,
                       ordered=overlap)
    np.testing.assert_array_equal(win, lane)
    assert not np.array_equal(win, arena)


def test_window_accumulate_lowers_for_a_4_gib_arena():
    """At the GUPS cell's table, 4 rows of 2**30 bytes, the lane plans
    are refused (their flat int32 index) and the window plan lowers
    from shapes alone, its arena never flattened; it serves disjoint
    and overlapping runs alike."""
    shape, kb, seg = (4, 1 << 30), 1024, 16
    for ordered in (False, True):
        with pytest.raises(NotImplementedError):
            sc.accumulate_plan(shape, kb, seg, kb * seg, op="bxor",
                               dtype="uint32", fetch=False, ordered=ordered)
    fn, _ = sc.accumulate_plan(shape, kb, seg, kb * seg, op="bxor",
                               dtype="uint32", fetch=False, window=True)
    assert sc.accumulate_plan(shape, kb, seg, kb * seg, op="bxor",
                              dtype="uint32", fetch=False, ordered=True,
                              window=True)[0] is fn
    text = fn.lower(
        jax.ShapeDtypeStruct(shape, jnp.uint8),
        jax.ShapeDtypeStruct((kb, sc.ACC_DESC_COLS), jnp.int32),
        jax.ShapeDtypeStruct((kb * seg,), jnp.uint8)).as_text()
    assert "tensor<4x1073741824xui8>" in text
    assert str(4 << 30) not in text


# ---------------------------------------------------- the engine's choice --

@pytest.fixture()
def ga():
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=4096))
    tracing.reset()
    # shm=False: every op takes the engine's device path, as on a chip
    yield ctx.alloc((512,), jnp.float32, shm=False)
    dart_exit(ctx)


def _launch_window_counter(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        fn()
    launch = tracing.totals()["dart.launch"]
    return launch["n"], launch["window"]


def test_contiguous_runs_take_the_window_path(ga, tmp_path):
    eng = ga.ctx.engine
    s0 = eng.dispatch_stats()
    value = np.arange(20, dtype=np.float32)

    def ops():
        ga.at[0, 64:84].put(value)
        hs = [ga.at[1, i * 32:i * 32 + 8].put_nb(value[:8])
              for i in range(4)]
        ga.flush()
        for h in hs:
            h.wait()
        np.testing.assert_array_equal(ga.at[0, 64:84].get(), value)
        np.testing.assert_array_equal(ga.at[1, 96:104].get(), value[:8])

    n, window = _launch_window_counter(tmp_path, ops)
    s1 = eng.dispatch_stats()
    assert n == window == 4                       # 2 put runs, 2 gets
    assert s1["window_dispatches"] - s0["window_dispatches"] == 4
    assert s1["dispatches"]["ref"] - s0["dispatches"]["ref"] == 4


def test_plain_accumulates_take_the_window_path(ga, tmp_path):
    """A plain contiguous accumulate run moves windows; a fetch (its
    pre-update values come from the lane plan's gather) and a strided
    run keep the lane plans.  The front end spans every accumulate call
    as ``dart.accumulate`` with its element count."""
    eng = ga.ctx.engine
    w0 = eng.dispatch_stats()["window_dispatches"]
    ones = np.ones(12, np.float32)

    def ops():
        hs = [ga.at[0, i * 8:i * 8 + 4].accumulate(ones[:4], "sum")
              for i in range(3)]
        ga.flush()
        for h in hs:
            h.wait()
        old = ga.at[0, 0:4].get_accumulate(ones[:4], "sum")
        np.testing.assert_array_equal(old, ones[:4])
        ga.at[1, 0:96:8].accumulate(ones, "max").wait()
        np.testing.assert_array_equal(ga.at[0, 0:4].get(), 2 * ones[:4])

    n, window = _launch_window_counter(tmp_path, ops)
    assert (n, window) == (4, 2)                  # + the get's window
    assert eng.dispatch_stats()["window_dispatches"] - w0 == 2
    acc = tracing.totals()["dart.accumulate"]
    assert (acc["n"], acc["elems"]) == (4, 3 * 4 + 12)


def test_strided_runs_take_the_lane_path(ga, tmp_path):
    eng = ga.ctx.engine
    w0 = eng.dispatch_stats()["window_dispatches"]
    col = ga.at[1, 0:96:8]
    value = np.arange(col.size, dtype=np.float32)

    def ops():
        col.put_nb(value).wait()
        np.testing.assert_array_equal(col.get(), value)

    n, window = _launch_window_counter(tmp_path, ops)
    assert (n, window) == (2, 0)
    assert eng.dispatch_stats()["window_dispatches"] == w0


def test_segment_wider_than_the_pool_takes_the_lane_path(tmp_path):
    """A 300-byte put into a 384-byte pool buckets to a 512-byte
    segment: no window of the row can hold it, so the lane path runs."""
    ctx = dart_init(n_units=2, config=DartConfig(
        non_collective_pool_bytes=4096, team_pool_bytes=384))
    tracing.reset()
    try:
        ga = ctx.alloc((80,), jnp.float32, shm=False)
        value = np.arange(75, dtype=np.float32)

        def ops():
            ga.at[1, 0:75].put(value)
            np.testing.assert_array_equal(ga.at[1, 0:75].get(), value)

        n, window = _launch_window_counter(tmp_path, ops)
        assert (n, window) == (2, 0)
        assert ctx.engine.dispatch_stats()["window_dispatches"] == 0
    finally:
        dart_exit(ctx)


def test_window_rule_reads_descriptors_segment_and_sharding():
    """``_window_path`` takes the window kernels only for an
    all-contiguous run, a segment bucket no wider than a row, and an
    arena held by one device (a row-sharded arena keeps the lane plans,
    which need no all-gather)."""
    desc, _, seg = sc.pack_descriptors([0, 1], [0, 8], [4, 4])
    one = jnp.zeros((2, 64), jnp.uint8)
    assert _os._window_path(desc, seg, one)
    strided, _, sseg = sc.pack_descriptors([0, 1], [0, 8], [4, 4],
                                           strides=[0, 16], counts=[1, 2])
    assert not _os._window_path(strided, sseg, one)
    assert not _os._window_path(desc, seg, jnp.zeros((2, 8), jnp.uint8))
    devices = [types.SimpleNamespace(id=i) for i in range(4)]
    sharded = types.SimpleNamespace(
        shape=(4, 64), sharding=types.SimpleNamespace(device_set=devices))
    assert not _os._window_path(desc, seg, sharded)
