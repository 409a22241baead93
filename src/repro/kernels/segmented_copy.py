"""Shape-stable segmented copy: the DispatchPlan substrate for
``CommEngine.flush`` (and the host-plane collectives).

The paper's §V.C case for DART-MPI is that the runtime adds only a
*constant, small* per-call overhead over the raw substrate.  Our
substrate is XLA, where every distinct input *shape* costs a trace +
compile — so a flush path that specializes kernels on the exact
``(run length, payload size)`` pair pays compile + host-staging costs
on every new epoch shape instead of a constant dispatch overhead.
This module removes the shape dependence:

* **Bucketing** — run length ``k`` and the per-op segment size are
  rounded up to the next power of two (:func:`bucket_pow2`), and the
  run is padded with masked no-op descriptors (``len = 0``).  A small
  fixed family of compiled kernels therefore serves *all* epochs; a
  steady-state loop of varying-size epochs performs zero recompiles
  after warmup.
* **Packed descriptors** — ``rows/offs/lens/starts/strides/counts``
  travel as ONE ``(k, 6)`` int32 array (:func:`pack_descriptors`), and
  every payload byte travels as ONE flat uint8 buffer assembled
  host-side into a bucketed staging array: two host→device transfers
  per flush instead of 3–5 tiny ones plus a per-op eager
  ``jnp.concatenate`` chain.  A descriptor names a *strided run* —
  ``count`` segments of ``len`` bytes, ``stride`` bytes apart — so a
  matrix column or tile halo is ONE descriptor, not one per element;
  contiguous ops are the ``stride=0, count=1`` degenerate case.
* **Flat-index addressing** — kernels address the arena as a flat byte
  string: op *i* touches positions
  ``row*P + off + (lane//len)*stride + lane%len`` for
  ``lane < len*count`` (payloads stay dense in lane order); masked
  lanes are routed to distinct out-of-range indices and dropped
  (scatter, ``mode='drop'``) or filled with zeros (gather,
  ``mode='fill'``).  Because only valid lanes produce in-range
  indices, padding never clamps, smears across rows, or needs pool
  headroom — the bounds check at initiation is the only range
  requirement.  One formula serves contiguous and strided ops alike,
  so stride/count live in the traced descriptor *data*, never the plan
  key: a varying-stride loop performs zero recompiles.
* **Vectorized vs ordered** — runs whose byte ranges are provably
  disjoint (``_RunMeta`` tracks this while the run is grown) dispatch
  as ONE vectorized segmented update (``unique_indices`` scatter);
  only overlapping uniform runs keep the sequential ``fori_loop`` so
  last-writer-wins program order is preserved.
* **Reduction plane** — accumulate runs (``dart_accumulate`` /
  ``dart_get_accumulate``) ride the same substrate through segmented
  read-modify-write kernels (:func:`accumulate_plan`): descriptors
  gain an op column, every payload slot is pre-filled with the op's
  **identity element** (:func:`op_identity` — masked lanes are no-ops
  by value as well as by mask), and only the run's ``(k, seg)``
  windows are ever bitcast to the dtype, never the arena.  Disjoint
  runs vectorize; overlapping same-op runs keep the ordered RMW loop
  (one dispatch either way — the ops commute).
* **Plan cache** — compiled executables are cached process-wide by
  ``(kind, impl, arena shape, buckets, ...)``; the engine counts
  misses (``compile_count``) and hits (``plan_cache_hits``) so tests
  and ``BENCH_engine/v6`` can *assert* the steady state compiles
  nothing.

``impl='pallas'`` selects the hand-tiled Pallas kernel (grid over
descriptors, scalar-prefetched descriptor table; interpret-mode off
TPU), mirroring the ``impl`` switch in :mod:`repro.kernels.ops`.  The
Pallas path stages pad-to-bucket windows through VMEM and therefore
requires ``off + (count-1)*stride + sseg <= pool_bytes`` for every
descriptor (``sseg`` = the per-segment bucket of
:func:`strided_buckets`); :func:`pallas_ok` checks this host-side and
callers fall back to the XLA (``'ref'``) kernels when it fails, so
semantics never depend on the impl choice.  TPU grids execute
sequentially, so the one Pallas scatter kernel serves ordered runs
too; strided runs widen its grid to ``(k, cb)`` — one step per
(descriptor, segment).

The Pallas kernels do not compile for TPU yet: every one maps the whole
arena into VMEM (``BlockSpec(arena.shape)``), Mosaic refuses the
unaligned dynamic 1-D slice of the flat payload (``flat_ref[pl.ds(st,
sseg)]``: index not provably a multiple of 1024), and the gather's
``(1, seg_out)`` output block breaks the (8, 128) block-shape rule.
:class:`~repro.core.onesided.CommEngine` therefore refuses
``impl='pallas'`` on a TPU backend.  Elsewhere they run in interpret
mode, and the differential suites hold them to the ref kernels.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# descriptor columns: desc[i] = (row, off, len, start, stride, count[, op])
# One descriptor now names a *strided run*: ``count`` segments of
# ``len`` bytes each, the j-th segment landing at ``off + j*stride``.
# A contiguous op is the degenerate case ``stride=0, count=1`` (so
# every pre-existing plan shape is unchanged); padding rows are
# all-zero (``count=0`` ⇒ zero valid lanes).  Accumulate descriptors
# carry a seventh column — the op code — so the packed table is
# self-describing (telemetry/debugging and the run split rule both
# read it); the combine function itself is static in the plan key,
# since XLA must trace it.
ROW, OFF, LEN, START, STRIDE, COUNT, OPCODE = 0, 1, 2, 3, 4, 5, 6
DESC_COLS = 6           # put/get descriptor width
ACC_DESC_COLS = 7       # accumulate descriptor width (adds OPCODE)

#: element-wise reduction ops of the reduction plane (dart_accumulate /
#: dart_allreduce): name → descriptor op code.
REDUCE_OPS = {"sum": 0, "prod": 1, "min": 2, "max": 3}

#: smallest segment bucket — tiny ops (1..16 B) share one compiled shape
SEG_FLOOR = 16
#: smallest run-length bucket — runs of 1..4 ops share one compiled
#: shape (a single blocking op and a short epoch hit the same plan)
K_FLOOR = 4
#: smallest flat-payload staging bucket
FLAT_FLOOR = 64


def bucket_pow2(n: int, floor: int = 1) -> int:
    """Next power of two >= max(n, floor) — the shape-stability rule."""
    n = max(int(n), floor, 1)
    return 1 << (n - 1).bit_length()


def pack_descriptors(rows: Sequence[int], offs: Sequence[int],
                     lens: Sequence[int],
                     payloads: Optional[Sequence[np.ndarray]] = None,
                     strides: Optional[Sequence[int]] = None,
                     counts: Optional[Sequence[int]] = None
                     ) -> Tuple[np.ndarray, Optional[np.ndarray], int]:
    """Host-side staging: k ops → one bucketed ``(k', 6)`` int32
    descriptor table (k' = pow2 bucket of k, padded with all-zero
    no-ops) and, for puts, one bucketed flat uint8 payload buffer.

    ``lens`` are **per-segment** bytes; op *i* moves
    ``lens[i] * counts[i]`` bytes in total (``counts`` defaults to all
    ones, ``strides`` to all zeros — the contiguous degenerate case,
    which packs byte-for-byte like the historical ``(k, 4)`` format).
    The segment-size bucket covers the *total* bytes of the largest
    op, so a strided run's dense payload/window footprint fits one
    descriptor row.  ``starts`` index into the flat buffer, where
    payloads pack densely (segment j of op i at
    ``start + j*len``); the buffer carries a trailing ``seg`` bytes of
    zero margin so a pad-to-bucket window read starting at any valid
    ``start`` stays in range (the Pallas path relies on this; the XLA
    path is range-safe regardless).  Returns ``(desc, flat, seg)``
    with ``flat is None`` for gathers.
    """
    k = len(rows)
    kb = bucket_pow2(k, K_FLOOR)
    lens = np.asarray(lens, np.int64)
    counts = (np.ones(k, np.int64) if counts is None
              else np.asarray(counts, np.int64))
    strides = (np.zeros(k, np.int64) if strides is None
               else np.asarray(strides, np.int64))
    totals = lens * counts
    seg = bucket_pow2(int(totals.max()) if k else 1, SEG_FLOOR)
    desc = np.zeros((kb, DESC_COLS), np.int32)
    desc[:k, ROW] = rows
    desc[:k, OFF] = offs
    desc[:k, LEN] = lens
    desc[:k, STRIDE] = strides
    desc[:k, COUNT] = counts
    starts = np.zeros(k, np.int64)
    np.cumsum(totals[:-1], out=starts[1:])
    desc[:k, START] = starts
    flat = None
    if payloads is not None:
        # sized by the BUCKETS, not the actual payload total, so the
        # flat staging shape is a pure function of (kb, seg) and warm
        # epochs with any payload mix inside the bucket reuse the plan
        flat = np.zeros(max(kb * seg + seg, FLAT_FLOOR), np.uint8)
        for s, p in zip(starts, payloads):
            flat[int(s):int(s) + p.size] = p
    return desc, flat, seg


def op_identity(op: str, dtype) -> np.ndarray:
    """The identity element of ``op`` over ``dtype`` — the value whose
    accumulation is a no-op (``x op identity == x``):

    ======  ==================  =====================
    op      floating            integral
    ======  ==================  =====================
    sum     ``0.0``             ``0``
    prod    ``1.0``             ``1``
    min     ``+inf``            ``iinfo(dtype).max``
    max     ``-inf``            ``iinfo(dtype).min``
    ======  ==================  =====================

    Padding lanes of accumulate payloads and masked element lanes of
    the bucketed allreduce carry this value, so pow2 bucketing never
    changes a reduction's result — masked lanes are no-ops *by value*
    as well as by index mask.
    """
    if op not in REDUCE_OPS:
        raise ValueError(f"unknown reduction op {op!r} "
                         f"(supported: {sorted(REDUCE_OPS)})")
    dt = jnp.dtype(dtype)
    floating = jnp.issubdtype(dt, jnp.floating)
    if op == "sum":
        v = 0
    elif op == "prod":
        v = 1
    elif op == "min":
        v = np.inf if floating else np.iinfo(dt).max
    else:                                        # max
        v = -np.inf if floating else np.iinfo(dt).min
    return np.asarray(v, dt)


def identity_bytes(op: str, dtype) -> np.ndarray:
    """``op``'s identity element as its little-endian byte pattern
    (``itemsize`` uint8 values) — the fill for accumulate payload
    staging buffers."""
    scalar = op_identity(op, dtype)
    return np.frombuffer(scalar.tobytes(), np.uint8).copy()


def pack_acc_descriptors(rows: Sequence[int], offs: Sequence[int],
                         lens: Sequence[int],
                         payloads: Sequence[np.ndarray],
                         op: str, dtype,
                         strides: Optional[Sequence[int]] = None,
                         counts: Optional[Sequence[int]] = None
                         ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host-side staging for an accumulate run: k read-modify-write ops
    → one bucketed ``(k', 7)`` int32 descriptor table (columns
    ``row, off, len, start, stride, count, op``; ``lens`` per-segment,
    as in :func:`pack_descriptors`) plus one flat uint8 payload buffer.

    Unlike :func:`pack_descriptors` (whose payloads pack densely), each
    accumulate op owns a full seg-aligned slot (``start = i * seg``)
    **pre-filled with the op's identity element**
    (:func:`identity_bytes`): every padded lane — the tail of a short
    payload and all lanes of bucket-padding descriptors — decodes to
    the identity, so combining it is arithmetically a no-op even
    before the index mask drops it.  A strided op's payload packs
    densely *within* its slot (``len*count`` bytes, then identity
    fill).  The flat staging size is a pure function of the
    ``(k', seg)`` buckets, keeping warm epochs on the cached plan.
    """
    k = len(rows)
    kb = bucket_pow2(k, K_FLOOR)
    lens = np.asarray(lens, np.int64)
    counts = (np.ones(k, np.int64) if counts is None
              else np.asarray(counts, np.int64))
    strides = (np.zeros(k, np.int64) if strides is None
               else np.asarray(strides, np.int64))
    totals = lens * counts
    seg = bucket_pow2(int(totals.max()) if k else 1, SEG_FLOOR)
    desc = np.zeros((kb, ACC_DESC_COLS), np.int32)
    desc[:k, ROW] = rows
    desc[:k, OFF] = offs
    desc[:k, LEN] = lens
    desc[:k, STRIDE] = strides
    desc[:k, COUNT] = counts
    desc[:k, START] = np.arange(k, dtype=np.int64) * seg
    desc[k:, START] = np.arange(k, kb, dtype=np.int64) * seg
    desc[:, OPCODE] = REDUCE_OPS[op]
    # exactly kb*seg (>= FLAT_FLOOR: kb >= 4, seg >= 16): the kernels
    # reshape the flat buffer to (kb, seg) payload slots
    ident = identity_bytes(op, dtype)
    flat = np.tile(ident, kb * seg // ident.size)
    for i, p in enumerate(payloads):
        flat[i * seg:i * seg + p.size] = p
    return desc, flat, seg


def check_flat_addressable(arena_shape: Tuple[int, int]) -> None:
    """The segmented kernels address the arena as a flat int32 byte
    index (``row * pool_bytes + off + lane``; OOB markers sit just
    above ``rows * pool_bytes``).  Without x64, index arithmetic stays
    int32, so arenas at or beyond 2**30 total bytes would overflow
    *silently* (mode='drop' would discard the wrapped indices — lost
    puts, zero-filled gets).  Refuse loudly instead."""
    n_cells = int(arena_shape[0]) * int(arena_shape[1])
    if n_cells >= 1 << 30:
        raise NotImplementedError(
            f"arena of {n_cells} bytes exceeds the flat int32 "
            "addressing range of the segmented-copy kernels (see "
            "ROADMAP: int64-lane variant for >1 GiB heaps)")


def strided_buckets(desc: np.ndarray, seg: int) -> Tuple[int, int]:
    """``(sseg, cb)`` buckets for the 2-D Pallas grid: the per-segment
    window bytes (pow2 of the largest ``LEN``) and the segment-count
    grid extent (pow2 of the largest ``COUNT``).  For an all-contiguous
    run this is exactly ``(seg, 1)`` — every total IS its segment — so
    contiguous Pallas plans stay in their historical shape family."""
    lens = desc[:, LEN]
    counts = desc[:, COUNT]
    sseg = bucket_pow2(int(lens.max()) if lens.size else 1, SEG_FLOOR)
    cb = bucket_pow2(int(counts.max()) if counts.size else 1, 1)
    return min(sseg, seg), cb


def pallas_ok(desc: np.ndarray, seg: int, pool_bytes: int) -> bool:
    """True iff every descriptor's padded windows fit the pool — the
    precondition for the VMEM-windowed Pallas kernels.  A strided
    descriptor's last segment window starts at
    ``off + (count-1)*stride`` and spans ``sseg`` padded bytes."""
    sseg, _ = strided_buckets(desc, seg)
    last = desc[:, OFF] + np.maximum(desc[:, COUNT] - 1, 0) * desc[:, STRIDE]
    return bool(np.all(last + sseg <= pool_bytes))


# --------------------------------------------------------------------------
# XLA ('ref') kernels — flat-index scatter/gather, shapes fixed by buckets
# --------------------------------------------------------------------------


def _lane_mask(desc: jax.Array, seg: int) -> Tuple[jax.Array, jax.Array]:
    """(k, seg) lane grid + validity mask (``lane < len*count``) for a
    descriptor table; callers turn invalid lanes into out-of-range
    flat indices (dropped by scatters, zero-filled by gathers).  Lane
    space is *dense*: lane ``j*len + r`` is byte ``r`` of segment
    ``j`` — payloads and gather windows pack without gaps."""
    lane = jnp.arange(seg, dtype=jnp.int32)[None, :]
    valid = lane < (desc[:, LEN] * desc[:, COUNT])[:, None]
    return valid, lane


def _strided_dst(desc: jax.Array, lane: jax.Array, P) -> jax.Array:
    """Flat arena byte index per dense lane:
    ``row*P + off + (lane // len)*stride + lane % len``.  The
    contiguous degenerate case (``stride=0, count=1``) reduces to the
    historical ``row*P + off + lane`` for every valid lane — ONE
    formula serves both, so varying stride/count mixes never leave the
    plan's shape family.  ``len`` is clamped to 1 so padding rows
    divide safely; their (garbage) indices are masked off by callers
    before use."""
    safe_len = jnp.maximum(desc[:, LEN], 1)[:, None]
    return (desc[:, ROW][:, None] * P + desc[:, OFF][:, None]
            + (lane // safe_len) * desc[:, STRIDE][:, None]
            + lane % safe_len)


def _ref_scatter_vec(arena: jax.Array, desc: jax.Array, flat: jax.Array,
                     *, seg: int) -> jax.Array:
    """Disjoint segmented put as ONE vectorized update: every valid lane
    lands via a unique-index scatter, masked lanes are dropped."""
    R, P = arena.shape
    n_cells = R * P
    valid, lane = _lane_mask(desc, seg)
    k = desc.shape[0]
    dst = _strided_dst(desc, lane, P)
    oob = n_cells + jnp.arange(k * seg, dtype=jnp.int32).reshape(k, seg)
    dst = jnp.where(valid, dst, oob)
    src_idx = jnp.where(valid, desc[:, START][:, None] + lane,
                        flat.shape[0])
    src = jnp.take(flat, src_idx, mode="fill", fill_value=0)
    out = arena.reshape(-1).at[dst.reshape(-1)].set(
        src.reshape(-1), mode="drop", unique_indices=True)
    return out.reshape(R, P)


def _ref_scatter_ordered(arena: jax.Array, desc: jax.Array,
                         flat: jax.Array, *, seg: int) -> jax.Array:
    """Overlap-tolerant segmented put: descriptors apply strictly in
    queue order (``fori_loop``), preserving last-writer-wins."""
    R, P = arena.shape
    n_cells = R * P
    lane = jnp.arange(seg, dtype=jnp.int32)

    def body(i, a):
        safe_len = jnp.maximum(desc[i, LEN], 1)
        valid = lane < desc[i, LEN] * desc[i, COUNT]
        dst = (desc[i, ROW] * P + desc[i, OFF]
               + (lane // safe_len) * desc[i, STRIDE] + lane % safe_len)
        dst = jnp.where(valid, dst, n_cells + lane)
        src = jnp.take(flat, jnp.where(valid, desc[i, START] + lane,
                                       flat.shape[0]),
                       mode="fill", fill_value=0)
        return a.at[dst].set(src, mode="drop", unique_indices=True)

    return jax.lax.fori_loop(0, desc.shape[0], body,
                             arena.reshape(-1)).reshape(R, P)


def _ref_gather(arena: jax.Array, desc: jax.Array, *, seg: int
                ) -> jax.Array:
    """Segmented get: (k, seg) pad-to-bucket byte windows in one
    dispatch; masked lanes read as zero."""
    R, P = arena.shape
    valid, lane = _lane_mask(desc, seg)
    idx = jnp.where(valid, _strided_dst(desc, lane, P), R * P)
    return jnp.take(arena.reshape(-1), idx, mode="fill", fill_value=0)


#: elementwise combine (window ⊕ payload) per reduction op, shared by
#: the ref and Pallas RMW kernels.
_ELT_COMBINE = {"sum": jnp.add, "prod": jnp.multiply, "min": jnp.minimum,
                "max": jnp.maximum}


def _bytes_as(raw: jax.Array, dt) -> jax.Array:
    """Reinterpret a flat uint8 buffer as typed elements (the
    ``from_bytes`` bitcast, kept local so the kernel layer has no
    dependency on ``repro.core``)."""
    dt = jnp.dtype(dt)
    if dt == jnp.uint8:
        return raw
    n = raw.size // dt.itemsize
    return jax.lax.bitcast_convert_type(raw.reshape(n, dt.itemsize), dt)


def _typed_as_bytes(typed: jax.Array) -> jax.Array:
    if typed.dtype == jnp.uint8:
        return typed.reshape(-1)
    return jax.lax.bitcast_convert_type(typed.reshape(-1),
                                        jnp.uint8).reshape(-1)


def _ref_accumulate_vec(arena: jax.Array, desc: jax.Array,
                        flat: jax.Array, *, seg: int, op: str, dt,
                        fetch: bool):
    """Byte-disjoint segmented read-modify-write in ONE vectorized
    dispatch: gather every op's current byte window, bitcast to the
    run's dtype, combine with the (identity-padded) payload slots,
    bitcast back, and scatter the combined bytes.  Only the ``(k,
    seg)`` windows are ever bitcast — never the arena — so the cost
    scales with the run, not the pool.  Masked lanes take the familiar
    route: distinct out-of-range destinations, dropped by the scatter;
    their payload decodes to the op identity anyway (no-op by value
    too).  With ``fetch`` the gathered pre-update windows — already in
    hand — are returned as well (``MPI_Get_accumulate``; the run
    builder keeps fetch runs byte-disjoint, so read-all-then-apply-all
    equals the sequential order)."""
    R, P = arena.shape
    dt = jnp.dtype(dt)
    n_cells = R * P
    valid, lane = _lane_mask(desc, seg)
    k = desc.shape[0]
    dst = _strided_dst(desc, lane, P)
    oob = n_cells + jnp.arange(k * seg, dtype=jnp.int32).reshape(k, seg)
    dst = jnp.where(valid, dst, oob)
    old = jnp.take(arena.reshape(-1), dst, mode="fill",
                   fill_value=0)                       # (k, seg) bytes
    old_t = _bytes_as(old.reshape(-1), dt).reshape(k, seg // dt.itemsize)
    pay_t = _bytes_as(flat, dt).reshape(k, seg // dt.itemsize)
    comb = _ELT_COMBINE[op](old_t, pay_t)
    comb_b = _typed_as_bytes(comb).reshape(k, seg)
    out = arena.reshape(-1).at[dst.reshape(-1)].set(
        comb_b.reshape(-1), mode="drop",
        unique_indices=True).reshape(R, P)
    return (out, old) if fetch else out


def _ref_accumulate_ordered(arena: jax.Array, desc: jax.Array,
                            flat: jax.Array, *, seg: int, op: str, dt):
    """Overlap-tolerant accumulate: descriptors read-modify-write
    strictly in queue order (``fori_loop``), one window at a time —
    the RMW analogue of :func:`_ref_scatter_ordered`.  (Commutative
    ops make any order correct; sequential keeps it bitwise equal to
    the blocking reference even for non-associative float rounding.)"""
    R, P = arena.shape
    dt = jnp.dtype(dt)
    n_cells = R * P
    eseg = seg // dt.itemsize
    lane = jnp.arange(seg, dtype=jnp.int32)

    def body(i, a):
        safe_len = jnp.maximum(desc[i, LEN], 1)
        valid = lane < desc[i, LEN] * desc[i, COUNT]
        idx = (desc[i, ROW] * P + desc[i, OFF]
               + (lane // safe_len) * desc[i, STRIDE] + lane % safe_len)
        idx = jnp.where(valid, idx, n_cells + lane)
        old_b = jnp.take(a, jnp.where(valid, idx, n_cells),
                         mode="fill", fill_value=0)
        old_t = _bytes_as(old_b, dt).reshape(eseg)
        pay_t = _bytes_as(flat[desc[i, START] + lane], dt).reshape(eseg)
        comb_b = _typed_as_bytes(_ELT_COMBINE[op](old_t, pay_t))
        return a.at[idx].set(comb_b, mode="drop", unique_indices=True)

    return jax.lax.fori_loop(0, desc.shape[0], body,
                             arena.reshape(-1)).reshape(R, P)


# --------------------------------------------------------------------------
# Pallas kernels — grid over descriptors, scalar-prefetched table
# --------------------------------------------------------------------------


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pallas_scatter_kernel(desc_ref, flat_ref, arena_ref, o_ref, *,
                           sseg: int):
    """Grid step (i, c): segment ``c`` of descriptor ``i``.  Inactive
    steps (``c >= count`` or a padding row) clamp their window to
    ``(0, 0)`` and their flat read to ``0``, mask every lane, and
    write the window back unchanged — safe because the TPU grid is
    sequential, so the read observes all prior writes."""
    i = pl.program_id(0)
    c = pl.program_id(1)
    ln = desc_ref[i, LEN]
    cnt = desc_ref[i, COUNT]
    active = (c < cnt) & (ln > 0)
    row = jnp.where(active, desc_ref[i, ROW], 0)
    off = jnp.where(active, desc_ref[i, OFF] + c * desc_ref[i, STRIDE], 0)
    st = jnp.where(active, desc_ref[i, START] + c * ln, 0)
    seg_bytes = flat_ref[pl.ds(st, sseg)]
    window = o_ref[pl.ds(row, 1), pl.ds(off, sseg)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, sseg), 1)
    mask = active & (lane < ln)
    o_ref[pl.ds(row, 1), pl.ds(off, sseg)] = jnp.where(
        mask, seg_bytes[None, :], window)


def _pallas_gather_kernel(desc_ref, arena_ref, o_ref, *, sseg: int):
    """Grid step (i, c): read segment ``c`` of descriptor ``i`` from
    the arena and pack it densely at ``c*len`` of output row ``i``
    (zero-initialised on the row's first step)."""
    i = pl.program_id(0)
    c = pl.program_id(1)
    ln = desc_ref[i, LEN]
    cnt = desc_ref[i, COUNT]
    active = (c < cnt) & (ln > 0)
    row = jnp.where(active, desc_ref[i, ROW], 0)
    off = jnp.where(active, desc_ref[i, OFF] + c * desc_ref[i, STRIDE], 0)
    wr = jnp.where(active, c * ln, 0)

    @pl.when(c == 0)
    def _zero_row():
        o_ref[...] = jnp.zeros_like(o_ref)

    window = arena_ref[pl.ds(row, 1), pl.ds(off, sseg)]
    cur = o_ref[pl.ds(0, 1), pl.ds(wr, sseg)]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, sseg), 1)
    mask = active & (lane < ln)
    o_ref[pl.ds(0, 1), pl.ds(wr, sseg)] = jnp.where(mask, window, cur)


def _pallas_acc_kernel(desc_ref, flat_ref, arena_ref, o_ref, *,
                       seg: int, op: str, dt):
    """Per-descriptor read-modify-write: load the byte window, bitcast
    to the run's dtype, combine with the (identity-padded) payload
    slot, bitcast back, and store the masked result.  The grid is
    sequential, so overlapping descriptors apply strictly in order —
    RMW-safe by construction."""
    i = pl.program_id(0)
    row = desc_ref[i, ROW]
    off = desc_ref[i, OFF]
    ln = desc_ref[i, LEN]
    st = desc_ref[i, START]
    window = o_ref[pl.ds(row, 1), pl.ds(off, seg)]      # (1, seg) uint8
    pay = flat_ref[pl.ds(st, seg)]                      # (seg,)
    dt = jnp.dtype(dt)
    isz = dt.itemsize
    if isz == 1:
        wt, pt = window.reshape(seg), pay
    else:
        wt = jax.lax.bitcast_convert_type(
            window.reshape(seg // isz, isz), dt)
        pt = jax.lax.bitcast_convert_type(pay.reshape(seg // isz, isz),
                                          dt)
    comb = _ELT_COMBINE[op](wt, pt)
    if isz == 1:
        cb = comb.reshape(1, seg)
    else:
        cb = jax.lax.bitcast_convert_type(comb, jnp.uint8).reshape(1, seg)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, seg), 1)
    o_ref[pl.ds(row, 1), pl.ds(off, seg)] = jnp.where(lane < ln, cb,
                                                      window)


def _pallas_accumulate(arena: jax.Array, desc: jax.Array,
                       flat: jax.Array, *, seg: int, op: str, dt
                       ) -> jax.Array:
    k = desc.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[pl.BlockSpec(flat.shape, lambda i, *_: (0,)),
                  pl.BlockSpec(arena.shape, lambda i, *_: (0, 0))],
        out_specs=pl.BlockSpec(arena.shape, lambda i, *_: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_pallas_acc_kernel, seg=seg, op=op, dt=dt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={2: 0},       # arena (arg after desc, flat)
        interpret=_interpret_default(),
    )(desc, flat, arena)


def _pallas_scatter(arena: jax.Array, desc: jax.Array, flat: jax.Array,
                    *, seg: int, sseg: int, cb: int) -> jax.Array:
    """Segmented scatter over a 2-D ``(descriptor, segment)`` grid.
    The grid is sequential on TPU (and in interpret mode), so this
    kernel is valid for ordered (overlapping) runs as well as disjoint
    ones.  A contiguous run has ``cb == 1, sseg == seg`` — exactly the
    historical one-step-per-descriptor shape."""
    k = desc.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, cb),
        in_specs=[pl.BlockSpec(flat.shape, lambda i, c, *_: (0,)),
                  pl.BlockSpec(arena.shape, lambda i, c, *_: (0, 0))],
        out_specs=pl.BlockSpec(arena.shape, lambda i, c, *_: (0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_pallas_scatter_kernel, sseg=sseg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={2: 0},       # arena (arg after desc, flat)
        interpret=_interpret_default(),
    )(desc, flat, arena)


def _pallas_gather(arena: jax.Array, desc: jax.Array, *, seg: int,
                   sseg: int, cb: int) -> jax.Array:
    """Segmented gather over a 2-D ``(descriptor, segment)`` grid.
    Output rows are ``seg`` wide for contiguous runs (``cb == 1`` —
    byte-identical to the historical layout) and ``seg + sseg`` wide
    otherwise: the last dense segment write (at ``(count-1)*len``) may
    overrun ``seg`` by up to ``sseg - len`` padded bytes, and the host
    decode only reads the first ``nbytes`` of each row anyway."""
    k = desc.shape[0]
    seg_out = seg if cb == 1 else seg + sseg
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, cb),
        in_specs=[pl.BlockSpec(arena.shape, lambda i, c, *_: (0, 0))],
        out_specs=pl.BlockSpec((1, seg_out), lambda i, c, *_: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_pallas_gather_kernel, sseg=sseg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, seg_out), jnp.uint8),
        interpret=_interpret_default(),
    )(desc, arena)


# --------------------------------------------------------------------------
# The plan cache
# --------------------------------------------------------------------------

_PLAN_CACHE: Dict[Tuple, Callable] = {}
_BUILD_COUNT = [0]      # process-total plan builds (≈ XLA compiles)
# flushes may now run concurrently (submitter threads + the background
# ProgressPlane), so the cache is guarded: one builder per key, and the
# hit/build counters stay exact.  build() only wraps a jax.jit (cheap;
# the XLA compile happens lazily on first call), so holding the lock
# across it is fine.
_PLAN_LOCK = threading.Lock()


def cached_plan(key: Tuple, build: Callable[[], Callable]
                ) -> Tuple[Callable, bool]:
    """Process-wide executable cache (the DispatchPlan layer): returns
    ``(fn, hit)``.  A miss runs ``build()`` — which creates a fresh
    ``jax.jit`` wrapper, so exactly one XLA trace+compile follows on
    first call — and records it; hits are the steady state."""
    with _PLAN_LOCK:
        fn = _PLAN_CACHE.get(key)
        if fn is not None:
            return fn, True
        fn = build()
        _PLAN_CACHE[key] = fn
        _BUILD_COUNT[0] += 1
        return fn, False


def clear_plan_cache() -> None:
    """Drop every cached executable (benchmarks use this to measure a
    true cold flush: rebuilt plans re-trace and re-compile)."""
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()


def plan_cache_stats() -> Dict[str, int]:
    with _PLAN_LOCK:
        return {"size": len(_PLAN_CACHE), "builds": _BUILD_COUNT[0]}


def scatter_plan(arena_shape: Tuple[int, int], kb: int, seg: int,
                 flat_len: int, *, ordered: bool, impl: str = "ref",
                 donate: bool = True, sseg: Optional[int] = None,
                 cb: Optional[int] = None) -> Tuple[Callable, bool]:
    """fn(arena, desc, flat) -> arena'. ``ordered`` keeps the
    sequential loop (overlapping uniform runs); otherwise the
    vectorized unique-index scatter runs.  The Pallas impl is
    inherently ordered (sequential grid) so one kernel serves both.

    ``(sseg, cb)`` are the :func:`strided_buckets` of the run —
    **Pallas-only** grid parameters, defaulting to the contiguous
    family ``(seg, 1)``.  The ref kernels read stride/count from the
    descriptor table itself (ONE traced formula), so ref callers pass
    ``None`` and a varying-stride loop never leaves the cached plan.
    """
    check_flat_addressable(arena_shape)
    sseg = seg if sseg is None else sseg
    cb = 1 if cb is None else cb
    key = ("scatter", impl, arena_shape, kb, seg, flat_len, ordered,
           donate, sseg, cb)

    def build():
        if impl == "pallas":
            fn = functools.partial(_pallas_scatter, seg=seg, sseg=sseg,
                                   cb=cb)
        else:
            fn = functools.partial(
                _ref_scatter_ordered if ordered else _ref_scatter_vec,
                seg=seg)
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    return cached_plan(key, build)


def accumulate_plan(arena_shape: Tuple[int, int], kb: int, seg: int,
                    flat_len: int, *, op: str, dtype, fetch: bool,
                    ordered: bool = False, impl: str = "ref",
                    donate: bool = True) -> Tuple[Callable, bool]:
    """fn(arena, desc, flat) -> arena'  (or ``(arena', old_windows)``
    with ``fetch`` — the ``MPI_Get_accumulate`` form, old values as
    ``(kb, seg)`` pad-to-bucket uint8 windows read before any of the
    run applies).

    The combine op and dtype are static in the key (XLA traces the
    combine); the descriptor's op column keeps the packed table
    self-describing.  Only the run's ``(k, seg)`` windows are bitcast
    to the dtype — never the arena — so a dispatch costs O(run), not
    O(pool).  Mirroring :func:`scatter_plan`: byte-disjoint runs take
    the vectorized gather-combine-scatter; overlapping runs
    (``ordered``) keep the sequential per-descriptor RMW loop — still
    ONE dispatch, and bitwise equal to the blocking order.  The Pallas
    kernel is a sequential descriptor grid, valid for both.  Fetch
    runs always take the vectorized ref path (the run builder keeps
    them byte-disjoint, so read-all-then-apply-all is
    order-equivalent and the gathered old windows come for free).

    Strided accumulate runs ride the REF kernels only (the engine's
    impl picker routes any run containing ``count > 1`` to ref): the
    Pallas RMW kernel's identity-padded slot layout is pinned to the
    exact ``kb*seg`` flat buffer, which leaves no room for a padded
    per-segment window scheme."""
    check_flat_addressable(arena_shape)
    dt = jnp.dtype(dtype)
    if op not in REDUCE_OPS:
        raise ValueError(f"unknown reduction op {op!r}")
    if seg % dt.itemsize or arena_shape[1] % dt.itemsize:
        raise ValueError(
            f"accumulate of {dt} needs element-aligned segment/pool "
            f"bytes (seg={seg}, pool_bytes={arena_shape[1]})")
    if fetch and impl == "pallas":
        raise ValueError("fused fetch-accumulate has no Pallas kernel; "
                         "it rides the vectorized ref path")
    key = ("accumulate", impl, arena_shape, kb, seg, flat_len, op,
           str(dt), fetch, ordered, donate)

    def build():
        if impl == "pallas":
            fn = functools.partial(_pallas_accumulate, seg=seg, op=op,
                                   dt=dt)
        elif ordered and not fetch:
            fn = functools.partial(_ref_accumulate_ordered, seg=seg,
                                   op=op, dt=dt)
        else:
            fn = functools.partial(_ref_accumulate_vec, seg=seg, op=op,
                                   dt=dt, fetch=fetch)
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    return cached_plan(key, build)


def gather_plan(arena_shape: Tuple[int, int], kb: int, seg: int, *,
                impl: str = "ref", sseg: Optional[int] = None,
                cb: Optional[int] = None) -> Tuple[Callable, bool]:
    """fn(arena, desc) -> (kb, >=seg) uint8 pad-to-bucket windows; each
    op's bytes pack densely from column 0 of its row (decode reads the
    first ``nbytes``).  ``(sseg, cb)`` as in :func:`scatter_plan`:
    Pallas-only, ``None`` (→ ``(seg, 1)``) for the ref impl and for
    contiguous Pallas runs, whose rows stay exactly ``seg`` wide."""
    check_flat_addressable(arena_shape)
    sseg = seg if sseg is None else sseg
    cb = 1 if cb is None else cb
    key = ("gather", impl, arena_shape, kb, seg, sseg, cb)

    def build():
        if impl == "pallas":
            return jax.jit(functools.partial(_pallas_gather, seg=seg,
                                             sseg=sseg, cb=cb))
        return jax.jit(functools.partial(_ref_gather, seg=seg))

    return cached_plan(key, build)
