"""Shared-memory windows: zero-copy local access (paper §VI future work).

The paper's planned extension: "enable the MPI-3 shared-memory window
option for DART, which provides true zero-copy mechanisms, as opposed
to traditional single-copy mechanisms … especially for small message
sizes, intra- and inter-NUMA communication becomes a lot more
efficient."

DART-JAX analogue: when the target unit's partition is host-visible
(an arena in host memory: the CPU backend), the shm plane bypasses
jitted dispatch in BOTH directions.  Arenas on an accelerator (TPU
HBM) are never host-visible, so on the chip every op takes the engine:

* **reads** — ``dart_shm_view`` returns a zero-copy numpy view of the
  addressed bytes (no dynamic-slice dispatch, no buffer copy).  The
  returned view stays read-only; with the write plane below it is a
  **live window** on the arena (MPI-3 shm semantics), not an epoch
  snapshot — a later shm put through the same window is visible in it.
* **writes** — ``dart_shm_put`` performs a locked host-side write into
  the arena's buffer and re-installs the arena under ``engine.lock``,
  exactly like a donating flush does, so XLA dataflow stays
  authoritative and program order holds against queued epochs, the
  ProgressPlane daemon, and the fault plane's failed-lane fail-fast.
* **collectives** — ``try_shm_bcast``/``try_shm_gather[_typed]``/
  ``try_shm_scatter[_typed]`` serve intra-node bcast/gather/scatter as
  memcpy loops through the window with ZERO jitted dispatches when the
  pool is SHM-writable (single-controller: one pool arena backs every
  member, so the locality proof is per pool — the per-subtree engine
  fallback of a multi-node tree degenerates to a per-pool fallback).

Pointers minted by ``dart_team_memalloc_shared`` (or ``ctx.alloc``'s
default ``shm=True``) carry ``FLAG_SHM`` to mark eligibility; actual
routing additionally requires the backing arena to be host-visible
(readable: dlpack) and, for writes, host-writable (a stable
``unsafe_buffer_pointer`` the host can store through).  Support is
probed ONCE per pool and cached per ``(context, poolid)`` —
``invalidate_shm_cache`` drops entries on ``dart_team_destroy`` /
``dart_exit``.  The cache used to be one boolean per *context*, so the
first probed pool poisoned routing for every other pool under mixed
visibility (host-visible CPU arena + device-only arena); it is keyed
by poolid now.

Effect on the CPU backend (benchmarks/out/BENCH_engine.json,
``shm_plane``): an intra-node put costs zero jitted dispatches, and so
does an intra-node broadcast — the paper's "a lot more efficient for
small messages" expectation, on the write side too.
"""

from __future__ import annotations

import contextlib
import ctypes
import enum
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from .faults import ShmBoundsError
from .globmem import nbytes_of
from .gptr import FLAG_SHM, GlobalPtr
from .onesided import Handle, _check_strided, _to_host_bytes, deref


class Locality(enum.Enum):
    """Where a deref'd target can be serviced (paper §VI routing)."""
    SHM_LOCAL = "shm_local"     # zero-copy host view, no jitted dispatch
    REMOTE = "remote"           # jitted arena dynamic-slice dispatch


# --------------------------------------------------------------------------
# Per-pool support probe + cache
# --------------------------------------------------------------------------


def _writable_arena_view(arena: jax.Array) -> np.ndarray:
    """Host-writable uint8 view of the arena's device buffer.

    ``np.from_dlpack`` on the CPU backend is read-only by design, so
    the write plane maps the buffer through its raw pointer instead.
    The view has NO lifetime anchor on the buffer — callers must hold
    the engine lock and keep ``arena`` alive for the view's whole use
    (the shm plane only ever uses it inside one locked write).
    """
    arena.block_until_ready()
    ptr = arena.unsafe_buffer_pointer()
    buf = (ctypes.c_uint8 * int(arena.size)).from_address(ptr)
    return np.frombuffer(buf, dtype=np.uint8).reshape(arena.shape)


def _probe_pool_locked(ctx, poolid: int) -> Tuple[bool, bool]:
    """Under the engine lock: ``(readable, writable)`` for ``poolid``.

    Cached per ``(context, poolid)`` in ``ctx._shm_cache`` — the
    classifier sits on the hot get path, so the dlpack/pointer probe
    must not re-run per deref (``ctx._shm_probe_count`` counts actual
    probes; tests pin it flat in the steady state).  Mixed-visibility
    heaps are why the key is the poolid: one pool's visibility proves
    nothing about another's.
    """
    cache: Optional[Dict[int, Tuple[bool, bool]]]
    cache = getattr(ctx, "_shm_cache", None)
    if cache is None:
        cache = {}
        try:
            ctx._shm_cache = cache
        except AttributeError:          # holder without attribute support
            cache = None
    if cache is not None and poolid in cache:
        return cache[poolid]
    arena = ctx.state[poolid]
    try:
        ctx._shm_probe_count = getattr(ctx, "_shm_probe_count", 0) + 1
    except AttributeError:
        pass
    # only host-memory arenas are host-visible: on an accelerator the
    # dlpack probe may succeed by copying to the host, and the buffer
    # pointer is a device address the host must never write through
    try:
        readable = all(d.platform == "cpu" for d in arena.devices())
        if readable:
            np.from_dlpack(arena)
    except Exception:   # noqa: BLE001 - any failure means "not visible"
        readable = False
    writable = False
    if readable:
        try:
            _writable_arena_view(arena)
            writable = True
        except Exception:   # noqa: BLE001
            writable = False
    result = (readable, writable)
    if cache is not None:
        cache[poolid] = result
    return result


def invalidate_shm_cache(ctx, poolid: Optional[int] = None) -> None:
    """Drop the per-pool shm support cache — one pool's entry, or (with
    ``poolid=None``) the whole cache.  Called by ``dart_team_destroy``
    (the dropped window's pool) and ``dart_exit`` (everything): a
    destroyed pool's poolid is never reused, but the stale entry would
    leak, and a re-init must re-probe."""
    cache = getattr(ctx, "_shm_cache", None)
    if cache is not None:
        if poolid is None:
            cache.clear()
        else:
            cache.pop(poolid, None)
    # defensively retire the legacy one-bool-per-context cache so an
    # old-style reader can never see a stale positive after teardown
    if getattr(ctx, "_shm_supported", None) is not None:
        try:
            ctx._shm_supported = None
        except AttributeError:
            pass


def _engine_guard(ctx):
    engine = getattr(ctx, "engine", None)
    return engine, (engine.lock if engine is not None
                    else contextlib.nullcontext())


def shm_supported(ctx, poolid=None) -> bool:
    """True when the addressed pool's arena is host-visible.

    Probes the *addressed* pool when ``poolid`` is given (an arbitrary
    pool's visibility does not prove another's), and reports False —
    instead of raising — when the pool is absent or the heap state is
    empty (after ``dart_exit``).  The probe result is cached per
    ``(context, poolid)``; without an explicit ``poolid`` the first
    live pool is probed (a backend-visibility convenience — its cache
    entry is still keyed by that pool's id).
    """
    # liveness first, cache second: the cache records a live pool's
    # host-visibility, which says nothing about whether the addressed
    # pool (or any pool, after dart_exit) still exists.  The probe
    # dlpacks a live arena, so it holds the engine lock like every
    # other raw-state reader (donation safety).
    engine, guard = _engine_guard(ctx)
    with guard:
        if not ctx.state:
            return False        # post-exit: nothing is addressable
        if poolid is None:
            poolid = next(iter(ctx.state))
        elif poolid not in ctx.state:
            return False        # addressed pool is gone
        return _probe_pool_locked(ctx, poolid)[0]


def shm_writable(ctx, poolid=None) -> bool:
    """True when the addressed pool's arena is host-WRITABLE (the shm
    write plane's routing predicate; implies :func:`shm_supported`).
    Same liveness/caching rules as :func:`shm_supported`."""
    engine, guard = _engine_guard(ctx)
    with guard:
        if not ctx.state:
            return False
        if poolid is None:
            poolid = next(iter(ctx.state))
        elif poolid not in ctx.state:
            return False
        return _probe_pool_locked(ctx, poolid)[1]


# --------------------------------------------------------------------------
# Locality classifier
# --------------------------------------------------------------------------


def _classify_locked(ctx, gptr: GlobalPtr) -> Tuple[Locality, int, int, int]:
    """Deref + cached probe in ONE step: ``(locality, poolid, row,
    off)``.  Caller holds the engine lock (or has no engine).  This is
    the hoisted hot-path form — the public :func:`classify_locality`
    and the read/write routes below all build on it, so a routed get
    does a single lock acquisition for deref + probe + flush + view
    instead of re-taking the lock per layer."""
    poolid, row, off = deref(ctx.heap, ctx.teams_by_slot, gptr)
    if not gptr.is_shm:
        return Locality.REMOTE, poolid, row, off
    if poolid not in ctx.state:
        return Locality.REMOTE, poolid, row, off
    if not _probe_pool_locked(ctx, poolid)[0]:
        return Locality.REMOTE, poolid, row, off
    return Locality.SHM_LOCAL, poolid, row, off


def classify_locality(ctx, gptr: GlobalPtr) -> Locality:
    """Locality classifier used on deref by the runtime's routed paths.

    A target is SHM_LOCAL when its pointer was minted by
    ``dart_team_memalloc_shared`` (FLAG_SHM) *and* the backing arena is
    host-visible on this controller (an arena in host memory).
    Everything else takes the jitted one-sided path.
    """
    if not gptr.is_shm:
        return Locality.REMOTE
    engine, guard = _engine_guard(ctx)
    with guard:
        return _classify_locked(ctx, gptr)[0]


def mint_shm(gptr: GlobalPtr) -> GlobalPtr:
    """Return ``gptr`` with ``FLAG_SHM`` set: marks it *eligible* for
    the zero-copy plane — actual routing still depends on the backing
    arena being host-visible (:func:`classify_locality`)."""
    return GlobalPtr(unitid=gptr.unitid, segid=gptr.segid,
                     flags=gptr.flags | FLAG_SHM, addr=gptr.addr)


def dart_team_memalloc_shared(ctx, teamid: int,
                              nbytes_per_unit: int) -> GlobalPtr:
    """Collective aligned allocation whose pointers allow shm routing."""
    from .runtime import dart_team_memalloc_aligned
    return mint_shm(dart_team_memalloc_aligned(ctx, teamid,
                                               nbytes_per_unit))


# --------------------------------------------------------------------------
# Read side: zero-copy views
# --------------------------------------------------------------------------


def _check_headroom(ctx, poolid: int, row: int, off: int,
                    nbytes: int) -> None:
    """Typed bounds check against the pool's per-unit partition: a
    shape/dtype whose byte span overruns ``pool_bytes`` used to
    silently truncate the view slice and then die on a bare numpy
    reshape ``ValueError``; it raises :class:`ShmBoundsError` (lane-
    addressed, PR 9 error ladder) before any slicing now."""
    pool_bytes = ctx.heap.pools[poolid].pool_bytes
    if off < 0 or off + nbytes > pool_bytes:
        err = ShmBoundsError(
            f"shm window access overruns the unit partition: "
            f"off {off} + {nbytes} bytes > pool_bytes {pool_bytes} "
            f"(pool {poolid}, row {row})")
        err.poolid, err.row, err.unit = poolid, row, None
        err.off, err.nbytes = off, nbytes
        raise err


def dart_shm_view(ctx, gptr: GlobalPtr, shape: Tuple[int, ...],
                  dtype) -> np.ndarray:
    """Zero-copy read-only view of the addressed bytes.

    Requires a FLAG_SHM pointer and a host-visible arena (an arena in
    host memory).  Falls back with an explicit error rather than
    silently copying.  The view is a **live window**: a
    later ``dart_shm_put`` through the same arena is visible in it
    (writes that flush a jitted epoch re-install a NEW arena, which a
    previously taken view does not follow).
    """
    if not (gptr.flags & FLAG_SHM):
        raise ValueError("pointer was not minted by "
                         "dart_team_memalloc_shared (no FLAG_SHM)")
    view = try_shm_view(ctx, gptr, shape, dtype)
    if view is None:
        raise RuntimeError(
            "arena is not host-visible; use dart_get_blocking "
            "(zero-copy unavailable)")
    return view


def try_shm_view(ctx, gptr: GlobalPtr, shape: Tuple[int, ...],
                 dtype) -> Optional[np.ndarray]:
    """Routing form of :func:`dart_shm_view`: ``None`` when the target
    is not SHM_LOCAL (caller falls back to the engine path), the view
    otherwise.  One lock acquisition covers classify + flush + capture:

    * every read path flushes the target's ``(pool, row)`` lane first
      (ROADMAP completion semantics): queued puts to this target land
      before the view is taken, or direct callers see stale bytes.
      Per-target lane only — other targets' queued epochs keep
      accumulating.
    * flush + raw ``ctx.state`` read + the dlpack capture stay under
      the engine lock as ONE unit: a concurrent flush (e.g. the
      background ProgressPlane) donates the arena, so an unlocked read
      could dlpack a buffer deleted between the flush and the capture.
    """
    if not (gptr.flags & FLAG_SHM):
        return None
    n = nbytes_of(shape, dtype)
    engine, guard = _engine_guard(ctx)
    with guard:
        loc, poolid, row, off = _classify_locked(ctx, gptr)
        if loc is not Locality.SHM_LOCAL:
            return None
        _check_headroom(ctx, poolid, row, off, n)
        if engine is not None:
            engine.flush(poolid, row)
        arena = ctx.state[poolid]
        try:
            host = np.from_dlpack(arena)    # zero-copy on host backends
        except (TypeError, RuntimeError):
            return None
        flat = host[row, off:off + n]
    view = flat.view(np.dtype(dtype)).reshape(shape)
    view.flags.writeable = False
    return view


# --------------------------------------------------------------------------
# Write side: locked host-side puts (the tentpole)
# --------------------------------------------------------------------------


def _shm_write_locked(engine, ctx, poolid: int, row: int, off: int,
                      payload: np.ndarray, seg_len: int, stride: int,
                      count: int, unit: int) -> Handle:
    """The locked write protocol shared by puts and collectives.

    Order matters (docs/API.md "Shared-memory plane"):

    1. ``flush(pool, row)`` — queued jitted ops on the target lane land
       FIRST (program order; the flush may donate + replace the
       arena, so the arena is fetched after it).
    2. re-check the lane passively — if a queued op just failed in
       that flush, this write is ordered after the hole it left and
       must not apply.
    3. drain the pool's read fences — a dispatched-but-unmaterialized
       jitted gather still sources from this arena's buffer; the
       in-place write waits for it (the jitted path never needed this
       because its writes produce a NEW arena).
    4. write through the raw-pointer view and re-install the arena
       under ``engine.lock``, exactly like donation does — holder
       state stays the authoritative dataflow input for every later
       jitted op.
    """
    engine.flush(poolid, row)
    engine._check_lane_live(poolid, row, unit)
    arena = ctx.state[poolid]
    engine._drain_read_fences(poolid)
    host = _writable_arena_view(arena)
    if count == 1:
        host[row, off:off + payload.size] = payload
    else:
        for i in range(count):
            dst = off + i * stride
            host[row, dst:dst + seg_len] = payload[i * seg_len:
                                                   (i + 1) * seg_len]
    ctx.state[poolid] = arena
    engine.shm_puts += 1
    h = Handle((arena,))
    h.poolid, h.row = poolid, row
    return h


def try_shm_put(ctx, gptr: GlobalPtr, value, *, stride: int = 0,
                count: int = 1) -> Optional[Handle]:
    """Route a blocking put through the shm window when the target is
    SHM-writable; ``None`` otherwise (caller falls back to the engine).

    Semantics match the engine path bit-for-bit: same host staging
    (:func:`~repro.core.onesided._to_host_bytes` canonicalization),
    same strided-geometry validation and errors, same fault-plane
    enqueue boundary (injector poll + dead-unit/failed-lane
    fail-fast).  What changes is the cost: zero jitted dispatches —
    the write is a host memcpy under the engine lock.
    """
    if not (gptr.flags & FLAG_SHM):
        return None
    engine = getattr(ctx, "engine", None)
    if engine is None:
        return None
    payload = _to_host_bytes(value)
    with engine.lock:
        loc, poolid, row, off = _classify_locked(ctx, gptr)
        seg_len, stride, count = _check_strided(
            off, int(payload.size), stride, count,
            ctx.heap.pools[poolid].pool_bytes, "put")
        if loc is not Locality.SHM_LOCAL:
            return None
        if not _probe_pool_locked(ctx, poolid)[1]:
            return None         # readable but not writable: engine path
        engine._precheck_enqueue(poolid, row, gptr.unitid)
        return _shm_write_locked(engine, ctx, poolid, row, off, payload,
                                 seg_len, stride, count, gptr.unitid)


def dart_shm_put(ctx, gptr: GlobalPtr, value, *, stride: int = 0,
                 count: int = 1) -> Handle:
    """Zero-copy blocking put through the shm window (strict form of
    :func:`try_shm_put`: raises instead of falling back).  Returns a
    complete :class:`~repro.core.onesided.Handle` carrying the lane."""
    if not (gptr.flags & FLAG_SHM):
        raise ValueError("pointer was not minted by "
                         "dart_team_memalloc_shared (no FLAG_SHM)")
    h = try_shm_put(ctx, gptr, value, stride=stride, count=count)
    if h is None:
        raise RuntimeError(
            "arena is not host-writable; use dart_put / "
            "dart_put_blocking (zero-copy write unavailable)")
    return h


# --------------------------------------------------------------------------
# Intra-node shm-direct collectives
# --------------------------------------------------------------------------
#
# Single-controller locality proof: one pool arena backs every member
# row, so "every member is SHM_LOCAL" is exactly "the pool is
# host-writable" — probed once, cached per pool.  Each try_* routine
# returns None when the proof fails (or when the request would leave
# the engine kernels' masked-drop envelope), and the runtime wrapper
# falls back to the engine path for the whole team — the degenerate,
# per-pool form of the per-subtree fallback a multi-node tree would
# need.  Ordering matches collectives._pre_collective: the WHOLE pool
# flushes first (queued one-sided ops are ordered before the
# collective), then the memcpy loop runs under the same lock hold.


def _shm_collective_enter(ctx, gptr: GlobalPtr, off: int, nbytes: int):
    """Locked entry shared by the shm-direct collectives: routing
    proof + whole-pool flush + writable window.  Returns ``(engine,
    poolid, arena, host)`` or ``None`` to fall back.  Caller holds the
    engine lock."""
    engine = getattr(ctx, "engine", None)
    if engine is None:
        return None
    loc, poolid, _, _ = _classify_locked(ctx, gptr)
    if loc is not Locality.SHM_LOCAL:
        return None
    if not _probe_pool_locked(ctx, poolid)[1]:
        return None
    if off < 0 or off + nbytes > ctx.heap.pools[poolid].pool_bytes:
        # the jitted kernels mask out-of-range lanes (mode='drop');
        # keep that exact envelope by falling back instead of raising
        return None
    engine.flush(poolid)
    arena = ctx.state[poolid]
    engine._drain_read_fences(poolid)
    host = _writable_arena_view(arena)
    return engine, poolid, arena, host


def _shm_collective_exit(engine, ctx, poolid: int, arena) -> Handle:
    ctx.state[poolid] = arena
    engine.shm_collective_ops += 1
    return Handle((arena,))


def try_shm_bcast(ctx, root_gptr: GlobalPtr, nbytes: int
                  ) -> Optional[Handle]:
    """Shm-direct broadcast: the root row's ``nbytes`` window memcpy'd
    to every member row — zero jitted dispatches.  ``None`` = caller
    falls back to the engine collective."""
    if not (root_gptr.flags & FLAG_SHM):
        return None
    engine, guard = _engine_guard(ctx)
    if engine is None:
        return None
    with guard:
        poolid, root_row, off = deref(ctx.heap, ctx.teams_by_slot,
                                      root_gptr)
        entered = _shm_collective_enter(ctx, root_gptr, off, nbytes)
        if entered is None:
            return None
        engine, poolid, arena, host = entered
        seg = np.array(host[root_row, off:off + nbytes])   # copy: src row
        for r in range(host.shape[0]):
            host[r, off:off + nbytes] = seg
        return _shm_collective_exit(engine, ctx, poolid, arena)


def try_shm_gather(ctx, gptr: GlobalPtr, per_unit_nbytes: int):
    """Shm-direct byte gather: every row's window copied host-side →
    ``(n_rows, per_unit_nbytes)`` uint8 (same value type as the engine
    path).  ``None`` = fall back."""
    if not (gptr.flags & FLAG_SHM):
        return None
    engine, guard = _engine_guard(ctx)
    if engine is None:
        return None
    with guard:
        poolid, _, off = deref(ctx.heap, ctx.teams_by_slot, gptr)
        entered = _shm_collective_enter(ctx, gptr, off, per_unit_nbytes)
        if entered is None:
            return None
        engine, poolid, arena, host = entered
        raw = np.array(host[:, off:off + per_unit_nbytes])   # host copy
        engine.shm_collective_ops += 1
    import jax.numpy as jnp
    out = jnp.asarray(raw)
    return out, Handle((out,))


def try_shm_gather_typed(ctx, gptr: GlobalPtr, shape, dtype):
    """Shm-direct typed gather: every row's value decoded host-side →
    ``(n_rows, *shape)`` of ``dtype`` (byte-identical to the engine
    path's decode).  ``None`` = fall back."""
    if not (gptr.flags & FLAG_SHM):
        return None
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    shape = tuple(shape)
    n_elems = (max(int(np.prod(shape, dtype=np.int64)), 1)
               if shape else 1)
    nbytes = n_elems * dt.itemsize
    engine, guard = _engine_guard(ctx)
    if engine is None:
        return None
    with guard:
        poolid, _, off = deref(ctx.heap, ctx.teams_by_slot, gptr)
        entered = _shm_collective_enter(ctx, gptr, off, nbytes)
        if entered is None:
            return None
        engine, poolid, arena, host = entered
        raw = np.array(host[:, off:off + nbytes])
        engine.shm_collective_ops += 1
    n_rows = raw.shape[0]
    vals = jnp.asarray(raw.view(dt).reshape((n_rows,) + shape))
    return vals, Handle((vals,))


def try_shm_scatter(ctx, gptr: GlobalPtr, values) -> Optional[Handle]:
    """Shm-direct byte scatter: row i of ``values`` (uint8
    ``(n_rows, nbytes)``) memcpy'd to unit i's window.  ``None`` =
    fall back (including shape mismatches: the engine path owns that
    error)."""
    if not (gptr.flags & FLAG_SHM):
        return None
    vh = np.asarray(values, np.uint8)
    engine, guard = _engine_guard(ctx)
    if engine is None:
        return None
    with guard:
        poolid, _, off = deref(ctx.heap, ctx.teams_by_slot, gptr)
        if (vh.ndim != 2
                or vh.shape[0] != ctx.heap.pools[poolid].n_rows):
            return None
        nbytes = int(vh.shape[1])
        entered = _shm_collective_enter(ctx, gptr, off, nbytes)
        if entered is None:
            return None
        engine, poolid, arena, host = entered
        host[:, off:off + nbytes] = vh
        return _shm_collective_exit(engine, ctx, poolid, arena)


def try_shm_scatter_typed(ctx, gptr: GlobalPtr, values
                          ) -> Optional[Handle]:
    """Shm-direct typed scatter: row i of ``values`` (``(n_rows,
    *shape)``, any dtype) encoded host-side — same canonicalization as
    the engine path (int64/float64 → 32-bit without x64) — and
    memcpy'd to unit i.  ``None`` = fall back."""
    if not (gptr.flags & FLAG_SHM):
        return None
    vh = np.asarray(values)
    canon = jax.dtypes.canonicalize_dtype(vh.dtype)
    if vh.dtype != canon:
        vh = vh.astype(canon)
    if vh.ndim < 1:
        return None
    rows_bytes = np.ascontiguousarray(
        vh.reshape(vh.shape[0], -1)).view(np.uint8)
    engine, guard = _engine_guard(ctx)
    if engine is None:
        return None
    with guard:
        poolid, _, off = deref(ctx.heap, ctx.teams_by_slot, gptr)
        if rows_bytes.shape[0] != ctx.heap.pools[poolid].n_rows:
            return None
        nbytes = int(rows_bytes.shape[1])
        entered = _shm_collective_enter(ctx, gptr, off, nbytes)
        if entered is None:
            return None
        engine, poolid, arena, host = entered
        host[:, off:off + nbytes] = rows_bytes
        return _shm_collective_exit(engine, ctx, poolid, arena)
