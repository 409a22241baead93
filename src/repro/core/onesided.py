"""DART one-sided communication (paper §III, §IV.B.5) + the
locality-aware non-blocking engine (§VI future work).

Two planes, mirroring how DART-MPI sits above MPI-3 RMA:

**Host plane** (single-controller, the analogue of the paper's
process-level API): ``dart_put/get`` dereference the global pointer
(flags → allocation kind, segid → team, absolute→relative unit
translation for collective pointers — §IV.B.4), then issue the
underlying substrate op.  The substrate here is XLA: a donated
``dynamic_update_slice`` on the sharded arena, which on a TPU mesh
compiles to a one-sided ICI DMA into the owning unit's HBM — the direct
analogue of ``MPI_Rput`` in a passive-target epoch.

**Epoch / flush / completion model** (the non-blocking engine):

The paper's non-blocking ops return request handles completed by
``dart_wait``/``dart_test``; underneath, MPI aggregates requests and a
``MPI_Win_flush`` completes them at the window.  We reproduce that
structure with :class:`CommEngine`, an **epoch-scoped pending-op
queue** over the symmetric heap:

* ``CommEngine.put/get`` *enqueue* — the pointer is dereferenced and
  bounds-checked at initiation (translation happens once, like the
  paper's dart_put), but no device work is dispatched.  The returned
  :class:`Handle` starts in the ``queued`` state.
  ``CommEngine.accumulate/get_accumulate`` (the ``MPI_Accumulate`` /
  ``MPI_Get_accumulate`` analogues — element-wise reductions applied
  *at the target*) enqueue the same way: same-(op, dtype) runs share
  one segmented read-modify-write dispatch — overlap included, the
  ops commute — while mixed-op or accumulate-vs-put overlap splits
  the run in queue order; fetch runs stay byte-disjoint so every
  fetched pre-value matches the sequential order (the *reduction
  plane*; identity-padded descriptors keep it on the same bucketed
  plan cache).
* ``CommEngine.flush`` closes the epoch: maximal runs of same-pool
  ops are **coalesced** into one batched jitted dispatch — N queued
  puts become a single XLA launch instead of N.  Same-size ops
  coalesce unconditionally; **mixed-size** ops share the dispatch
  when their byte ranges are disjoint and split the run when they
  overlap.  Program order is preserved run-by-run, so overlapping
  writes resolve exactly as the equivalent sequence of blocking ops
  (last writer wins).
* Dispatch is **shape-stable** (the DispatchPlan layer,
  :mod:`repro.kernels.segmented_copy`): run length and segment size
  are bucketed to powers of two, padded with masked no-op
  descriptors, so a steady-state loop of varying-size epochs hits a
  small fixed family of compiled kernels — zero recompiles after
  warmup (``compile_count`` / ``plan_cache_hits`` make this
  assertable).  Each flush stages its metadata as ONE packed
  ``(k, 4)`` descriptor array and its payload as ONE flat byte
  buffer (two host→device transfers, not 3–5 tiny ones per run), and
  provably disjoint put runs dispatch as one *vectorized* segmented
  update; only overlapping uniform runs keep the sequential in-order
  loop.  See docs/API.md "Flush cost model".
* ``CommEngine.flush(poolid, row)`` is the **per-target** form — the
  ``MPI_Win_flush_local(rank, win)`` analogue: only the named
  ``(pool, row)`` lane dispatches; other targets' queued epochs keep
  accumulating (rows are disjoint per-unit partitions, so this can
  never reorder visible effects).  ``handle.wait()`` flushes only its
  own lane; the runtime surfaces ``dart_flush(ctx, gptr,
  target=unit)`` and the typed layer ``ga[unit].flush()``.
* Handle lifecycle: ``queued`` → (flush) → ``issued`` → (XLA async
  dispatch drains) → ``complete`` — the paper's §III
  issued/locally-complete/remotely-complete ladder.  ``dart_wait`` on
  a queued handle triggers the flush itself; ``dart_test`` reports
  False until the op has been dispatched.

**Threading model**: the engine is thread-safe.  ``CommEngine.lock``
(a reentrant lock) serializes every mutation of the pending queue, the
instrumentation counters, and — critically — every ``holder.state``
swap: the batched kernels *donate* the arena, so an unserialized
``ctx.state`` read racing a flush could observe a deleted buffer.  Any
code that reads ``holder.state`` outside the engine (the heap atomics
in :mod:`repro.core.atomic_ops`, the zero-copy view in
:mod:`repro.core.shm`, the host-plane collectives) takes the same lock.
N submitter threads may enqueue/flush/wait/test concurrently; handle
state transitions (``queued → issued → complete`` / ``failed``) happen
under the lock, so ``dart_test``/``dart_wait``/``dart_waitall`` are
safe from any thread while a flusher runs — including the background
:class:`repro.core.progress.ProgressPlane`, which drains queued epochs
at a byte/op watermark or an idle deadline without any caller
involvement (the paper's passive-target progress, docs/API.md
"Threading model & progress").

The engine also carries ``dispatch_count``, a counter of jitted kernel
launches, so tests and benchmarks can *assert* that a coalesced flush
issues fewer dispatches than the equivalent blocking sequence.

**Locality classifier**: on deref, ``FLAG_SHM``-eligible pointers
whose arena is host-visible are routed through the zero-copy view in
:mod:`repro.core.shm` instead of a jitted dynamic-slice dispatch (the
paper's §VI shared-memory-window plan) — see
:func:`repro.core.shm.classify_locality` and the runtime-level
``dart_get_blocking``.

Epochs: MPI requires RMA calls to sit inside an access epoch; DART opens
a shared epoch on every window at init/alloc time so users never see it
(§IV.B.5).  In XLA the "epoch" is the program region between two
flushes — conflict freedom inside it is guaranteed by dataflow, exactly
the RMA *unified* memory model the paper adopts.

**Device plane** (inside ``shard_map``; the analogue of what DASH's
compiled kernels do): ``shmem_put/get`` move bytes between unit rows
with ``lax.ppermute`` (static peers → point-to-point ICI DMA) or an
``all_gather`` + dynamic row-select (dynamic peers).  The Pallas RDMA
kernels in ``repro.kernels.rdma`` are the hand-tiled fast path for the
same semantics.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import functools
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import segmented_copy as _sc

from .faults import (DartError, FaultPlane, FlushTimeoutError,
                     RetriesExhaustedError, TransientDispatchFault,
                     UnitFailedError)
from .globmem import (HeapState, SymmetricHeap, WindowDestroyedError,
                      copy_state, from_bytes, nbytes_of, to_bytes)
from .gptr import GlobalPtr


def _to_host_bytes(value) -> np.ndarray:
    """Typed value → host-staged 1-D uint8 bytes (little-endian bitcast,
    identical layout to :func:`~repro.core.globmem.to_bytes`).

    Puts stage their payload on the HOST at initiation so that flush
    can assemble one flat buffer with plain ``memcpy`` and ship it in a
    single host→device transfer — instead of one device bitcast per
    enqueue plus an eager concatenate chain at flush.
    """
    arr = np.asarray(value)
    canon = jax.dtypes.canonicalize_dtype(arr.dtype)
    if arr.dtype != canon:
        # mirror jnp.asarray: Python floats/ints arrive as 64-bit numpy
        # dtypes but the heap's byte layout is the canonical (32-bit
        # unless x64 is enabled) one the device path always used
        arr = arr.astype(canon)
    arr = np.ascontiguousarray(arr).reshape(-1)
    if arr.dtype != np.uint8:
        arr = arr.view(np.uint8)
    return arr


def _host_decode(raw: np.ndarray, shape: Tuple[int, ...], dtype
                 ) -> np.ndarray:
    """Inverse of :func:`_to_host_bytes` on a host byte window."""
    dt = jnp.dtype(dtype)
    return raw[: nbytes_of(shape, dt)].copy().view(dt).reshape(shape)

# --------------------------------------------------------------------------
# Request handles (paper: MPI_Rput/Rget handles + dart_wait/test[all])
# --------------------------------------------------------------------------


def _arr_done(a) -> bool:
    """is_deleted-or-is_ready, tolerating a flush donating the buffer
    BETWEEN the two probes (the TOCTOU a concurrent flusher opens up):
    donated ⇒ a successor consumed it ⇒ complete by program order."""
    try:
        return a.is_deleted() or a.is_ready()
    except Exception as e:  # noqa: BLE001 - narrow on message below
        if "deleted" in str(e) or "donated" in str(e):
            return True
        raise


def _block_ready(arrays) -> None:
    """Per-array ``block_until_ready`` with the same donation-race
    tolerance as :func:`_arr_done` — a batched
    ``jax.block_until_ready(list)`` would raise on a buffer donated
    after the caller's ``is_deleted`` filter ran."""
    for a in arrays:
        try:
            if not a.is_deleted():
                a.block_until_ready()
        except Exception as e:  # noqa: BLE001 - narrow on message below
            if "deleted" in str(e) or "donated" in str(e):
                continue
            raise


class Handle:
    """A DART communication handle.

    Lifecycle (paper §III): ``queued`` (enqueued on a
    :class:`CommEngine`, not yet dispatched) → ``issued`` (dispatched
    to XLA, asynchronously in flight) → ``complete`` (buffers ready).
    Handles constructed directly from arrays — the immediate path used
    by collectives — are born ``issued``.

    If an array has been *donated* to a later op (e.g. a subsequent put
    to the same pool), it is treated as complete: XLA executes ops on a
    device in program order, so a successor consuming the buffer is
    ordered after this op, and all reads flow through the successor's
    heap state anyway (dataflow = the RMA unified model, docs/API.md).
    """

    def __init__(self, arrays: Tuple[jax.Array, ...] = (),
                 engine: "Optional[CommEngine]" = None):
        self.arrays = tuple(arrays)
        self._engine = engine
        self._issued = engine is None
        self._error: Optional[BaseException] = None

    @property
    def state(self) -> str:
        if self._error is not None:
            return "failed"
        if not self._issued:
            return "queued"
        if all(_arr_done(a) for a in self.arrays):
            return "complete"
        return "issued"

    def _resolve(self, arrays: Tuple[jax.Array, ...]) -> None:
        self.arrays = tuple(arrays)
        self._issued = True

    def _fail(self, error) -> None:
        """Mark the op as terminally **failed** (window destroyed
        before dispatch, target unit dead, retries exhausted, ...);
        wait/test raise the typed error.  Accepts an exception from
        the :class:`~repro.core.faults.DartError` ladder, or a bare
        message (wrapped in ``DartError``)."""
        if isinstance(error, str):
            error = DartError(error)
        self._error = error

    def _check_failed(self) -> None:
        if self._error is not None:
            raise self._error

    def _dropped_error(self) -> DartError:
        err = DartError(
            f"queued op ({self._lane_repr()}) was dropped before "
            "dispatch (engine cleared by dart_exit?)")
        err.poolid = getattr(self, "poolid", None)
        err.row = getattr(self, "row", None)
        return err

    def _lane_repr(self) -> str:
        return (f"pool {getattr(self, 'poolid', '?')}, "
                f"row {getattr(self, 'row', '?')}")

    def wait(self) -> None:
        self._check_failed()
        if not self._issued and self._engine is not None:
            # close only this handle's (pool, row) lane — the
            # MPI_Win_flush_local(rank, win) analogue; other targets
            # keep accumulating ops for their own coalesced flush.
            # flush() serializes on the engine lock, so if a concurrent
            # flusher (another thread, or the background progress
            # plane) already dispatched this op, ours is a no-op and
            # the _issued re-check below observes the transition.
            self._engine.flush(getattr(self, "poolid", None),
                               getattr(self, "row", None))
            self._check_failed()
            if not self._issued:
                raise self._dropped_error()
        _block_ready(self.arrays)

    def test(self) -> bool:
        self._check_failed()
        if not self._issued:
            return False
        return all(_arr_done(a) for a in self.arrays)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"Handle(state={self.state}, n_arrays={len(self.arrays)})"


class _GatherBatch:
    """One coalesced get dispatch: the ``(k, seg)`` pad-to-bucket byte
    windows every handle of the run shares.  The device→host copy is
    made ONCE, lazily, on the first ``value()``; per-op typed decoding
    is then pure host work (no per-op jitted slice/bitcast launches —
    the whole run stays inside the single counted dispatch)."""

    __slots__ = ("raws", "_host")

    def __init__(self, raws: jax.Array):
        self.raws = raws
        self._host: Optional[np.ndarray] = None

    def host(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self.raws)
        return self._host


class GetHandle(Handle):
    """Handle of a queued get; ``value()`` flushes and returns the
    typed result (identical bytes to the blocking path)."""

    def __init__(self, shape: Tuple[int, ...], dtype,
                 engine: "CommEngine"):
        super().__init__((), engine)
        self.shape = tuple(shape)
        self.dtype = dtype
        self._value: Optional[jax.Array] = None
        self._batch: Optional[_GatherBatch] = None
        self._batch_idx = 0

    def _resolve_gather(self, batch: _GatherBatch, idx: int) -> None:
        self._batch = batch
        self._batch_idx = idx
        self._resolve((batch.raws,))

    def value(self) -> jax.Array:
        self.wait()
        if self._value is None and self._batch is not None:
            self._value = jnp.asarray(_host_decode(
                self._batch.host()[self._batch_idx], self.shape,
                self.dtype))
        if self._value is None:
            raise self._dropped_error()
        return self._value


def dart_wait(handle: Handle) -> None:
    handle.wait()


def dart_test(handle: Handle) -> bool:
    return handle.test()


def dart_waitall(handles: Sequence[Handle]) -> None:
    # group queued handles by (engine, pool) and flush each pool's
    # UNION of target lanes once: the whole batch coalesces into the
    # minimal number of dispatches (a per-handle lane flush would split
    # it N ways for zero benefit — every listed lane completes here
    # anyway), while untargeted lanes keep accumulating their epochs
    lanes: Dict = {}
    for h in handles:
        h._check_failed()
        if not h._issued and h._engine is not None:
            key = (h._engine, getattr(h, "poolid", None))
            row = getattr(h, "row", None)
            if key not in lanes:
                lanes[key] = None if row is None else {row}
            elif lanes[key] is not None:
                if row is None:
                    lanes[key] = None        # unknown lane: whole pool
                else:
                    lanes[key].add(row)
    for (engine, poolid), rows in lanes.items():
        engine.flush(poolid, rows)
    for h in handles:
        if not h._issued and h._engine is not None:
            # The lane scan above is a racy snapshot: a concurrent
            # flusher (another thread, the progress plane) may have
            # issued this handle between the scan and here — or may
            # even have been mid-flush while we scanned, so OUR flush
            # of its lane found nothing.  Never raise off the stale
            # scan; wait() re-flushes only the handle's own lane (a
            # no-op if it was issued meanwhile, serialized by the
            # engine lock) and raises the lane-named "dropped" error
            # only when the op is truly gone from a flushed lane.
            h._check_failed()
            h.wait()
    _block_ready([a for h in handles for a in h.arrays])


def dart_testall(handles: Sequence[Handle]) -> bool:
    return all(h.test() for h in handles)


# --------------------------------------------------------------------------
# Jitted substrate kernels (the "pure MPI" ops the runtime wraps).
# Shapes are static per (nbytes,) so re-dispatches hit the jit cache.
# --------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=0, static_argnums=())
def _arena_write(arena: jax.Array, row: jax.Array, off: jax.Array,
                 payload: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice(arena, payload[None, :], (row, off))


@functools.partial(jax.jit, static_argnums=(3,))
def _arena_read(arena: jax.Array, row: jax.Array, off: jax.Array,
                nbytes: int) -> jax.Array:
    return jax.lax.dynamic_slice(arena, (row, off), (1, nbytes))[0]


# Batched (coalesced-run) dispatch goes through the shape-stable
# DispatchPlan layer instead: repro.kernels.segmented_copy buckets the
# run length and segment size to powers of two, packs rows/offs/lens/
# starts into ONE (k, 4) int32 descriptor array, and serves every epoch
# from a small cached family of compiled segmented scatter/gather
# kernels (XLA 'ref' or hand-tiled Pallas) — see CommEngine.


# --------------------------------------------------------------------------
# Global-pointer dereference (paper §IV.B.4)
# --------------------------------------------------------------------------


def deref(heap: SymmetricHeap, teams_by_slot, gptr: GlobalPtr
          ) -> Tuple[int, int, int]:
    """gptr → (poolid, row, offset).

    Collective pointers: segid is the owning team's teamlist slot; the
    absolute unitid is translated to the team-relative id, which indexes
    the team pool's rows.  The pool itself is resolved through the
    heap's :class:`~repro.core.globmem.WindowRegistry` (teamid → live
    PoolMeta) — the binding DART-MPI keeps between a team and its MPI
    window object.  Slots are reused after ``dart_team_destroy``
    (§IV.B.2) while pool ids grow monotonically, so any slot↔pool
    arithmetic would route a recreated team's pointers at a dropped (or
    worse, a foreign) pool; the registry makes the reuse case correct by
    construction.  Non-collective pointers address the WORLD pool
    directly by absolute unitid — "trivially dereferenced without the
    unit translations" (paper §IV.B.4).
    """
    if gptr.is_collective:
        team = teams_by_slot[gptr.segid]
        rel = team.myid(gptr.unitid)
        if rel < 0:
            raise KeyError(
                f"unit {gptr.unitid} is not a member of team {team.teamid}")
        meta = heap.windows.lookup(team.teamid)
        return meta.poolid, rel, gptr.addr
    return WORLD_POOLID, gptr.unitid, gptr.addr


#: poolid of the pre-reserved non-collective WORLD pool (reserved first
#: at dart_init, so it is always 0).
WORLD_POOLID = 0


# --------------------------------------------------------------------------
# The non-blocking engine: epoch-scoped pending-op queue + coalesced flush
# --------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class _PendingPut:
    poolid: int
    row: int
    off: int
    payload: np.ndarray         # 1-D uint8, host-staged at initiation
    handle: Handle
    ts: float = 0.0             # monotonic enqueue time (progress plane)
    stride: int = 0             # byte distance between strided segments
    count: int = 1              # segments (1 = contiguous)
    unit: int = -1              # absolute target unitid (fault plane)


@dataclasses.dataclass(eq=False)
class _PendingGet:
    poolid: int
    row: int
    off: int
    nbytes: int
    handle: GetHandle
    ts: float = 0.0
    stride: int = 0
    count: int = 1
    unit: int = -1


@dataclasses.dataclass(eq=False)
class _PendingAcc:
    """A queued element-wise accumulate (``MPI_Accumulate`` /
    ``MPI_Get_accumulate``): read-modify-write at the target inside
    the same epoch/flush discipline as puts.  ``fetch`` marks the
    get-accumulate form, whose handle yields the pre-update value."""
    poolid: int
    row: int
    off: int
    payload: np.ndarray         # 1-D uint8, host-staged at initiation
    op: str
    dtype: str                  # canonical dtype name (part of run key)
    fetch: bool
    handle: Handle
    ts: float = 0.0
    stride: int = 0
    count: int = 1
    unit: int = -1


def _check_strided(off: int, total: int, stride: int, count: int,
                   pool_bytes: int, what: str) -> Tuple[int, int, int]:
    """Validate a (possibly strided) op's geometry at initiation and
    return ``(seg_len, stride, count)`` normalized so contiguous ops
    are always ``(total, 0, 1)``.

    ``total`` bytes split into ``count`` equal segments placed
    ``stride`` bytes apart.  ``stride >= seg_len`` is required for
    ``count > 1``: segments of one op may never self-overlap, which is
    what licenses the vectorized unique-index scatter to treat every
    lane of a descriptor as a distinct arena byte."""
    count = int(count)
    stride = int(stride)
    if count < 1:
        raise ValueError(f"{what}: count must be >= 1, got {count}")
    if total % count:
        raise ValueError(
            f"{what}: {total} payload bytes do not split into {count} "
            "equal segments")
    seg_len = total // count
    if count == 1:
        stride = 0          # canonical contiguous form
    else:
        if stride < seg_len:
            raise ValueError(
                f"{what}: stride ({stride} B) must be >= the segment "
                f"length ({seg_len} B) — overlapping segments of one "
                "op are not addressable")
    span = off + (count - 1) * stride + seg_len if total else off
    if span > pool_bytes:
        raise ValueError(f"{what} overruns the target allocation's pool")
    return seg_len, stride, count


class CommEngine:
    """Epoch-scoped pending-op queue over a heap-state holder.

    ``holder`` is any object with a mutable ``state: HeapState``
    attribute (normally the :class:`repro.core.runtime.DartContext`).
    Ops enqueue with pointer translation + bounds checks done eagerly
    (initiation, paper DTIT); ``flush`` closes the epoch by dispatching
    coalesced runs and bumping ``epoch``.

    Instrumentation:

    * ``dispatch_count`` — jitted kernel launches issued by this engine
      (the quantity the coalescing is meant to minimize).
    * ``ops_enqueued`` / ``ops_coalesced`` — totals; ``ops_coalesced``
      counts ops that shared a dispatch with at least one neighbour.
    * ``compile_count`` / ``plan_cache_hits`` — DispatchPlan cache
      misses (each builds + compiles one bucketed kernel) vs hits.  A
      warm steady state must show hits only; tests assert
      ``compile_count`` stays flat across varying-size epochs.

    ``impl`` selects the batched-kernel implementation (matching
    :mod:`repro.kernels.ops`): ``'ref'`` = XLA segmented scatter/
    gather, ``'pallas'`` = the hand-tiled descriptor-grid kernel,
    ``'auto'`` = ref on every backend.  The Pallas kernels map the
    whole arena into VMEM and slice it at unaligned byte offsets, which
    the TPU compiler refuses, so ``impl='pallas'`` raises on a TPU
    backend and runs in interpret mode elsewhere.  Runs whose
    descriptors fail the Pallas window precondition (or that have no
    Pallas kernel: strided accumulates, fused fetches) fall back to ref
    per-dispatch, so the choice never changes semantics; every such
    fallback is counted (``impl_fallbacks``, :meth:`dispatch_stats`).

    **Thread safety**: ``lock`` (reentrant) guards ``_pending``, the
    counters, and the holder-state swap inside ``flush`` — submitters,
    waiters, and the background progress plane may run concurrently.
    External readers of ``holder.state`` (heap atomics, shm views,
    collectives) must take the same lock: the batched kernels donate
    the arena, so an unserialized raw read can observe a deleted
    buffer mid-flush.
    """

    def __init__(self, holder=None, impl: str = "auto"):
        self._holder = holder
        self._pending: List = []        # program order across pools
        #: serializes queue mutation, counters, and holder.state swaps
        #: (reentrant: flush may be re-entered from locked callers)
        self.lock = threading.RLock()
        self._on_enqueue: Optional[Callable[[], None]] = None
        self.epoch = 0
        self.dispatch_count = 0
        self.ops_enqueued = 0
        self.ops_coalesced = 0
        self.compile_count = 0
        self.plan_cache_hits = 0
        #: one-sided run dispatches by the kernel impl that served them
        self.impl_dispatches: Dict[str, int] = {"ref": 0, "pallas": 0}
        #: runs of a 'pallas' engine served by 'ref' instead
        self.impl_fallbacks = 0
        # -- shm plane (repro.core.shm; docs/API.md "Shared-memory
        # plane") ------------------------------------------------------
        #: locked host-side writes routed through the shm window (each
        #: one a put that cost ZERO jitted dispatches)
        self.shm_puts = 0
        #: collectives served as memcpy loops through the shm window
        self.shm_collective_ops = 0
        #: poolid -> jitted READ outputs (gather / fetch-accumulate
        #: batches) dispatched against that pool's arena and possibly
        #: still in flight.  An in-place shm write must not mutate an
        #: arena a dispatched-but-unmaterialized read is still sourcing
        #: from, so the shm plane blocks + clears a pool's fences
        #: before writing (_drain_read_fences).  Bounded: draining
        #: clears, and the recorder caps the per-pool backlog.
        self._read_fences: Dict[int, List[jax.Array]] = {}
        # -- fault plane (docs/API.md "Failure model & fault plane") ----
        #: attached injector (None = fault-free: zero-overhead dispatch)
        self.faults: Optional[FaultPlane] = None
        #: absolute unitids declared dead — enqueues fail fast
        self.dead_units: Set[int] = set()
        #: (pool, row) -> the DartError that killed the lane; enqueues
        #: to a failed lane fail fast until clear_lane()
        self.failed_lanes: Dict[Tuple[int, int], DartError] = {}
        # retry/deadline knobs (DartConfig overrides these defaults)
        self.retry_limit = 3            # retries after the first attempt
        self.retry_base_s = 0.001       # backoff = base * 2^retry
        self.retry_max_s = 0.05         # backoff cap
        self.flush_deadline_s: Optional[float] = None   # None = no deadline
        # deterministic jitter stream (differential chaos replays need
        # the backoff schedule reproducible, like everything else)
        self._retry_rng = random.Random(0xDA27)
        # fault counters (fault_stats())
        self.retries = 0
        self.retries_exhausted = 0
        self.flush_timeouts = 0
        self.at_most_once_aborts = 0
        self.failed_runs = 0
        self.enqueue_rejections = 0
        self.impl = impl

    @property
    def impl(self) -> str:
        return self._impl

    @impl.setter
    def impl(self, impl: str) -> None:
        if impl == "auto":
            impl = "ref"
        if impl not in ("ref", "pallas"):
            raise ValueError(f"unknown engine impl {impl!r} "
                             "(expected 'ref', 'pallas' or 'auto')")
        if impl == "pallas" and jax.default_backend() == "tpu":
            raise NotImplementedError(
                "impl='pallas' does not compile on TPU: the segmented-"
                "copy kernels map the whole arena into VMEM and slice it "
                "at unaligned byte offsets; use impl='ref' (or 'auto')")
        self._impl = impl

    def bind(self, holder) -> None:
        self._holder = holder

    # -- fault plane -----------------------------------------------------
    def attach_faults(self, plane: Optional[FaultPlane]) -> None:
        """Attach (or detach, with None) a fault injector.  With no
        plane attached the dispatch path takes the historical zero-
        overhead route — no gates, no retry loop."""
        with self.lock:
            self.faults = plane

    def mark_unit_dead(self, unit: int, reason: str = "") -> int:
        """Declare an absolute unit dead: every op queued against it
        fails with :class:`UnitFailedError` and subsequent enqueues to
        it fail fast.  Surviving lanes are untouched — their queued
        epochs keep flushing.  Returns the number of queued ops
        doomed."""
        with self.lock:
            return self._mark_unit_dead_locked(unit, reason)

    def _mark_unit_dead_locked(self, unit: int, reason: str = "") -> int:
        if unit in self.dead_units:
            return 0
        self.dead_units.add(unit)
        doomed = [op for op in self._pending
                  if getattr(op, "unit", -1) == unit]
        if doomed:
            self._pending = [op for op in self._pending
                             if getattr(op, "unit", -1) != unit]
            err = UnitFailedError(
                f"unit {unit} declared dead"
                f"{' (' + reason + ')' if reason else ''} with this op "
                "still queued")
            err.unit = unit
            for op in doomed:
                op.handle._fail(err)
        return len(doomed)

    def revive_unit(self, unit: int) -> None:
        """Clear a unit's dead mark (elastic re-admission); already-
        failed handles stay failed."""
        with self.lock:
            self.dead_units.discard(unit)

    def clear_lane(self, poolid: int, row: int) -> Optional[DartError]:
        """Clear a failed lane so new enqueues flow again; returns the
        error the lane carried (None if it was not failed)."""
        with self.lock:
            return self.failed_lanes.pop((poolid, row), None)

    def _precheck_enqueue(self, poolid: int, row: int,
                          unit: int) -> None:
        """Enqueue-boundary fault hook + fail-fast checks.  Called
        under the engine lock before appending a pending op: polls the
        injector's poison/unit-death schedule, then rejects ops bound
        for dead units or failed lanes with the recorded typed error."""
        if self.faults is not None:
            for spec in self.faults.poll_enqueue(poolid, row, unit):
                if spec.kind == "unit_dead":
                    dead = unit if spec.unit is None else spec.unit
                    self._mark_unit_dead_locked(dead,
                                                reason="fault injection")
                else:                                   # poison
                    err = DartError(
                        f"lane (pool {poolid}, row {row}) poisoned by "
                        "fault injection")
                    err.poolid, err.row = poolid, row
                    self.failed_lanes[(poolid, row)] = err
        if unit in self.dead_units:
            self.enqueue_rejections += 1
            err = UnitFailedError(
                f"unit {unit} is dead; op rejected at enqueue "
                f"(lane: pool {poolid}, row {row})")
            err.unit, err.poolid, err.row = unit, poolid, row
            raise err
        lane_err = self.failed_lanes.get((poolid, row))
        if lane_err is not None:
            self.enqueue_rejections += 1
            raise lane_err

    def _check_lane_live(self, poolid: int, row: int, unit: int) -> None:
        """Passive (no injector poll) dead-unit / failed-lane fail-fast
        — the shm plane re-checks a lane AFTER its ordering flush ran:
        if a queued op on the lane just failed, the host write behind
        it must not apply (program order), but the op already paid its
        one ``poll_enqueue`` in :meth:`_precheck_enqueue`."""
        if unit in self.dead_units:
            self.enqueue_rejections += 1
            err = UnitFailedError(
                f"unit {unit} is dead; shm write rejected "
                f"(lane: pool {poolid}, row {row})")
            err.unit, err.poolid, err.row = unit, poolid, row
            raise err
        lane_err = self.failed_lanes.get((poolid, row))
        if lane_err is not None:
            self.enqueue_rejections += 1
            raise lane_err

    # -- shm-plane read fences ------------------------------------------

    def _record_read_fence(self, poolid: int, arr) -> None:
        """Under the engine lock: remember a jitted read's output so an
        shm write to the pool can block on it before mutating the
        arena in place.  Caps the backlog (pure-engine workloads never
        drain) by blocking + dropping the oldest entries."""
        fences = self._read_fences.setdefault(poolid, [])
        fences.append(arr)
        if len(fences) > 64:
            drop = fences[: len(fences) - 64]
            del fences[: len(fences) - 64]
            _block_ready(drop)

    def _drain_read_fences(self, poolid: int) -> None:
        """Under the engine lock: block until every recorded jitted
        read of the pool's arena has materialized, then forget them —
        after this an in-place host write cannot race a reader."""
        fences = self._read_fences.pop(poolid, None)
        if fences:
            _block_ready(fences)

    def fault_stats(self) -> Dict[str, object]:
        """Process-wide fault counters: the engine's retry/abort/
        rejection totals plus (when attached) the injector's own."""
        with self.lock:
            s: Dict[str, object] = {
                "retries": self.retries,
                "retries_exhausted": self.retries_exhausted,
                "flush_timeouts": self.flush_timeouts,
                "at_most_once_aborts": self.at_most_once_aborts,
                "failed_runs": self.failed_runs,
                "enqueue_rejections": self.enqueue_rejections,
                "dead_units": sorted(self.dead_units),
                "failed_lanes": sorted(self.failed_lanes),
            }
            plane = self.faults
        if plane is not None:
            s["injector"] = plane.stats()
        return s

    def dispatch_stats(self) -> Dict[str, object]:
        """Which kernels served the one-sided runs: the engine impl,
        dispatches per impl, Pallas→ref fallbacks, and the plan cache's
        compile/hit counts."""
        with self.lock:
            return {
                "impl": self.impl,
                "dispatches": dict(self.impl_dispatches),
                "impl_fallbacks": self.impl_fallbacks,
                "compile_count": self.compile_count,
                "plan_cache_hits": self.plan_cache_hits,
            }

    def set_progress_notifier(self, cb: Optional[Callable[[], None]]
                              ) -> None:
        """Register (or clear) the enqueue callback the progress plane
        uses to wake its drain thread.  Called OUTSIDE the engine lock
        so the plane's condition variable never nests inside it."""
        self._on_enqueue = cb

    def _notify_enqueue(self) -> None:
        cb = self._on_enqueue
        if cb is not None:
            cb()

    def _note_plan(self, hit: bool) -> None:
        if hit:
            self.plan_cache_hits += 1
        else:
            self.compile_count += 1

    def _pick_impl(self, desc: np.ndarray, seg: int, pool_bytes: int,
                   has_pallas: bool = True) -> str:
        """Kernel impl for one run, counted: a 'pallas' engine serves a
        run with ref (and counts a fallback) when the run has no Pallas
        kernel or fails the window precondition."""
        impl = "ref"
        if self.impl == "pallas":
            if has_pallas and _sc.pallas_ok(desc, seg, pool_bytes):
                impl = "pallas"
            else:
                self.impl_fallbacks += 1
        self.impl_dispatches[impl] += 1
        return impl

    # -- enqueue (initiation) -------------------------------------------
    def put(self, heap: SymmetricHeap, teams_by_slot, gptr: GlobalPtr,
            value, *, stride: int = 0, count: int = 1) -> Handle:
        """Queue a put of the value's bytes at the target.  With
        ``count > 1`` the payload splits into ``count`` equal segments
        landing ``stride`` bytes apart (a strided run — ONE descriptor,
        ONE dispatch share, never one op per segment)."""
        poolid, row, off = deref(heap, teams_by_slot, gptr)
        payload = _to_host_bytes(value)
        stride, count = self._check_geom(
            "put", heap, poolid, off, int(payload.size), stride, count)
        h = Handle((), engine=self)
        h.poolid = poolid
        h.row = row
        with self.lock:
            self._precheck_enqueue(poolid, row, gptr.unitid)
            self._pending.append(_PendingPut(poolid, row, off, payload,
                                             h, time.monotonic(),
                                             stride=stride, count=count,
                                             unit=gptr.unitid))
            self.ops_enqueued += 1
        self._notify_enqueue()
        return h

    def get(self, heap: SymmetricHeap, teams_by_slot, gptr: GlobalPtr,
            shape: Tuple[int, ...], dtype, *, stride: int = 0,
            count: int = 1) -> GetHandle:
        """Queue a get of ``shape``/``dtype`` from the target; with
        ``count > 1`` the bytes are gathered from ``count`` equal
        segments ``stride`` bytes apart and returned densely packed in
        the requested shape."""
        poolid, row, off = deref(heap, teams_by_slot, gptr)
        n = nbytes_of(shape, dtype)
        stride, count = self._check_geom(
            "get", heap, poolid, off, n, stride, count)
        h = GetHandle(shape, dtype, engine=self)
        h.poolid = poolid
        h.row = row
        with self.lock:
            self._precheck_enqueue(poolid, row, gptr.unitid)
            self._pending.append(_PendingGet(poolid, row, off, n, h,
                                             time.monotonic(),
                                             stride=stride, count=count,
                                             unit=gptr.unitid))
            self.ops_enqueued += 1
        self._notify_enqueue()
        return h

    def _check_geom(self, what: str, heap: SymmetricHeap, poolid: int,
                    off: int, total: int, stride: int, count: int
                    ) -> Tuple[int, int]:
        _, stride, count = _check_strided(
            off, total, stride, count, heap.pools[poolid].pool_bytes,
            what)
        return stride, count

    def _stage_acc(self, heap: SymmetricHeap, teams_by_slot,
                   gptr: GlobalPtr, value, op: str, stride: int,
                   count: int):
        """Shared accumulate initiation: deref + canonicalize + the
        alignment/bounds checks the RMW kernels rely on."""
        if op not in _sc.REDUCE_OPS:
            raise ValueError(f"unknown reduction op {op!r} "
                             f"(supported: {sorted(_sc.REDUCE_OPS)})")
        poolid, row, off = deref(heap, teams_by_slot, gptr)
        arr = np.asarray(value)
        canon = jax.dtypes.canonicalize_dtype(arr.dtype)
        if arr.dtype != canon:
            arr = arr.astype(canon)
        dt = jnp.dtype(canon)
        payload = _to_host_bytes(arr)     # same staging rule as puts
        pool_bytes = heap.pools[poolid].pool_bytes
        if off % dt.itemsize or pool_bytes % dt.itemsize:
            raise ValueError(
                f"accumulate of {dt} needs an element-aligned offset "
                f"and pool (off={off}, pool_bytes={pool_bytes})")
        seg_len, stride, count = _check_strided(
            off, int(payload.size), stride, count, pool_bytes,
            "accumulate")
        if seg_len % dt.itemsize or stride % dt.itemsize:
            raise ValueError(
                f"strided accumulate of {dt} needs element-aligned "
                f"segment length and stride (seg={seg_len}, "
                f"stride={stride})")
        return poolid, row, off, arr, payload, dt, stride, count

    def accumulate(self, heap: SymmetricHeap, teams_by_slot,
                   gptr: GlobalPtr, value, op: str = "sum", *,
                   stride: int = 0, count: int = 1) -> Handle:
        """Queued element-wise accumulate at the target
        (``MPI_Accumulate``): enqueues like ``put``; same-op runs
        coalesce into one segmented read-modify-write dispatch at
        flush — even overlapping ones (the ops commute), while
        mixed-op or accumulate-vs-put overlap splits the run in queue
        order (last-writer-wins preserved run-by-run)."""
        poolid, row, off, _, payload, dt, stride, count = self._stage_acc(
            heap, teams_by_slot, gptr, value, op, stride, count)
        h = Handle((), engine=self)
        h.poolid = poolid
        h.row = row
        with self.lock:
            self._precheck_enqueue(poolid, row, gptr.unitid)
            self._pending.append(_PendingAcc(poolid, row, off, payload,
                                             op, str(dt), False, h,
                                             time.monotonic(),
                                             stride=stride, count=count,
                                             unit=gptr.unitid))
            self.ops_enqueued += 1
        self._notify_enqueue()
        return h

    def get_accumulate(self, heap: SymmetricHeap, teams_by_slot,
                       gptr: GlobalPtr, value, op: str = "sum", *,
                       stride: int = 0, count: int = 1) -> GetHandle:
        """Queued fetch-and-accumulate (``MPI_Get_accumulate``):
        ``handle.value()`` flushes and yields the target's value from
        *before* this op applied.  Byte-disjoint same-op fetches share
        one fused dispatch; overlap splits the run so every fetched
        value matches the sequential order."""
        poolid, row, off, arr, payload, dt, stride, count = self._stage_acc(
            heap, teams_by_slot, gptr, value, op, stride, count)
        h = GetHandle(arr.shape, dt, engine=self)
        h.poolid = poolid
        h.row = row
        with self.lock:
            self._precheck_enqueue(poolid, row, gptr.unitid)
            self._pending.append(_PendingAcc(poolid, row, off, payload,
                                             op, str(dt), True, h,
                                             time.monotonic(),
                                             stride=stride, count=count,
                                             unit=gptr.unitid))
            self.ops_enqueued += 1
        self._notify_enqueue()
        return h

    def pending_ops(self, poolid: Optional[int] = None,
                    row: Optional[int] = None) -> int:
        with self.lock:
            if poolid is None:
                return len(self._pending)
            return sum(1 for op in self._pending if op.poolid == poolid
                       and (row is None or op.row == row))

    def lane_stats(self) -> Dict[Tuple[int, int], Tuple[int, int, float]]:
        """Snapshot of the pending queue grouped by ``(pool, row)``
        lane: ``{lane: (ops, bytes, oldest_enqueue_ts)}``.  The
        progress plane's watermark/idle-deadline decisions key off
        this; ops are in queue order, so the first op seen per lane is
        its oldest."""
        with self.lock:
            stats: Dict[Tuple[int, int], List] = {}
            for op in self._pending:
                key = (op.poolid, op.row)
                n = _op_nbytes(op)
                s = stats.get(key)
                if s is None:
                    stats[key] = [1, n, op.ts]
                else:
                    s[0] += 1
                    s[1] += n
            return {k: (v[0], v[1], v[2]) for k, v in stats.items()}

    # -- flush (epoch close) --------------------------------------------
    def flush(self, poolid: Optional[int] = None,
              row=None) -> HeapState:
        """Dispatch pending ops in program order: all of them, one
        pool's, or — the ``MPI_Win_flush_local(rank, win)`` analogue —
        one ``(pool, row)`` target lane (``row`` may also be a
        collection of rows: the union of lanes flushes as one epoch, so
        a batch spanning targets still coalesces).

        Runs of same-pool ops of one kind are coalesced into one batched
        jitted dispatch; mixed payload sizes share a dispatch when their
        byte ranges are disjoint (:func:`_coalesced_runs`).  Ops on
        distinct pools touch distinct arrays, and ops on distinct rows
        of one pool touch disjoint per-unit partitions, so a per-pool or
        per-target flush cannot reorder visible effects.

        The whole epoch close — queue selection, dispatch (which
        donates the arenas), handle resolution, and the holder-state
        swap — runs under the engine lock, so concurrent flushes
        serialize and no thread can observe a half-donated state.

        **Failure isolation** (docs/API.md "Failure model"): a run
        whose dispatch fails terminally (retries exhausted, deadline,
        at-most-once abort) fails *its own* handles with the typed
        error and marks its lanes failed — later ops on those lanes in
        this epoch fail too (program order: op N dropped ⇒ op N+1 must
        not apply), while runs on surviving lanes keep dispatching.
        ``flush`` itself never raises for an injected fault; waiters
        see the error through ``wait()``/``test()``.
        """
        with self.lock:
            if poolid is None:
                todo, rest = self._pending, []
            else:
                rows = (None if row is None else
                        set(row) if isinstance(row, (set, frozenset,
                                                     list, tuple))
                        else {row})

                def _sel(op):
                    return op.poolid == poolid and (rows is None
                                                    or op.row in rows)
                todo = [op for op in self._pending if _sel(op)]
                rest = [op for op in self._pending if not _sel(op)]
            if not todo:
                return self._holder.state
            state = copy_state(self._holder.state)
            failed_now: Set[Tuple[int, int]] = set()
            for run, disjoint in _coalesced_runs(todo):
                pid = run[0].poolid
                if failed_now:
                    # program order on a lane that just failed: fail
                    # the lane's later ops instead of dispatching them
                    # past the hole the dropped run left
                    live = []
                    for op in run:
                        lane = (op.poolid, op.row)
                        if lane in failed_now:
                            op.handle._fail(self.failed_lanes[lane])
                        else:
                            live.append(op)
                    if not live:
                        continue
                    run = live
                try:
                    if isinstance(run[0], _PendingPut):
                        cell = {"arena": state[pid]}

                        def _put(cell=cell, run=run, disjoint=disjoint):
                            cell["arena"] = self._dispatch_put_run(
                                cell["arena"], run, disjoint)
                        try:
                            self._guarded("put", run, _put,
                                          retryable_post=True)
                        finally:
                            state[pid] = cell["arena"]
                        for op in run:
                            op.handle._resolve((state[pid],))
                    elif isinstance(run[0], _PendingAcc):
                        cell = {"arena": state[pid]}

                        def _acc(cell=cell, run=run, disjoint=disjoint):
                            cell["arena"] = self._dispatch_acc_run(
                                cell["arena"], run, disjoint)
                        try:
                            # at-most-once: a post-dispatch fault on an
                            # RMW run must never re-issue
                            self._guarded("gacc" if run[0].fetch
                                          else "acc", run, _acc,
                                          retryable_post=False)
                        finally:
                            state[pid] = cell["arena"]
                    else:
                        def _get(run=run, arena=state[pid]):
                            self._dispatch_get_run(arena, run)
                        self._guarded("get", run, _get,
                                      retryable_post=True)
                except DartError as e:
                    self.failed_runs += 1
                    lanes = {(op.poolid, op.row) for op in run}
                    for op in run:
                        op.handle._fail(e)
                    for lane in lanes:
                        self.failed_lanes[lane] = e
                    failed_now |= lanes
            self._pending = rest
            self._holder.state = state
            self.epoch += 1
            return state

    def _guarded(self, kind: str, run: Sequence, attempt: Callable[[], None],
                 retryable_post: bool) -> None:
        """Run one coalesced dispatch with fault gates + retry/deadline
        semantics.  ``attempt()`` performs one dispatch attempt,
        threading the arena through a caller-owned cell — critical for
        retry: the batched kernels DONATE the arena, so a retry after a
        post-dispatch fault re-applies the same packed descriptors to
        the attempt's *result* arena (idempotent for puts — the same
        bytes land at the same offsets — and for gets, which only
        read).  Accumulate runs pass ``retryable_post=False``: a fault
        after the RMW kernel ran aborts instead of re-issuing
        (at-most-once).

        Transient faults retry with exponential backoff + deterministic
        jitter up to ``retry_limit`` times, bounded by the per-flush
        ``flush_deadline_s``; exhaustion raises
        :class:`RetriesExhaustedError` / :class:`FlushTimeoutError`.
        With no injector attached this is a zero-overhead passthrough.
        """
        if self.faults is None:
            attempt()
            return
        # a coalesced run can span rows (one batched dispatch for many
        # lanes): consult the gate for EVERY distinct lane, and on a
        # terminal failure the whole run shares the dispatch's fate —
        # flush marks all its lanes failed.
        lanes = sorted({(op.poolid, op.row) for op in run})
        deadline = (None if self.flush_deadline_s is None
                    else time.monotonic() + self.flush_deadline_s)
        retries = 0
        while True:
            issued = False
            poolid, row = lanes[0]
            try:
                for poolid, row in lanes:
                    self.faults.dispatch_gate(kind, poolid, row, "pre")
                poolid, row = lanes[0]
                attempt()
                issued = True
                for poolid, row in lanes:
                    self.faults.dispatch_gate(kind, poolid, row, "post")
                return
            except TransientDispatchFault as e:
                e.poolid, e.row = poolid, row
                if issued and not retryable_post:
                    self.at_most_once_aborts += 1
                    err = DartError(
                        f"{kind} run on lane (pool {poolid}, row {row}) "
                        "faulted after dispatch; not retried "
                        "(at-most-once — re-issuing a read-modify-write "
                        "could double-apply it)")
                    err.poolid, err.row = poolid, row
                    raise err from e
                if retries >= self.retry_limit:
                    self.retries_exhausted += 1
                    err = RetriesExhaustedError(
                        f"{kind} run on lane (pool {poolid}, row {row}) "
                        f"still faulting after {retries} retries: {e}")
                    err.poolid, err.row = poolid, row
                    raise err from e
                backoff = min(self.retry_max_s,
                              self.retry_base_s * (1 << retries))
                backoff *= 0.5 + self._retry_rng.random()
                if (deadline is not None
                        and time.monotonic() + backoff > deadline):
                    self.flush_timeouts += 1
                    err = FlushTimeoutError(
                        f"flush deadline ({self.flush_deadline_s}s) "
                        f"exceeded retrying {kind} run on lane "
                        f"(pool {poolid}, row {row}): {e}")
                    err.poolid, err.row = poolid, row
                    raise err from e
                retries += 1
                self.retries += 1
                time.sleep(backoff)

    def drop_pool(self, poolid: int, reason: str = "",
                  teamid: Optional[int] = None) -> int:
        """Discard queued ops targeting ``poolid`` and fail their
        handles (the pool's window is being destroyed, so dispatching —
        or silently dropping — them would be wrong).  The failure is a
        typed :class:`~repro.core.globmem.WindowDestroyedError`
        carrying ``poolid`` (and ``teamid`` when the drop came from
        ``dart_team_destroy``).  Returns the number of ops dropped."""
        with self.lock:
            self._read_fences.pop(poolid, None)
            dropped = [op for op in self._pending if op.poolid == poolid]
            if not dropped:
                return 0
            self._pending = [op for op in self._pending
                             if op.poolid != poolid]
            err = WindowDestroyedError(
                f"window destroyed: pool {poolid} was dropped with "
                f"this op still queued"
                f"{' (' + reason + ')' if reason else ''}")
            err.poolid, err.teamid = poolid, teamid
            for op in dropped:
                op.handle._fail(err)
            return len(dropped)

    def _dispatch_put_run(self, arena: jax.Array,
                          run: Sequence[_PendingPut],
                          disjoint: bool = True) -> jax.Array:
        """One counted dispatch for the whole run: pack descriptors +
        flat payload on the host (one transfer each), then hit the
        cached bucketed plan — vectorized when the run's byte ranges
        are provably disjoint, the sequential in-order loop otherwise
        (overlapping uniform runs: last writer wins)."""
        self.dispatch_count += 1
        if len(run) > 1:
            self.ops_coalesced += len(run)
        desc, flat, seg = _sc.pack_descriptors(
            [op.row for op in run], [op.off for op in run],
            [int(op.payload.size) // op.count for op in run],
            [op.payload for op in run],
            strides=[op.stride for op in run],
            counts=[op.count for op in run])
        impl = self._pick_impl(desc, seg, int(arena.shape[1]))
        sseg, cb = (_sc.strided_buckets(desc, seg)
                    if impl == "pallas" else (None, None))
        fn, hit = _sc.scatter_plan(
            arena.shape, desc.shape[0], seg, flat.shape[0],
            ordered=not disjoint, impl=impl, sseg=sseg, cb=cb)
        self._note_plan(hit)
        return fn(arena, desc, flat)

    def _dispatch_acc_run(self, arena: jax.Array,
                          run: Sequence["_PendingAcc"],
                          disjoint: bool = True) -> jax.Array:
        """One counted dispatch for a same-(op, dtype) accumulate run:
        identity-padded descriptors + flat payload feed the segmented
        read-modify-write kernel — vectorized gather-combine-scatter
        when the run's byte ranges are provably disjoint, the ordered
        per-descriptor RMW loop otherwise (still one dispatch; the ops
        commute, so either order is the program-order result).  Fetch
        runs are byte-disjoint by the run rule and return every op's
        pre-update window from the same fused dispatch."""
        self.dispatch_count += 1
        if len(run) > 1:
            self.ops_coalesced += len(run)
        first = run[0]
        desc, flat, seg = _sc.pack_acc_descriptors(
            [op.row for op in run], [op.off for op in run],
            [int(op.payload.size) // op.count for op in run],
            [op.payload for op in run], first.op, first.dtype,
            strides=[op.stride for op in run],
            counts=[op.count for op in run])
        # strided RMW and fused fetches ride the ref kernels only: the
        # Pallas accumulate keeps its exact kb*seg identity-slot layout
        # (contiguous runs) and returns no pre-update windows
        impl = self._pick_impl(
            desc, seg, int(arena.shape[1]),
            has_pallas=not first.fetch and all(op.count == 1 for op in run))
        fn, hit = _sc.accumulate_plan(
            arena.shape, desc.shape[0], seg, flat.shape[0],
            op=first.op, dtype=first.dtype, fetch=first.fetch,
            ordered=not disjoint, impl=impl)
        self._note_plan(hit)
        if first.fetch:
            arena, old = fn(arena, desc, flat)
            batch = _GatherBatch(old)
            self._record_read_fence(first.poolid, old)
            for i, op in enumerate(run):
                op.handle._resolve_gather(batch, i)
        else:
            arena = fn(arena, desc, flat)
            for op in run:
                op.handle._resolve((arena,))
        return arena

    def _dispatch_get_run(self, arena: jax.Array,
                          run: Sequence[_PendingGet]) -> None:
        """One counted dispatch for the whole run (uniform AND mixed
        sizes): a bucketed segmented gather returns every op's
        pad-to-bucket byte window; the typed decode happens on the
        host from ONE device→host copy, shared by the run
        (:class:`_GatherBatch`) — no per-op jitted slice/bitcast
        launches after the gather."""
        self.dispatch_count += 1
        if len(run) > 1:
            self.ops_coalesced += len(run)
        desc, _, seg = _sc.pack_descriptors(
            [op.row for op in run], [op.off for op in run],
            [op.nbytes // op.count for op in run],
            strides=[op.stride for op in run],
            counts=[op.count for op in run])
        impl = self._pick_impl(desc, seg, int(arena.shape[1]))
        sseg, cb = (_sc.strided_buckets(desc, seg)
                    if impl == "pallas" else (None, None))
        fn, hit = _sc.gather_plan(
            arena.shape, desc.shape[0], seg, impl=impl, sseg=sseg,
            cb=cb)
        self._note_plan(hit)
        batch = _GatherBatch(fn(arena, desc))
        self._record_read_fence(run[0].poolid, batch.raws)
        for i, op in enumerate(run):
            op.handle._resolve_gather(batch, i)

    @contextlib.contextmanager
    def epoch_scope(self, poolid: Optional[int] = None):
        """Explicit epoch as a ``with`` block (the typed front-end's
        ``ctx.epoch()``): ops enqueued inside stay queued; leaving the
        block closes the epoch with one coalesced flush — of everything,
        or of a single pool when ``poolid`` is given.  The flush runs
        even on error so no op is silently left queued."""
        try:
            yield self
        finally:
            self.flush(poolid)

    def clear(self) -> None:
        """Drop queued ops without dispatching (dart_exit teardown)."""
        with self.lock:
            self._pending = []
            self._read_fences.clear()


def _kind_key(op) -> Tuple:
    if isinstance(op, _PendingPut):
        return ("put", op.poolid)
    if isinstance(op, _PendingAcc):
        # accumulates coalesce only with the SAME (op, dtype, fetch?):
        # a mixed-op (or mixed-dtype) overlap is not commutative, so it
        # splits the run and dispatches in queue order — exactly the
        # last-writer-wins rule puts follow
        kind = "gacc" if op.fetch else "acc"
        return (kind, op.poolid, op.op, op.dtype)
    return ("get", op.poolid)


def _op_nbytes(op) -> int:
    if isinstance(op, _PendingPut) or isinstance(op, _PendingAcc):
        return int(op.payload.size)
    return op.nbytes


def _op_span(op) -> int:
    """Bytes of the op's *covering interval* ``[off, off + span)`` —
    for a strided op this includes the gaps between segments
    (``(count-1)*stride + seg_len``), a deliberately conservative
    overlap proxy: two interleaved strided ops whose bytes never
    collide still read as overlapping, which only demotes the run to
    the ordered kernel (or splits it) — always correct, never unsafe.
    Contiguous ops: span == nbytes, the historical rule unchanged."""
    n = _op_nbytes(op)
    if op.count <= 1:
        return n
    return (op.count - 1) * op.stride + n // op.count


class _RunMeta:
    """Bookkeeping for the run currently being grown: payload sizes,
    per-row byte intervals, and whether every recorded write range is
    pairwise *disjoint* — the proof the dispatcher uses to issue the
    run as one vectorized segmented update (disjoint) instead of the
    sequential in-order loop (overlapping).

    Intervals are kept per row as a *merged* sorted disjoint set
    (parallel ``starts``/``ends`` lists), so the disjointness query is
    a bisect against at most two neighbours — O(log k) per candidate
    instead of a linear scan over every recorded op.  Only put runs
    track intervals: reads commute, so a get run never needs the
    disjointness rule (a write would split the run by kind anyway).

    The bucketed flat-index kernels never read or write outside an
    op's exact byte range (masked lanes are dropped/filled, not
    clamped), so there is no pool-headroom constraint: mixed-size runs
    coalesce anywhere in the pool, including hard against its end.
    """

    __slots__ = ("kind", "sizes", "max_n", "disjoint", "intervals")

    def __init__(self, op, n: int):
        self.kind = _kind_key(op)
        self.sizes = {n}
        self.max_n = n
        self.disjoint = True
        # row -> (starts, ends): merged, sorted, pairwise-disjoint.
        # Tracked for puts and plain accumulates (the vectorized-vs-
        # ordered dispatch proof — accumulates never *split* on
        # overlap, they just demote to the ordered RMW loop) and for
        # fetch-accumulates (whose run rule *requires* disjointness so
        # the fused read-all-then-apply-all equals sequential order).
        self.intervals: Dict[int, Tuple[List[int], List[int]]] = {}
        if self.kind[0] in ("put", "acc", "gacc"):
            self._note(op.row, op.off, op.off + _op_span(op))

    def _note(self, row: int, off: int, end: int) -> None:
        starts, ends = self.intervals.setdefault(row, ([], []))
        i = bisect.bisect_right(starts, off)
        # absorb a left neighbour that reaches (or touches) us
        if i > 0 and ends[i - 1] >= off:
            i -= 1
            off = starts[i]
            end = max(end, ends[i])
            del starts[i], ends[i]
        # absorb every following interval we now cover
        while i < len(starts) and starts[i] <= end:
            end = max(end, ends[i])
            del starts[i], ends[i]
        starts.insert(i, off)
        ends.insert(i, end)

    def _disjoint(self, op, n: int) -> bool:
        row_ivs = self.intervals.get(op.row)
        if row_ivs is None:
            return True
        starts, ends = row_ivs
        end = op.off + _op_span(op)
        i = bisect.bisect_right(starts, op.off)
        if i > 0 and ends[i - 1] > op.off:
            return False
        return not (i < len(starts) and starts[i] < end)

    def can_extend(self, op, n: int) -> bool:
        if _kind_key(op) != self.kind:
            return False
        if self.kind[0] == "acc":
            # same-(op, dtype) accumulates commute: any mix of sizes
            # and overlaps shares ONE dispatch — an overlapping
            # extension just demotes it to the ordered RMW kernel
            return True
        if self.kind[0] == "gacc":
            # fetch-accumulate: each fetched value must equal what a
            # sequential execution would read, and the fused kernel
            # reads every window before applying any op — valid only
            # while the run stays byte-disjoint; overlap splits it
            return self._disjoint(op, n)
        if self.sizes == {n}:
            # uniform run: unconditional, exactly the pre-registry rule —
            # an overlapping extension just demotes the dispatch to the
            # ordered kernel, so even overlapping ranges keep
            # last-writer-wins
            return True
        # mixed-size extension (bucketed segmented dispatch): puts
        # require byte-range disjointness — overlapping writes stay in
        # separate, sequentially dispatched runs so program order is
        # preserved; gets commute, so they coalesce unconditionally
        return self.kind[0] != "put" or self._disjoint(op, n)

    def extend(self, op, n: int) -> None:
        self.sizes.add(n)
        self.max_n = max(self.max_n, n)
        if self.kind[0] in ("put", "acc"):
            if self.disjoint and not self._disjoint(op, n):
                self.disjoint = False
            self._note(op.row, op.off, op.off + _op_span(op))
        elif self.kind[0] == "gacc":
            self._note(op.row, op.off, op.off + _op_span(op))


def _coalesced_runs(ops: Sequence) -> List[Tuple[List, bool]]:
    """Split into maximal ``(run, disjoint)`` pairs, each run sharing
    one batched dispatch.

    An op extends the current run when it has the same kind and pool
    and either (a) the same payload size as a so-far-uniform run — the
    original coalescing rule — or (b) for mixed sizes, a byte range
    *disjoint* from every write already in the run.  Overlapping
    ranges of different sizes split the run, so dispatching runs in
    queue order preserves put/put and put/get program order (last
    writer wins, reads see prior writes), exactly like the blocking
    sequence.  ``disjoint`` reports whether every write range in the
    run is pairwise disjoint — the dispatcher's license to use the
    vectorized segmented kernel instead of the ordered loop.
    """
    runs: List[List] = []
    metas: List[_RunMeta] = []
    for op in ops:
        n = _op_nbytes(op)
        if runs and metas[-1].can_extend(op, n):
            runs[-1].append(op)
            metas[-1].extend(op, n)
        else:
            runs.append([op])
            metas.append(_RunMeta(op, n))
    return [(run, meta.disjoint) for run, meta in zip(runs, metas)]


# --------------------------------------------------------------------------
# Host-plane one-sided ops (immediate / functional path)
# --------------------------------------------------------------------------


def dart_put(state: HeapState, heap: SymmetricHeap, teams_by_slot,
             gptr: GlobalPtr, value) -> Tuple[HeapState, Handle]:
    """Non-blocking one-sided put (``dart_put``, paper §III).

    Returns the updated heap state and a handle.  The write is issued
    immediately (async dispatch); completion = handle.wait()/test().
    The engine-backed path in :mod:`repro.core.runtime` defers the
    dispatch instead (queued → flush-coalesced).
    """
    poolid, row, off = deref(heap, teams_by_slot, gptr)
    payload = to_bytes(jnp.asarray(value))
    meta = heap.pools[poolid]
    if off + payload.size > meta.pool_bytes:
        raise ValueError("put overruns the target allocation's pool")
    arena = _arena_write(state[poolid], jnp.int32(row), jnp.int32(off),
                         payload)
    new_state = copy_state(state)
    new_state[poolid] = arena
    return new_state, Handle((arena,))


def dart_put_blocking(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                      gptr: GlobalPtr, value) -> HeapState:
    """Blocking put: returns after local+remote completion (paper §III)."""
    new_state, h = dart_put(state, heap, teams_by_slot, gptr, value)
    h.wait()
    return new_state


def dart_get(state: HeapState, heap: SymmetricHeap, teams_by_slot,
             gptr: GlobalPtr, shape: Tuple[int, ...], dtype
             ) -> Tuple[jax.Array, Handle]:
    """Non-blocking one-sided get: returns (value-future, handle)."""
    poolid, row, off = deref(heap, teams_by_slot, gptr)
    n = nbytes_of(shape, dtype)
    meta = heap.pools[poolid]
    if off + n > meta.pool_bytes:
        raise ValueError("get overruns the target allocation's pool")
    raw = _arena_read(state[poolid], jnp.int32(row), jnp.int32(off), n)
    value = from_bytes(raw, shape, dtype)
    return value, Handle((value,))


def dart_get_blocking(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                      gptr: GlobalPtr, shape: Tuple[int, ...], dtype
                      ) -> jax.Array:
    value, h = dart_get(state, heap, teams_by_slot, gptr, shape, dtype)
    h.wait()
    return value


# --------------------------------------------------------------------------
# Device-plane (shard_map) one-sided ops — SPMD "shmem" style.
#
# These are called from inside ``shard_map`` bodies where ``arena_row``
# is this unit's (1, pool_bytes) row of a symmetric-heap pool and
# ``axis`` is the unit axis name.  Peers are specified *statically*
# (trace-time ints) for the ppermute fast path — on TPU this lowers to
# a point-to-point ICI DMA, i.e. a true one-sided put.
# --------------------------------------------------------------------------


def shmem_put(arena_row: jax.Array, value: jax.Array, offset,
              perm: Sequence[Tuple[int, int]], axis: str) -> jax.Array:
    """Every unit sends ``value`` along ``perm``; receivers store at
    ``offset`` (same offset everywhere — the aligned/symmetric property).

    Units not appearing as a destination in ``perm`` receive zeros and
    must not be considered written (mask accordingly at the call site or
    use a complete permutation).
    """
    payload = to_bytes(value)
    moved = jax.lax.ppermute(payload, axis, perm)
    return jax.lax.dynamic_update_slice(
        arena_row, moved[None, :], (jnp.int32(0), jnp.asarray(offset, jnp.int32)))


def shmem_get(arena_row: jax.Array, offset, nbytes: int,
              perm: Sequence[Tuple[int, int]], axis: str,
              shape: Tuple[int, ...], dtype) -> jax.Array:
    """One-sided get with static peers: fetch ``nbytes`` at ``offset``
    from the unit that maps to me under ``perm`` (src, dst) pairs."""
    raw = jax.lax.dynamic_slice(
        arena_row, (jnp.int32(0), jnp.asarray(offset, jnp.int32)),
        (1, nbytes))[0]
    fetched = jax.lax.ppermute(raw, axis, perm)
    return from_bytes(fetched, shape, dtype)


def shmem_get_dynamic(arena_row: jax.Array, offset, nbytes: int,
                      src_unit: jax.Array, axis: str,
                      shape: Tuple[int, ...], dtype,
                      axis_index_groups=None) -> jax.Array:
    """Dynamic-peer get: peer id is a traced scalar.

    Lowers to all_gather + one-hot row select.  Semantically exact;
    costs a team-wide gather of the addressed window, so the static
    ``shmem_get`` / Pallas RDMA path is preferred where the pattern is
    known at trace time (documented perf note, docs/API.md).
    """
    raw = jax.lax.dynamic_slice(
        arena_row, (jnp.int32(0), jnp.asarray(offset, jnp.int32)),
        (1, nbytes))[0]
    everyone = jax.lax.all_gather(raw, axis,
                                  axis_index_groups=axis_index_groups)
    n = everyone.shape[0]
    onehot = (jnp.arange(n, dtype=jnp.int32) ==
              jnp.asarray(src_unit, jnp.int32)).astype(jnp.uint8)
    picked = jnp.einsum("n,nb->b", onehot, everyone)
    return from_bytes(picked.astype(jnp.uint8), shape, dtype)


def shmem_halo_exchange(arena_row: jax.Array, left_val: jax.Array,
                        right_val: jax.Array, left_off, right_off,
                        axis: str, n_units: int,
                        wrap: bool = False) -> jax.Array:
    """Classic PGAS halo exchange built from two one-sided puts.

    Each unit puts ``right_val`` into its right neighbour at
    ``left_off`` (it arrives as the neighbour's *left* halo) and
    ``left_val`` into its left neighbour at ``right_off``.
    """
    def ring(delta):
        pairs = []
        for i in range(n_units):
            j = i + delta
            if wrap:
                pairs.append((i, j % n_units))
            elif 0 <= j < n_units:
                pairs.append((i, j))
        return pairs

    arena_row = shmem_put(arena_row, right_val, left_off, ring(+1), axis)
    arena_row = shmem_put(arena_row, left_val, right_off, ring(-1), axis)
    return arena_row
