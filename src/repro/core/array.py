"""Typed GlobalArray front-end: a DASH-style object API over the
byte-offset DART core (docs/API.md).

The paper's DART API is deliberately C-flavored — raw 128-bit global
pointers, byte offsets, untyped put/get.  The PGAS promise ("program it
like shared memory") is delivered by the typed layer built on top, as
DASH does over DART.  :class:`GlobalArray` is that layer:

* minted by ``ctx.alloc(shape, dtype, team=...)`` / ``Team.alloc`` —
  one collective symmetric allocation, one block of ``shape`` elements
  of ``dtype`` per team member, byte layout never exposed;
* addressed NumPy-style: ``ga[unit]`` is a typed :class:`GlobalRef`
  view of that member's block, ``ga.at[unit, 3:7]`` an element run
  inside it — including strided and multi-dimensional selections like
  ``ga.at[unit, :, 2]`` (a column) or ``ga.at[unit, ::4]``, which
  lower onto ONE strided engine descriptor — each supporting
  ``.put/.get`` (blocking) and ``.put_nb/.get_nb`` (engine-queued,
  coalescing at flush);
* collective ops are typed too: ``ga.allreduce("sum")``,
  ``ga.broadcast(root)``, ``ga.gather()``, ``ga.scatter(values)``;
* ``ga.local`` reads this controller's portion through the
  ``FLAG_SHM`` / :func:`repro.core.shm.classify_locality` fast path —
  a zero-copy, zero-dispatch numpy view on host-visible arenas.

Every data-plane op lowers onto the existing :class:`CommEngine`
enqueue path — never around it — so N typed non-blocking puts still
coalesce into one jitted dispatch, and ``with ctx.epoch(): ...``
(→ :meth:`CommEngine.epoch_scope`) preserves the paper's
queued→issued→complete ladder.  The raw ``dart_*`` byte API remains
the documented substrate layer underneath (docs/API.md has the
migration table).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .globmem import nbytes_of
from .gptr import GlobalPtr
from .team import DART_TEAM_ALL

Index = Union[int, slice, Tuple[Union[int, slice], ...]]


def _element_run(shape: Tuple[int, ...], index: Index
                 ) -> Tuple[int, Tuple[int, ...], int, int, int]:
    """Translate a NumPy-style index on ``shape`` (row-major) into ONE
    strided element run:
    ``(element_offset, out_shape, seg_elems, stride_elems, count)`` —
    ``count`` segments of ``seg_elems`` consecutive elements placed
    ``stride_elems`` apart.  A contiguous selection is the degenerate
    case ``(seg_elems == prod(out_shape), stride 0, count 1)``.

    Addressability rule: after collapsing every contiguous tail
    (integer axes, size-1 slices, and slices that continue the dense
    run), at most ONE strided level may remain — that's what a single
    engine descriptor expresses.  Two or more broken levels (e.g. a
    strided slice over rows *and* a partial slice over columns of a
    3-D block) would need one descriptor per outer segment; index the
    outer level per-iteration instead.  Negative-step slices raise
    ``ValueError`` — silently reversing bytes on the wire is the kind
    of misaddressing this front-end exists to prevent.  A step larger
    than the axis extent just selects the first element (count 1), and
    an empty slice yields a zero-element run (no data moves).
    """
    if not isinstance(index, tuple):
        index = (index,)
    if len(index) > len(shape):
        raise IndexError(f"too many indices for shape {shape}")
    elem_strides = [1] * len(shape)
    for ax in range(len(shape) - 2, -1, -1):
        elem_strides[ax] = elem_strides[ax + 1] * shape[ax + 1]
    offset = 0
    out_shape = []
    # (n, pitch) per non-trivial axis: n selected elements, pitch
    # element-stride between consecutive ones (= step * axis stride)
    levels = []
    for ax, idx in enumerate(index):
        extent = shape[ax]
        if isinstance(idx, (int, np.integer)):
            i = int(idx)
            if i < 0:
                i += extent
            if not (0 <= i < extent):
                raise IndexError(
                    f"index {idx} out of range for axis {ax} (size {extent})")
            offset += i * elem_strides[ax]
        elif isinstance(idx, slice):
            if idx.step is not None and idx.step < 0:
                raise ValueError(
                    f"negative-step slice {idx!r} on axis {ax}: "
                    "reversed runs are not addressable as one-sided "
                    "transfers (read forward and reverse locally)")
            start, stop, step = idx.indices(extent)
            n = max(0, -(-(stop - start) // step))
            offset += start * elem_strides[ax]
            out_shape.append(n)
            if n != 1:
                levels.append((n, step * elem_strides[ax]))
        else:
            raise TypeError(f"unsupported index {idx!r}")
    for ax in range(len(index), len(shape)):
        out_shape.append(shape[ax])
        if shape[ax] != 1:
            levels.append((shape[ax], elem_strides[ax]))
    if 0 in out_shape:
        # empty selection: a zero-element contiguous run — callers
        # skip the wire entirely (no descriptor, no dispatch)
        return offset, tuple(out_shape), 0, 0, 1
    # collapse the dense tail: innermost levels whose pitch continues
    # the contiguous block merge into one segment of seg elements
    seg = 1
    while levels and levels[-1][1] == seg:
        seg *= levels.pop()[0]
    if not levels:
        return offset, tuple(out_shape), seg, 0, 1
    if len(levels) > 1:
        raise IndexError(
            f"index {index!r} on shape {shape} addresses "
            f"{len(levels)} strided levels; one engine descriptor "
            "carries a single (stride, count) — index the outer "
            "level per-iteration instead")
    n, pitch = levels[0]
    return offset, tuple(out_shape), seg, pitch, n


class GlobalRef:
    """A typed reference to one (possibly strided) element run on one
    unit.

    Immutable and cheap: holds (array, unit, element offset, shape)
    plus the run geometry ``(seg, stride, count)`` — ``count``
    segments of ``seg`` consecutive elements, ``stride`` elements
    apart (contiguous refs are ``count == 1``).  A matrix column, a
    tile halo, or a block-cyclic slice is therefore ONE ref lowering
    onto ONE engine descriptor, never one op per element.  Data ops
    translate to engine ops on the underlying byte pointer — the
    translation the raw API forces every caller to hand-roll.
    """

    __slots__ = ("array", "unit", "offset", "shape", "seg", "stride",
                 "count")

    def __init__(self, array: "GlobalArray", unit: int, offset: int,
                 shape: Tuple[int, ...], seg: Optional[int] = None,
                 stride: int = 0, count: int = 1):
        self.array = array
        self.unit = unit
        self.offset = offset
        self.shape = shape
        self.seg = (int(np.prod(shape, dtype=np.int64)) if seg is None
                    else seg)
        self.stride = stride
        self.count = count

    @property
    def dtype(self):
        return self.array.dtype

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))

    @property
    def gptr(self) -> GlobalPtr:
        """The substrate-layer byte pointer this ref denotes (its
        first segment's first element)."""
        return (self.array.gptr.setunit(self.unit)
                .incaddr(self.offset * self.array.itemsize))

    def _byte_geom(self) -> dict:
        """The engine kwargs of this run: stride in BYTES, count."""
        return {"stride": self.stride * self.array.itemsize,
                "count": self.count}

    def __getitem__(self, index: Index) -> "GlobalRef":
        if self.count != 1:
            raise IndexError(
                "cannot re-index a strided GlobalRef (one descriptor "
                "carries one (stride, count) level); index the parent "
                "block instead")
        off, shp, seg, stride, count = _element_run(self.shape, index)
        return GlobalRef(self.array, self.unit, self.offset + off, shp,
                         seg, stride, count)

    def _coerce(self, value) -> jax.Array:
        """``value`` as a device array of this ref's dtype and shape, in
        a ``dart.coerce`` span that counts the host->device bytes of a
        host input."""
        with tracing.span("dart.coerce") as sp:
            v = jnp.asarray(value, dtype=self.dtype)
            if sp.on and not isinstance(value, jax.Array):
                sp.add(h2d_bytes=int(v.nbytes))
            if v.shape == self.shape:
                return v
            if v.ndim == 0:
                return jnp.broadcast_to(v, self.shape)
            if v.size == int(np.prod(self.shape, dtype=np.int64)):
                return v.reshape(self.shape)
        raise ValueError(
            f"value of shape {v.shape} does not fit ref of shape "
            f"{self.shape}")

    def _empty_handle(self):
        """A born-complete Handle for zero-element refs: nothing moves,
        nothing dispatches."""
        from .onesided import Handle
        return Handle(())

    def _empty_get_handle(self):
        from .onesided import GetHandle
        h = GetHandle(self.shape, self.dtype, engine=None)
        h._value = jnp.zeros(self.shape, self.dtype)
        return h

    # -- data plane (lowers onto the CommEngine, never around it) --------
    def put(self, value) -> None:
        """Blocking put, locality-routed: SHM-writable targets take the
        zero-copy window write (no jitted dispatch); everything else is
        enqueue + flush + completion through the engine."""
        from . import runtime as rt
        if self.size == 0:
            return
        rt.dart_put_blocking(self.array.ctx, self.gptr,
                             self._coerce(value), **self._byte_geom())

    def put_nb(self, value):
        """Non-blocking put: queued on the engine; coalesces with its
        neighbours at the next epoch close.  Returns the Handle.
        Never shm-routed — a direct write would defeat the queued
        coalescing this method exists for."""
        from . import runtime as rt
        if self.size == 0:
            return self._empty_handle()
        return rt.dart_put(self.array.ctx, self.gptr,
                           self._coerce(value), **self._byte_geom())

    def get(self) -> jax.Array:
        """Blocking get, locality-routed (zero-copy on SHM_LOCAL) for
        contiguous refs; strided refs gather through the engine's one
        coalesced descriptor."""
        from . import runtime as rt
        if self.size == 0:
            return jnp.zeros(self.shape, self.dtype)
        if self.count == 1:
            return rt.dart_get_blocking(self.array.ctx, self.gptr,
                                        self.shape, self.dtype)
        val, _ = rt.dart_get(self.array.ctx, self.gptr, self.shape,
                             self.dtype, **self._byte_geom())
        return val

    def get_nb(self):
        """Non-blocking get: queued; ``handle.value()`` flushes and
        yields the typed result."""
        from . import runtime as rt
        if self.size == 0:
            return self._empty_get_handle()
        return rt.dart_get_nb(self.array.ctx, self.gptr, self.shape,
                              self.dtype, **self._byte_geom())

    # -- element-wise reductions at the target (the reduction plane) ----
    def accumulate(self, value, op: str = "sum"):
        """Non-blocking element-wise accumulate at the target (the
        ``MPI_Accumulate`` analogue): queued on the engine; consecutive
        same-``op`` accumulates coalesce into ONE read-modify-write
        dispatch at the next epoch close — overlapping runs included
        (the ops commute).  ``op`` is one of ``sum``, ``prod``,
        ``min``, ``max`` and ``bxor`` (int32/uint32 only; any other
        dtype raises ``TypeError`` here).  Traced as ``dart.accumulate``
        with its element count.  Returns the Handle."""
        from . import runtime as rt
        if self.size == 0:
            return self._empty_handle()
        with tracing.span("dart.accumulate", elems=self.size):
            return rt.dart_accumulate(self.array.ctx, self.gptr,
                                      self._coerce(value), op,
                                      **self._byte_geom())

    def add(self, value):
        """``ref.add(v)`` ≡ ``ref.accumulate(v, "sum")``."""
        return self.accumulate(value, "sum")

    def mul(self, value):
        return self.accumulate(value, "prod")

    def min(self, value):
        return self.accumulate(value, "min")

    def max(self, value):
        return self.accumulate(value, "max")

    def get_accumulate(self, value, op: str = "sum"):
        """Fetch-and-accumulate (``MPI_Get_accumulate``): applies
        ``value`` under ``op`` and returns the target's typed value
        from *before* the op, concrete (flushes this ref's lane)."""
        from . import runtime as rt
        if self.size == 0:
            return jnp.zeros(self.shape, self.dtype)
        old, _ = rt.dart_get_accumulate(self.array.ctx, self.gptr,
                                        self._coerce(value), op,
                                        **self._byte_geom())
        return old

    def flush(self) -> None:
        """Per-target flush (the ``MPI_Win_flush_local(rank, win)``
        analogue): dispatch only this unit's queued ops on the array's
        window, coalesced; other targets' queued epochs keep
        accumulating for their own flush."""
        from . import runtime as rt
        rt.dart_flush(self.array.ctx, self.array.gptr, target=self.unit)

    # -- one-sided atomics (paper §IV.B.6, typed) ------------------------
    def fetch_add(self, delta: int) -> int:
        """Atomic fetch-and-add on a single-element int32 ref (the
        typed ``dart_fetch_and_add`` / ``MPI_Fetch_and_op`` analogue);
        returns the pre-update value.  Atomic with respect to every
        other heap atomic on the context — the serving plane's
        refcount primitive.  Flushes queued ops on the heap first, so
        the read-modify-write never sees a stale cell."""
        if self.dtype != jnp.int32:
            raise TypeError(
                f"fetch_add needs an int32 ref, got {self.dtype}")
        if int(np.prod(self.shape, dtype=np.int64)) != 1:
            raise ValueError(
                f"fetch_add needs a single-element ref, got shape "
                f"{self.shape}")
        from . import atomic_ops as _ao
        return _ao.dart_fetch_and_add(self.array.ctx, self.gptr,
                                      int(delta))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        geom = ("" if self.count == 1 else
                f", seg={self.seg}, stride={self.stride}, "
                f"count={self.count}")
        return (f"GlobalRef(unit={self.unit}, offset={self.offset}, "
                f"shape={self.shape}, dtype={self.dtype}{geom})")


class _AtIndexer:
    """``ga.at[unit, <element index>]`` → :class:`GlobalRef`."""

    __slots__ = ("_array",)

    def __init__(self, array: "GlobalArray"):
        self._array = array

    def __getitem__(self, key) -> GlobalRef:
        if isinstance(key, tuple):
            unit, index = key[0], key[1:]
        else:
            unit, index = key, ()
        return self._array[unit][index]


class GlobalArray:
    """A typed, team-distributed array over one symmetric allocation.

    Each member of ``team`` owns one block of ``shape`` elements of
    ``dtype`` at the same offset in the team pool (aligned & symmetric,
    paper §III) — so any unit's block is addressable from a locally
    computed pointer, which is exactly what :class:`GlobalRef` hides.
    """

    def __init__(self, ctx, gptr: GlobalPtr, shape: Sequence[int], dtype,
                 teamid: int):
        self.ctx = ctx
        self.gptr = gptr
        self.shape = tuple(int(s) for s in shape)
        self.dtype = jnp.dtype(dtype)
        self.teamid = teamid

    # -- allocation ------------------------------------------------------
    @classmethod
    def alloc(cls, ctx, shape: Sequence[int], dtype,
              team: int = DART_TEAM_ALL, shm: bool = True) -> "GlobalArray":
        """Collective symmetric allocation, typed.

        ``shm=True`` (default) mints a ``FLAG_SHM`` pointer so, on
        host-visible arenas, blocking reads AND writes take the
        zero-copy locality fast path and the data-moving collectives
        (``broadcast``/``gather``/``scatter``) go shm-direct with zero
        jitted dispatches; pass ``shm=False`` to force everything
        through the jitted one-sided path (useful for benchmarking the
        substrate, or when a test pins engine dispatch counts).
        """
        from . import runtime as rt
        from .shm import mint_shm
        shape = tuple(int(s) for s in shape)
        g = rt.dart_team_memalloc_aligned(ctx, team,
                                          nbytes_of(shape, dtype))
        if shm:
            g = mint_shm(g)
        return cls(ctx, g, shape, dtype, team)

    def free(self) -> None:
        """Release the backing allocation (``dart_team_memfree``)."""
        from . import runtime as rt
        rt.dart_team_memfree(self.ctx, self.teamid, self.gptr)

    # -- identity --------------------------------------------------------
    @property
    def team(self):
        return self.ctx.teams[self.teamid]

    @property
    def units(self) -> Tuple[int, ...]:
        """Absolute unit ids of the owning team's members."""
        return self.team.group.members

    @property
    def team_size(self) -> int:
        return self.team.size()

    @property
    def itemsize(self) -> int:
        return self.dtype.itemsize

    @property
    def nbytes_per_unit(self) -> int:
        return nbytes_of(self.shape, self.dtype)

    def _check_unit(self, unit: int) -> int:
        unit = int(unit)
        if self.team.myid(unit) < 0:
            raise KeyError(
                f"unit {unit} is not a member of team {self.teamid} "
                f"(members {self.units})")
        return unit

    # -- addressing ------------------------------------------------------
    def __getitem__(self, unit: int) -> GlobalRef:
        """Typed view of ``unit``'s whole block."""
        return GlobalRef(self, self._check_unit(unit), 0, self.shape)

    @property
    def at(self) -> _AtIndexer:
        """Element-granular addressing: ``ga.at[unit, 3:7]`` denotes a
        contiguous run inside ``unit``'s block."""
        return _AtIndexer(self)

    # -- local (zero-copy) view -----------------------------------------
    @property
    def local(self):
        """This controller's portion — in the single-controller runtime,
        the base pointer's owning unit (the team's first member).

        Routed through :func:`repro.core.shm.classify_locality`: on a
        host-visible arena with a ``FLAG_SHM`` pointer this is a
        read-only zero-copy numpy view with **zero** jitted dispatches
        (queued writes to the pool are flushed first, so the view sees
        them); otherwise it falls back to the jitted one-sided get.
        Writes must go through ``put``/``put_nb`` so XLA dataflow stays
        authoritative.
        """
        return self.local_view(self.gptr.unitid)

    def local_view(self, unit: int):
        """Locality-routed read of any member's block (see :attr:`local`)."""
        from . import runtime as rt
        return rt.dart_get_blocking(self.ctx,
                                    self.gptr.setunit(self._check_unit(unit)),
                                    self.shape, self.dtype)

    # -- element-wise reductions at the target --------------------------
    def accumulate(self, unit: int, index, value, op: str = "sum"):
        """Non-blocking accumulate into a contiguous run of ``unit``'s
        block: ``ga.accumulate(u, slice(3, 7), v, "sum")`` ≡
        ``ga.at[u, 3:7].accumulate(v, "sum")`` (pass ``index=None``
        for the whole block).  Returns the queued Handle."""
        ref = self[unit] if index is None else self[unit][index]
        return ref.accumulate(value, op)

    # -- typed collectives ----------------------------------------------
    def allreduce(self, op: str = "sum") -> jax.Array:
        """All-reduce the per-member blocks elementwise across the team;
        every member's block is replaced by the result, which is also
        returned typed.  Shape-stable: element counts bucket to pow2
        with op-identity padding, so varying-shape loops never
        recompile after warmup."""
        from . import runtime as rt
        return rt.dart_allreduce(self.ctx, self.gptr, self.shape,
                                 self.dtype, op=op)

    def reduce(self, op: str = "sum", root: int = 0) -> jax.Array:
        """Root-taking reduce: the reduced value replaces only
        ``root``'s block; other members keep theirs.  Returns the
        reduced value."""
        from . import runtime as rt
        return rt.dart_reduce(self.ctx, self.gptr, self.shape,
                              self.dtype, op=op,
                              root=self._check_unit(root))

    def broadcast(self, root: int):
        """Broadcast ``root``'s block to every member.  Returns the
        collective's Handle (born issued).  Shm-direct (zero jitted
        dispatches) on SHM-writable pools; one jitted dispatch
        otherwise."""
        from . import runtime as rt
        return rt.dart_bcast(self.ctx,
                             self.gptr.setunit(self._check_unit(root)),
                             self.nbytes_per_unit)

    def gather(self) -> jax.Array:
        """Gather every member's block → typed ``(team_size, *shape)``
        array, in team-relative order — shm-direct (zero jitted
        dispatches) on host-visible pools, one jitted dispatch
        otherwise."""
        from . import runtime as rt
        vals, _ = rt.dart_gather_typed(self.ctx, self.gptr, self.shape,
                                       self.dtype)
        return vals

    def scatter(self, values) -> None:
        """Scatter row i of ``values`` (``(team_size, *shape)``) to the
        team's i-th member — shm-direct on SHM-writable pools, one
        jitted dispatch otherwise."""
        values = jnp.asarray(values, dtype=self.dtype)
        want = (self.team_size,) + self.shape
        if values.shape != want:
            raise ValueError(
                f"scatter values of shape {values.shape}, expected {want}")
        from . import runtime as rt
        rt.dart_scatter_typed(self.ctx, self.gptr, values).wait()

    # -- epochs ----------------------------------------------------------
    def flush(self, unit: Optional[int] = None) -> None:
        """Flush this array's window: all queued ops on its pool, or —
        with ``unit`` — only that target's lane (``ga.flush(u)`` ≡
        ``ga[u].flush()``)."""
        from . import runtime as rt
        if unit is None:
            rt.dart_flush(self.ctx, self.gptr)
        else:
            rt.dart_flush(self.ctx, self.gptr,
                          target=self._check_unit(unit))

    def epoch(self):
        """Epoch scoped to this array's pool: non-blocking ops enqueued
        inside coalesce into one flush on exit (other pools keep
        accumulating)."""
        return self.ctx.epoch(self.gptr)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"GlobalArray(shape={self.shape}, dtype={self.dtype}, "
                f"team={self.teamid}, units={self.units})")
