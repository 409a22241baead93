"""DART collective communication (paper §III, §IV.B.5).

The paper implements DART collectives "straightforwardly by using the
MPI-3 collective counterparts", after resolving the team → communicator
translation.  We do the same against the JAX substrate:

* **Device plane** (inside ``shard_map``): team → ``axis_index_groups``
  (the JAX analogue of a sub-communicator).  ``psum`` lacks group
  support on some backends, so the team all-reduce is decomposed into
  reduce-scatter + all-gather — the canonical ring decomposition, and
  incidentally the DART-style construction of a collective from
  one-sided phases.

* **Host plane**: collectives over heap segments (bcast/scatter/gather)
  are expressed as row motions on the arena via jitted gather/scatter.

``dart_barrier`` on the host plane is a device-queue fence; inside a
step it is a zero-payload psum (token barrier).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import segmented_copy as _sc

from .globmem import (HeapState, SymmetricHeap, copy_state,
                      from_bytes, nbytes_of, to_bytes)
from .gptr import GlobalPtr
from .onesided import Handle, deref

# --------------------------------------------------------------------------
# Device-plane team collectives (call inside shard_map)
# --------------------------------------------------------------------------


def team_all_gather(x, axis: str, groups=None, tiled: bool = False):
    return jax.lax.all_gather(x, axis, axis_index_groups=groups, tiled=tiled)


def team_reduce_scatter(x, axis: str, groups=None):
    return jax.lax.psum_scatter(x, axis, axis_index_groups=groups,
                                tiled=True)


def team_psum(x, axis: str, groups=None):
    """Team all-reduce.

    With groups: reduce-scatter + all-gather (RS+AG) over a padded
    leading axis — ``lax.psum`` does not accept ``axis_index_groups`` on
    the CPU/interpret path.  Without groups: plain psum.
    """
    if groups is None:
        return jax.lax.psum(x, axis)
    g = len(groups[0])
    flat = x.reshape(-1)
    pad = (-flat.size) % g
    flat = jnp.pad(flat, (0, pad))
    scat = jax.lax.psum_scatter(flat, axis, axis_index_groups=groups,
                                tiled=True)
    full = jax.lax.all_gather(scat, axis, axis_index_groups=groups,
                              tiled=True)
    if pad:
        full = full[:-pad]
    return full.reshape(x.shape)


def team_pmax(x, axis: str, groups=None):
    return jax.lax.pmax(x, axis, axis_index_groups=groups)


def team_all_to_all(x, axis: str, split_axis: int, concat_axis: int,
                    groups=None):
    return jax.lax.all_to_all(x, axis, split_axis, concat_axis,
                              axis_index_groups=groups, tiled=True)


def team_broadcast(x, axis: str, root_rel: int, groups=None):
    """Broadcast from the team-relative root: all_gather + static pick."""
    g = jax.lax.all_gather(x, axis, axis_index_groups=groups)
    return jax.lax.index_in_dim(g, root_rel, axis=0, keepdims=False)


def team_barrier(axis: str, groups=None):
    """Token barrier: a zero-payload team reduction."""
    return team_psum(jnp.zeros((), jnp.int32) + 1, axis, groups)


# --------------------------------------------------------------------------
# Host-plane collectives over heap segments
#
# These share the engine's batched dispatch discipline: each collective
# is ONE jitted kernel over the addressed segment (not an eager op per
# lax call), and when a CommEngine is passed, the target pool's pending
# one-sided ops are flushed first (queued puts are ordered *before* the
# collective, matching the paper's epoch semantics) and the kernel
# launch is counted in engine.dispatch_count.
#
# The kernels follow the engine's shape-stable DispatchPlan discipline
# (repro.kernels.segmented_copy): segment bytes / element counts are
# bucketed to powers of two and the true length travels as a traced
# scalar in a packed int32 params array, so varying collective sizes
# hit a small cached kernel family instead of recompiling per size.
# Masked flat-index addressing (scatter mode='drop', gather
# mode='fill') keeps padded lanes from ever touching bytes outside the
# addressed segment.  Donation is ENGINE-GATED: with an engine the
# arena is holder-owned and donated; on the functional engine=None
# path the caller keeps its snapshot, so the kernels must not donate
# (previously _seg_bcast/_seg_scatter/_seg_scatter_typed donated
# unconditionally and deleted the caller's retained state —
# _seg_allreduce already documented why that is wrong).
# --------------------------------------------------------------------------


def _row_lane_dst(R: int, P: int, off, lane, valid):
    """(R, seg) flat arena positions for every row's segment lane;
    masked lanes get distinct out-of-range markers (dropped)."""
    rows = jnp.arange(R, dtype=jnp.int32)[:, None]
    seg = lane.shape[0]
    return jnp.where(valid[None, :], rows * P + off + lane[None, :],
                     R * P + rows * seg + lane[None, :])


def _donate(donate: bool):
    return (0,) if donate else ()


def _bcast_plan(arena_shape, seg: int, donate: bool):
    _sc.check_flat_addressable(arena_shape)
    key = ("coll_bcast", arena_shape, seg, donate)

    def build():
        def fn(arena, params):          # params = [root_row, off, nbytes]
            R, P = arena.shape
            root, off, n = params[0], params[1], params[2]
            lane = jnp.arange(seg, dtype=jnp.int32)
            valid = lane < n
            src = jnp.take(arena.reshape(-1),
                           jnp.where(valid, root * P + off + lane, R * P),
                           mode="fill", fill_value=0)
            dst = _row_lane_dst(R, P, off, lane, valid)
            out = arena.reshape(-1).at[dst.reshape(-1)].set(
                jnp.broadcast_to(src, (R, seg)).reshape(-1),
                mode="drop", unique_indices=True)
            return out.reshape(R, P)
        return jax.jit(fn, donate_argnums=_donate(donate))

    return _sc.cached_plan(key, build)


def _row_gather_plan(arena_shape, seg: int):
    _sc.check_flat_addressable(arena_shape)
    key = ("coll_gather", arena_shape, seg)

    def build():
        def fn(arena, params):          # params = [off, nbytes]
            R, P = arena.shape
            off, n = params[0], params[1]
            lane = jnp.arange(seg, dtype=jnp.int32)
            valid = lane < n
            rows = jnp.arange(R, dtype=jnp.int32)[:, None]
            idx = jnp.where(valid[None, :],
                            rows * P + off + lane[None, :], R * P)
            return jnp.take(arena.reshape(-1), idx, mode="fill",
                            fill_value=0)
        return jax.jit(fn)

    return _sc.cached_plan(key, build)


def _row_scatter_plan(arena_shape, seg: int, donate: bool):
    _sc.check_flat_addressable(arena_shape)
    key = ("coll_scatter", arena_shape, seg, donate)

    def build():
        def fn(arena, params, values):  # values (R, seg) uint8 padded
            R, P = arena.shape
            off, n = params[0], params[1]
            lane = jnp.arange(seg, dtype=jnp.int32)
            dst = _row_lane_dst(R, P, off, lane, lane < n)
            out = arena.reshape(-1).at[dst.reshape(-1)].set(
                values.reshape(-1), mode="drop", unique_indices=True)
            return out.reshape(R, P)
        return jax.jit(fn, donate_argnums=_donate(donate))

    return _sc.cached_plan(key, build)


def _row_scatter_typed_plan(arena_shape, dtype, eb: int, donate: bool):
    _sc.check_flat_addressable(arena_shape)
    key = ("coll_scatter_typed", arena_shape, str(jnp.dtype(dtype)), eb,
           donate)

    def build():
        def fn(arena, params, values):  # values (R, eb) dtype padded
            R, P = arena.shape
            off, n = params[0], params[1]
            rows = jax.vmap(to_bytes)(values)          # (R, eb*itemsize)
            seg = rows.shape[1]
            lane = jnp.arange(seg, dtype=jnp.int32)
            dst = _row_lane_dst(R, P, off, lane, lane < n)
            out = arena.reshape(-1).at[dst.reshape(-1)].set(
                rows.reshape(-1), mode="drop", unique_indices=True)
            return out.reshape(R, P)
        return jax.jit(fn, donate_argnums=_donate(donate))

    return _sc.cached_plan(key, build)


def _row_gather_typed_plan(arena_shape, dtype, eb: int):
    dt = jnp.dtype(dtype)
    _sc.check_flat_addressable(arena_shape)
    key = ("coll_gather_typed", arena_shape, str(dt), eb)

    def build():
        def fn(arena, params):          # params = [off, nbytes]
            R, P = arena.shape
            off, n = params[0], params[1]
            seg = eb * dt.itemsize
            lane = jnp.arange(seg, dtype=jnp.int32)
            valid = lane < n
            rows = jnp.arange(R, dtype=jnp.int32)[:, None]
            idx = jnp.where(valid[None, :],
                            rows * P + off + lane[None, :], R * P)
            raw = jnp.take(arena.reshape(-1), idx, mode="fill",
                           fill_value=0)
            return jax.vmap(lambda r: from_bytes(r, (eb,), dt))(raw)
        return jax.jit(fn)

    return _sc.cached_plan(key, build)


def _bxor_reduce(vals, axis):
    return jax.lax.reduce(vals, np.asarray(0, vals.dtype),
                          jax.lax.bitwise_xor, (axis,))


_REDUCERS = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min,
             "prod": jnp.prod, "bxor": _bxor_reduce}


def _reduce_plan(arena_shape, eb: int, dtype, op: str, root: bool,
                 donate: bool):
    """Shape-stable reduce/allreduce (the reduction plane's collective
    half): the element count is bucketed to ``eb`` (pow2, floor 4) and
    masked element lanes read as the **op identity**
    (:func:`repro.kernels.segmented_copy.op_identity` — 0/1/±inf by
    op), so the cross-row reduction of a padded lane is itself the
    identity and the kernel's output shape is a pure function of the
    bucket.  Varying (shape, dtype, op) steady-state loops therefore
    hit a small cached family — the kernel is keyed on ``eb``, never
    the exact shape; the true byte length travels as a traced scalar
    and the padded reduced vector is trimmed host-side.  ``root``
    selects the root-taking reduce (write-back to one row) vs the
    allreduce (write-back to every row).  Donation is engine-gated
    like the other collectives: with an engine the arena is
    holder-owned and donated (the write-back is in-place, no
    arena-sized copy); on the functional ``engine=None`` path the
    caller keeps its snapshot."""
    dt = jnp.dtype(dtype)
    _sc.check_flat_addressable(arena_shape)
    key = ("coll_reduce", arena_shape, eb, str(dt), op, root, donate)

    def build():
        def fn(arena, params):       # params = [off, nbytes, root_row]
            R, P = arena.shape
            off, n, root_row = params[0], params[1], params[2]
            isz = dt.itemsize
            seg = eb * isz
            blane = jnp.arange(seg, dtype=jnp.int32)
            bvalid = blane < n
            rows = jnp.arange(R, dtype=jnp.int32)[:, None]
            idx = jnp.where(bvalid[None, :],
                            rows * P + off + blane[None, :], R * P)
            raw = jnp.take(arena.reshape(-1), idx, mode="fill",
                           fill_value=0)                      # (R, seg)
            vals = jax.vmap(lambda r: from_bytes(r, (eb,), dt))(raw)
            evalid = jnp.arange(eb, dtype=jnp.int32) * isz < n
            ident = jnp.asarray(_sc.op_identity(op, dt))
            vals = jnp.where(evalid[None, :], vals, ident)
            red = _REDUCERS[op](vals, axis=0)                 # (eb,)
            out_b = to_bytes(red)                             # (seg,)
            if root:
                dst = jnp.where(bvalid, root_row * P + off + blane,
                                R * P + blane)
                payload = out_b
            else:
                dst = _row_lane_dst(R, P, off, blane, bvalid).reshape(-1)
                payload = jnp.broadcast_to(out_b, (R, seg)).reshape(-1)
            arena2 = arena.reshape(-1).at[dst].set(
                payload, mode="drop", unique_indices=True).reshape(R, P)
            return arena2, red
        return jax.jit(fn, donate_argnums=_donate(donate))

    return _sc.cached_plan(key, build)


def _pre_collective(state, poolid, engine):
    """Flush queued one-sided ops on the pool, count our dispatch.

    With an engine, the collective operates on the engine holder's
    freshly flushed state — the caller-passed ``state`` is superseded
    (runtime callers always pass ``ctx.state`` where ``ctx`` is the
    holder).  Pass ``engine=None`` to thread state purely functionally.

    Routing note: the runtime wrappers (``runtime.dart_bcast`` etc.)
    only reach this module's data movers when the shm-direct path
    declined — FLAG_SHM pointers on host-writable arenas are served by
    ``shm.try_shm_bcast``/``try_shm_gather``/``try_shm_scatter`` as
    memcpy loops with zero jitted dispatches (and therefore zero
    ``dispatch_count`` increments).  The ordering contract is shared:
    both paths flush the whole pool first, so queued one-sided ops are
    ordered before the collective either way.
    """
    if engine is not None:
        state = engine.flush(poolid)
        engine.dispatch_count += 1
    return state


def _note_plan(engine, hit: bool) -> None:
    if engine is not None:
        engine._note_plan(hit)


def dart_bcast(state: HeapState, heap: SymmetricHeap, teams_by_slot,
               root_gptr: GlobalPtr, nbytes: int, engine=None):
    """Broadcast ``nbytes`` at the root's allocation to every row of the
    segment (team members all see the root's bytes at the same offset)."""
    poolid, row, off = deref(heap, teams_by_slot, root_gptr)
    state = _pre_collective(state, poolid, engine)
    seg = _sc.bucket_pow2(nbytes, _sc.SEG_FLOOR)
    fn, hit = _bcast_plan(state[poolid].shape, seg,
                          donate=engine is not None)
    _note_plan(engine, hit)
    arena = fn(state[poolid], np.asarray([row, off, nbytes], np.int32))
    new_state = copy_state(state)
    new_state[poolid] = arena
    return new_state, Handle((arena,))


def dart_gather(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                gptr: GlobalPtr, per_unit_nbytes: int, engine=None):
    """Gather each row's ``per_unit_nbytes`` at gptr.addr → host value of
    shape (n_rows, per_unit_nbytes) uint8."""
    poolid, _, off = deref(heap, teams_by_slot, gptr)
    state = _pre_collective(state, poolid, engine)
    seg = _sc.bucket_pow2(per_unit_nbytes, _sc.SEG_FLOOR)
    fn, hit = _row_gather_plan(state[poolid].shape, seg)
    _note_plan(engine, hit)
    padded = fn(state[poolid],
                np.asarray([off, per_unit_nbytes], np.int32))
    # trim the bucket padding host-side (one device→host copy; no
    # extra jitted launch after the counted gather)
    out = jnp.asarray(np.asarray(padded)[:, :per_unit_nbytes])
    return out, Handle((out,))


def dart_scatter(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                 gptr: GlobalPtr, values: jax.Array, engine=None):
    """Scatter row i of ``values`` (uint8[n_rows, nbytes]) to unit i."""
    poolid, _, off = deref(heap, teams_by_slot, gptr)
    state = _pre_collective(state, poolid, engine)
    vh = np.asarray(values, np.uint8)
    nbytes = vh.shape[1]
    seg = _sc.bucket_pow2(nbytes, _sc.SEG_FLOOR)
    padded = np.zeros((vh.shape[0], seg), np.uint8)
    padded[:, :nbytes] = vh                      # host staging: one H2D
    fn, hit = _row_scatter_plan(state[poolid].shape, seg,
                                donate=engine is not None)
    _note_plan(engine, hit)
    arena = fn(state[poolid], np.asarray([off, nbytes], np.int32), padded)
    new_state = copy_state(state)
    new_state[poolid] = arena
    return new_state, Handle((arena,))


def dart_gather_typed(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                      gptr: GlobalPtr, shape, dtype, engine=None):
    """Typed gather: each row's value at ``gptr.addr`` decoded to its
    dtype → ``(n_rows, *shape)``.  Slice *and* decode run inside the
    single counted jitted dispatch, bucketed on the element count so
    varying gather sizes share a cached kernel; the bucket padding is
    trimmed host-side from the one device→host copy."""
    dt = jnp.dtype(dtype)
    shape = tuple(shape)
    n_elems = max(int(np.prod(shape, dtype=np.int64)), 1) if shape else 1
    poolid, _, off = deref(heap, teams_by_slot, gptr)
    state = _pre_collective(state, poolid, engine)
    eb = _sc.bucket_pow2(n_elems, 4)
    fn, hit = _row_gather_typed_plan(state[poolid].shape, dt, eb)
    _note_plan(engine, hit)
    padded = fn(state[poolid],
                np.asarray([off, n_elems * dt.itemsize], np.int32))
    n_rows = state[poolid].shape[0]
    vals = jnp.asarray(
        np.asarray(padded)[:, :n_elems].reshape((n_rows,) + shape))
    return vals, Handle((vals,))


def dart_scatter_typed(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                       gptr: GlobalPtr, values: jax.Array, engine=None):
    """Typed scatter: row i of ``values`` (``(n_rows, *shape)``, any
    dtype) lands at ``gptr.addr`` on unit i.  Encode + update run
    inside the single counted jitted dispatch, bucketed on the element
    count (values are host-padded to the bucket and masked to the true
    byte length in-kernel) so varying sizes share a cached kernel."""
    vh = np.asarray(values)
    canon = jax.dtypes.canonicalize_dtype(vh.dtype)
    if vh.dtype != canon:
        # mirror the old jnp.asarray path: the kernel's byte mask must
        # be computed from the dtype the jit will actually store
        # (int64/float64 inputs canonicalize to 32-bit without x64)
        vh = vh.astype(canon)
    vh = vh.reshape(vh.shape[0], -1)
    n_elems = vh.shape[1]
    dt = vh.dtype
    poolid, _, off = deref(heap, teams_by_slot, gptr)
    state = _pre_collective(state, poolid, engine)
    eb = _sc.bucket_pow2(n_elems, 4)
    padded = np.zeros((vh.shape[0], eb), dt)
    padded[:, :n_elems] = vh                     # host staging: one H2D
    fn, hit = _row_scatter_typed_plan(state[poolid].shape, dt, eb,
                                      donate=engine is not None)
    _note_plan(engine, hit)
    arena = fn(state[poolid],
               np.asarray([off, n_elems * dt.itemsize], np.int32), padded)
    new_state = copy_state(state)
    new_state[poolid] = arena
    return new_state, Handle((arena,))


def _run_reduce(state, heap, teams_by_slot, gptr, shape, dtype, op,
                engine, root_unit):
    dt = jnp.dtype(dtype)
    _sc.check_op_dtype(op, dt)      # before the flush: nothing queued moves
    shape = tuple(shape)
    n_elems = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if root_unit is None:
        poolid, root_row, off = deref(heap, teams_by_slot, gptr)
        root_row = 0
    else:
        poolid, root_row, off = deref(heap, teams_by_slot,
                                      gptr.setunit(root_unit))
    state = _pre_collective(state, poolid, engine)
    eb = _sc.bucket_pow2(max(n_elems, 1), 4)
    fn, hit = _reduce_plan(state[poolid].shape, eb, dt, op,
                           root=root_unit is not None,
                           donate=engine is not None)
    _note_plan(engine, hit)
    arena, red_padded = fn(
        state[poolid],
        np.asarray([off, n_elems * dt.itemsize, root_row], np.int32))
    new_state = copy_state(state)
    new_state[poolid] = arena
    # trim the bucket padding host-side (one device→host copy, no
    # extra jitted launch after the counted dispatch) — padded lanes
    # hold the op identity, never caller data
    red = jnp.asarray(
        np.asarray(red_padded)[:n_elems].reshape(shape))
    return new_state, red


def dart_allreduce(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                   gptr: GlobalPtr, shape, dtype, op: str = "sum",
                   engine=None):
    """All-reduce the typed value at gptr.addr across rows; the result
    replaces every row's copy.  Returns (new_state, reduced_value).

    Shape-stable: the element count buckets to pow2 with op-identity
    padding (see :func:`_reduce_plan`), so steady-state loops of
    varying (shape, dtype, op) hit the plan cache with zero
    recompiles."""
    return _run_reduce(state, heap, teams_by_slot, gptr, shape, dtype,
                       op, engine, root_unit=None)


def dart_reduce(state: HeapState, heap: SymmetricHeap, teams_by_slot,
                gptr: GlobalPtr, shape, dtype, op: str = "sum",
                root: int = 0, engine=None):
    """Root-taking reduce: like :func:`dart_allreduce` but the reduced
    value replaces only ``root``'s row (absolute unit id); every other
    row keeps its own copy.  Returns (new_state, reduced_value)."""
    return _run_reduce(state, heap, teams_by_slot, gptr, shape, dtype,
                       op, engine, root_unit=root)


def dart_barrier(state: Optional[HeapState] = None) -> None:
    """Host-plane barrier: fence the device queue (single-controller)."""
    if state:
        jax.block_until_ready(list(state.values()))
    else:
        jax.block_until_ready(jnp.zeros(()))
