"""The systems a run can drive, behind one small interface.

:class:`DartSystem` is the system under test: the DART runtime through
its public entry points (``dart_init``, ``ctx.alloc`` GlobalArrays,
each op kind's ``ga.at[u, lo:hi]`` calls, ``ga.flush()`` and
``dart_waitall``).  :class:`LowPrecisionControl` is the plain reference
put in its place with its payloads stored in bfloat16: the control that
the comparison in :mod:`.reference` has to fail.

Every op takes element offsets into one unit's window; values are host
numpy arrays of the deployment's dtype.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import numpy as np

from .drive import null_span
from .reference import Mirror


class DartSystem:
    """One ``GlobalArray`` that spans each unit's whole team window.  The
    run's devices (the cell's chips) place the units: on one device, as
    the rows of one arena there; on several, one unit per device, the
    arena row-sharded over a ``unit`` mesh of them.  The array is
    allocated with ``shm=False`` so that every op takes the engine's
    device path on every backend, as it does on a chip."""

    def __init__(self, config: dict, devices):
        import jax.numpy as jnp

        from repro.core import DartConfig, dart_init

        self.units = int(config["units"])
        self.dtype = np.dtype(config["dtype"])
        window = int(config["window_bytes_per_unit"])
        dcfg = DartConfig(team_pool_bytes=window,
                          non_collective_pool_bytes=int(
                              config["world_pool_bytes_per_unit"]))
        if len(devices) <= 1:
            self.ctx = dart_init(n_units=self.units, config=dcfg)
        else:
            from repro.launch.mesh import make_mesh
            if len(devices) != self.units:
                raise ValueError(f"{len(devices)} devices need one unit each: "
                                 f"the deployment has {self.units}")
            mesh = make_mesh((self.units,), ("unit",), devices=devices)
            self.ctx = dart_init(mesh=mesh, unit_axes=("unit",), config=dcfg)
        self.elems = window // self.dtype.itemsize
        self.ga = self.ctx.alloc((self.elems,), jnp.dtype(self.dtype),
                                 shm=False)
        if self.ga.gptr.addr != 0:
            raise RuntimeError("the window array does not start the pool")
        self.engine = self.ctx.engine
        self.span = null_span

    # -- ops -----------------------------------------------------------
    def issue(self, op, u: int, lo: int, n: int, payload, blocking: bool):
        """Issue one op of kind ``op`` (an ``ops/<kind>.py`` module):
        its value if ``blocking``, else its handle."""
        return op.issue(self, u, lo, n, payload, blocking)

    def flush(self) -> None:
        self.ga.flush()

    def complete(self, handles: List) -> None:
        from repro.core import dart_waitall
        dart_waitall(handles)

    def value(self, handle) -> np.ndarray:
        return np.asarray(handle.value())

    # -- what the harness reads ---------------------------------------
    def counters(self) -> dict:
        eng = self.engine
        return {"dispatch_count": eng.dispatch_count,
                "compile_count": eng.compile_count}

    def windows(self) -> np.ndarray:
        """Every unit's window as the device holds it, copied to the host
        once: ``(units, elems)``.  The array lives in DART_TEAM_ALL's
        pool, whose row ``u`` is unit ``u``."""
        from repro.core import deref
        self.engine.flush()
        poolid, _, _ = deref(self.ctx.heap, self.ctx.teams_by_slot,
                             self.ga.gptr)
        arena = np.asarray(self.ctx.state[poolid]).view(self.dtype)
        return arena[:, :self.elems]

    def instrument(self, span: Callable[[str], object],
                   entries: Sequence[str]) -> None:
        """Trace the blocking path's host steps: wrap the engine's entry
        points ``entries`` in ``enqueue`` spans and its flush in a
        ``flush`` span (instance attributes, so the engine's own calls
        through ``self`` find them)."""
        self.span = span
        eng = self.engine
        for attr, name in ([(a, "enqueue") for a in entries]
                           + [("flush", "flush")]):
            inner = getattr(eng, attr)

            def wrapped(*a, _inner=inner, _name=name, **kw):
                with span(_name):
                    return _inner(*a, **kw)
            setattr(eng, attr, wrapped)

    def close(self) -> None:
        from repro.core import dart_exit
        dart_exit(self.ctx)


class _Done:
    """A completed control op: its value, if it was a get."""

    __slots__ = ("val",)

    def __init__(self, val: Optional[np.ndarray] = None):
        self.val = val


class LowPrecisionControl:
    """The plain reference in the program's place, one precision lower:
    each op kind's ``model`` on a numpy mirror, with every payload
    rounded to bfloat16 before it is applied.  A sound comparison reads
    it as not correct."""

    def __init__(self, config: dict, devices=None):
        import ml_dtypes
        self._low = np.dtype(ml_dtypes.bfloat16)
        self.units = int(config["units"])
        self.dtype = np.dtype(config["dtype"])
        self.elems = int(config["window_bytes_per_unit"]) // self.dtype.itemsize
        self.mirror = Mirror(self.units, self.elems, self.dtype)
        self.dispatches = 0

    def issue(self, op, u, lo, n, payload, blocking):
        if payload is not None:
            payload = payload.astype(self._low).astype(self.dtype)
        value = op.model(self.mirror.rows, u, lo, n, payload)
        if blocking:
            self.dispatches += 1
            return value
        return _Done(value)

    def flush(self):
        self.dispatches += 1

    def complete(self, handles):
        pass

    def value(self, handle):
        return handle.val

    def counters(self):
        return {"dispatch_count": self.dispatches, "compile_count": 0}

    def windows(self):
        return self.mirror.rows

    def instrument(self, span, entries):
        pass

    def close(self):
        pass
