"""DART-JAX quickstart: the PGAS runtime in 60 lines — typed edition.

    PYTHONPATH=src python examples/quickstart.py

Covers the five DART API areas (paper §III) through the typed
GlobalArray front-end (docs/API.md): init/exit, teams+groups, global
memory, one-sided communication, synchronization.  No byte offsets, no
to_bytes/from_bytes — the raw ``dart_*`` substrate stays available one
layer down.
"""

import threading

import jax.numpy as jnp
import numpy as np

from repro.core import (DART_TEAM_ALL, DartConfig, dart_barrier, dart_exit,
                        dart_init, dart_team_create, dart_team_myid,
                        group_from_units)
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

# 1. initialize a runtime with 8 units -----------------------------------
ctx = dart_init(n_units=8, config=DartConfig())
print("units:", ctx.n_units)

# 2. teams & groups: split off the even units ----------------------------
evens = group_from_units([0, 2, 4, 6])
team = dart_team_create(ctx, DART_TEAM_ALL, evens)
print("unit 4 has relative id", dart_team_myid(ctx, team, 4),
      "in the even team")

# 3. global memory: typed collective allocation --------------------------
# 8 float32 per member — shape/dtype bookkeeping lives on the array,
# not on the caller (the substrate's byte offsets never appear).
ga = ctx.alloc((8,), jnp.float32, team=team)
print(f"GlobalArray: shape={ga.shape} dtype={ga.dtype} units={ga.units}")

# 4. one-sided communication ---------------------------------------------
# blocking put to unit 6's block, then get it back
ga[6].put(jnp.arange(8, dtype=jnp.float32))
print("roundtrip:", np.asarray(ga[6].get()))

# non-blocking puts inside an epoch: the puts queue on the engine and
# the epoch close flushes them as ONE coalesced jitted dispatch
d0 = ctx.engine.dispatch_count
with ctx.epoch():
    handles = [ga.at[u, 4:8].put_nb(jnp.full((4,), float(u)))
               for u in ga.units]
print(f"coalesced {len(handles)} puts into "
      f"{ctx.engine.dispatch_count - d0} dispatch(es)")

# non-blocking gets: enqueue, then value() flushes — per target: each
# handle dispatches only its own unit's lane, leaving other targets'
# queued epochs untouched (MPI_Win_flush_local analogue)
gets = {u: ga.at[u, 4:8].get_nb() for u in ga.units}
assert all(float(np.asarray(h.value())[0]) == float(u)
           for u, h in gets.items())

# collective: allreduce the blocks the members just wrote
print("allreduce(sum):", np.asarray(ga.allreduce("sum")[4:8]))  # 0+2+4+6

# zero-copy local view: routed through the locality classifier — on
# host-visible arenas this is a numpy view with zero jitted dispatches
print("local view:", np.asarray(ga.local[4:8]))

# 5. synchronization: the MCS queueing lock (paper §IV.B.6) --------------
lock = ctx.locks.create_lock(ctx.teams[DART_TEAM_ALL])
counter = {"v": 0}

def worker(u):
    for _ in range(100):
        ctx.locks.acquire(lock, u)
        counter["v"] += 1
        ctx.locks.release(lock, u)

threads = [threading.Thread(target=worker, args=(u,)) for u in range(8)]
for t in threads: t.start()
for t in threads: t.join()
print("lock-protected counter:", counter["v"], "(expected 800)")

dart_barrier(ctx)
dart_exit(ctx)
print("done.")
