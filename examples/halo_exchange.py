"""Classic PGAS application: 1-D heat diffusion with one-sided halo
exchange (the pattern DART/DASH was built for) — on the typed
GlobalArray front-end (docs/API.md).

Each of 8 units owns a block of the rod; every step it PUTs its edge
cells into its neighbours' halo slots (one-sided — neighbours don't
participate), then applies the stencil locally.  The halo array is a
``ctx.alloc((2,), float32)``: element 0 is a unit's *left* halo,
element 1 its *right* halo — no byte offsets, no to_bytes/from_bytes.

Per step the runtime does exactly ONE jitted dispatch on a
host-visible heap: every edge put of the epoch coalesces into one
batched scatter, and the typed ``ga.gather()`` goes shm-direct —
a zero-dispatch memcpy through the shared-memory window (two
dispatches/step on device-only arenas, where the gather stays on the
jitted engine path).  Result is checked against a single-device dense
reference.

    PYTHONPATH=src python examples/halo_exchange.py
"""

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import DartConfig, dart_exit, dart_init, shm_supported

enable_compile_cache()

N_UNITS = 8
LOCAL = 32                      # cells per unit
ALPHA = 0.1
STEPS = 50

LEFT, RIGHT = 0, 1              # element slots in the halo array

ctx = dart_init(n_units=N_UNITS, config=DartConfig())
halo = ctx.alloc((2,), jnp.float32)

# initial condition: a hot spike in the middle
x0 = np.zeros(N_UNITS * LOCAL, np.float32)
x0[len(x0) // 2 - 4:len(x0) // 2 + 4] = 100.0
blocks = x0.reshape(N_UNITS, LOCAL).copy()

dispatches0 = ctx.engine.dispatch_count
for _ in range(STEPS):
    # one-sided halo exchange: each unit puts its edges into its
    # neighbours' halo slots; the epoch close coalesces all 14 puts
    # into a single jitted dispatch.
    with ctx.epoch():
        for u in range(N_UNITS):
            if u > 0:
                halo.at[u - 1, RIGHT].put_nb(blocks[u, 0])
            if u < N_UNITS - 1:
                halo.at[u + 1, LEFT].put_nb(blocks[u, -1])
    halos = np.asarray(halo.gather())   # (N_UNITS, 2), shm-direct: 0 dispatch
    # local stencil update (insulated ends: boundary units reuse their
    # own edge value as the missing halo)
    lh = np.where(np.arange(N_UNITS) == 0, blocks[:, 0], halos[:, LEFT])
    rh = np.where(np.arange(N_UNITS) == N_UNITS - 1, blocks[:, -1],
                  halos[:, RIGHT])
    padded = np.concatenate([lh[:, None], blocks, rh[:, None]], axis=1)
    blocks = blocks + ALPHA * (padded[:, :-2] - 2 * blocks + padded[:, 2:])

result = blocks.reshape(-1)
n_dispatch = ctx.engine.dispatch_count - dispatches0
per_step = 1 if shm_supported(ctx) else 2   # shm-direct gather costs 0
print(f"{STEPS} steps -> {n_dispatch} jitted dispatches "
      f"({n_dispatch / STEPS:.0f}/step: 1 coalesced put"
      f"{' + 1 gather' if per_step == 2 else ' + shm-direct gather'})")
assert n_dispatch == per_step * STEPS

# dense single-device reference
ref = x0.copy()
for _ in range(STEPS):
    padded = np.concatenate([ref[:1], ref, ref[-1:]])
    ref = ref + ALPHA * (padded[:-2] - 2 * ref + padded[2:])

err = np.max(np.abs(result - ref))
print(f"max |PGAS - dense| after {STEPS} steps: {err:.2e}")
assert err < 1e-4, "halo exchange diverged from the dense reference"
print("OK — one-sided halo exchange matches the dense stencil.")
print("temperature profile (coarse):",
      np.round(result.reshape(N_UNITS, LOCAL).mean(axis=1), 2))
dart_exit(ctx)
