"""Device-plane checks for shmem ops + team collectives.

Run in a subprocess with 8 forced host devices (see
tests/test_multidevice.py) so the main pytest process keeps 1 device.
Prints CHECK:<name>:OK per assertion block and ALL:OK at the end.
"""

import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

from repro.core import (team_all_gather, team_all_to_all, team_barrier,
                        team_broadcast, team_pmax, team_psum,
                        team_reduce_scatter)
from repro.core.onesided import (shmem_get, shmem_get_dynamic,
                                 shmem_halo_exchange, shmem_put)

N = 8
mesh = make_mesh((N,), ("unit",))
GROUPS = [[0, 1, 2, 3], [4, 5, 6, 7]]


def check(name, ok):
    assert ok, name
    print(f"CHECK:{name}:OK", flush=True)


# ---------------------------------------------------------- shmem_put ------
pool_bytes = 1024
arena = jnp.zeros((N, pool_bytes), jnp.uint8)
vals = jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4)  # per-unit payload
ring = [(i, (i + 1) % N) for i in range(N)]


def put_body(arena_row, v):
    return shmem_put(arena_row, v, 128, ring, "unit")


f = jax.jit(jax.shard_map(put_body, mesh=mesh,
                          in_specs=(P("unit", None), P("unit", None)),
                          out_specs=P("unit", None)))
arena2 = f(arena, vals)
got = np.asarray(arena2)[:, 128:128 + 16]
expect = np.asarray(
    jax.vmap(lambda v: jax.lax.bitcast_convert_type(v, jnp.uint8).reshape(-1))
    (jnp.roll(vals, 1, axis=0)))
check("shmem_put_ring", np.array_equal(got, expect))

# ---------------------------------------------------------- shmem_get ------
rev = [((i + 1) % N, i) for i in range(N)]   # get from right neighbour


def get_body(arena_row):
    return shmem_get(arena_row, 128, 16, rev, "unit", (4,), jnp.float32)


g = jax.jit(jax.shard_map(get_body, mesh=mesh, in_specs=P("unit", None),
                          out_specs=P("unit")))
fetched = np.asarray(g(arena2)).reshape(N, 4)
check("shmem_get_ring", np.allclose(fetched, np.roll(np.asarray(
    np.roll(vals, 1, axis=0)), -1, axis=0)))

# --------------------------------------------------- shmem_get_dynamic -----


def dyn_body(arena_row, src):
    return shmem_get_dynamic(arena_row, 128, 16, src[0], "unit",
                             (4,), jnp.float32)


srcs = jnp.array([[3]] * N, dtype=jnp.int32)   # everyone reads unit 3
d = jax.jit(jax.shard_map(dyn_body, mesh=mesh,
                          in_specs=(P("unit", None), P("unit", None)),
                          out_specs=P("unit"), check_vma=False))
out = np.asarray(d(arena2, srcs)).reshape(N, 4)
row3 = np.asarray(jnp.roll(vals, 1, axis=0))[3]
check("shmem_get_dynamic", np.allclose(out, np.tile(row3, (N, 1))))

# ------------------------------------------------------- halo exchange -----


def halo_body(arena_row, v):
    return shmem_halo_exchange(arena_row, v, v + 100.0, 0, 256,
                               "unit", N, wrap=False)


h = jax.jit(jax.shard_map(halo_body, mesh=mesh,
                          in_specs=(P("unit", None), P("unit", None)),
                          out_specs=P("unit", None)))
arena3 = np.asarray(h(jnp.zeros((N, pool_bytes), jnp.uint8), vals))
left_halo = arena3[:, 0:16].view(np.float32).reshape(N, 4)
right_halo = arena3[:, 256:272].view(np.float32).reshape(N, 4)
v_np = np.asarray(vals)
# unit i's left halo = unit i-1's right_val (v+100); right halo = unit
# i+1's left_val (v); edges untouched (zeros).
check("halo_left", np.allclose(left_halo[1:], v_np[:-1] + 100.0)
      and np.allclose(left_halo[0], 0))
check("halo_right", np.allclose(right_halo[:-1], v_np[1:])
      and np.allclose(right_halo[-1], 0))

# ------------------------------------------------- team collectives --------
x = jnp.arange(N, dtype=jnp.float32)


def coll_body(xi):
    s = team_psum(xi, "unit", GROUPS)
    m = team_pmax(xi, "unit", GROUPS)
    b = team_broadcast(xi, "unit", 1, GROUPS)
    ag = team_all_gather(xi, "unit", GROUPS)
    t = team_barrier("unit", GROUPS)
    return s, m, b, ag, t.reshape(1)


c = jax.jit(jax.shard_map(coll_body, mesh=mesh, in_specs=P("unit"),
                          out_specs=(P("unit"),) * 5, check_vma=False))
s, m, b, ag, t = c(x)
check("team_psum", np.allclose(np.asarray(s), [6] * 4 + [22] * 4))
check("team_pmax", np.allclose(np.asarray(m), [3] * 4 + [7] * 4))
check("team_broadcast", np.allclose(np.asarray(b), [1] * 4 + [5] * 4))
ag = np.asarray(ag).reshape(N, 4)
check("team_all_gather", np.allclose(ag[0], [0, 1, 2, 3])
      and np.allclose(ag[7], [4, 5, 6, 7]))
check("team_barrier", np.all(np.asarray(t) == 4))

# reduce_scatter: each unit contributes [0..3], gets 1 reduced element


def rs_body(xi):
    return team_reduce_scatter(xi[0], "unit", GROUPS)


xs = jnp.tile(jnp.arange(4, dtype=jnp.float32)[None], (N, 1))
rs = jax.jit(jax.shard_map(rs_body, mesh=mesh, in_specs=P("unit", None),
                           out_specs=P("unit"), check_vma=False))
out = np.asarray(rs(xs)).reshape(-1)
check("team_reduce_scatter", np.allclose(out, [0, 4, 8, 12] * 2))

# all_to_all within groups


def a2a_body(xi):
    return team_all_to_all(xi[0], "unit", 0, 0, GROUPS)[None]


xs = jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4)
a2a = jax.jit(jax.shard_map(a2a_body, mesh=mesh, in_specs=P("unit", None),
                            out_specs=P("unit", None), check_vma=False))
out = np.asarray(a2a(xs)).reshape(N, 4)
blk = np.asarray(xs).reshape(2, 4, 4)
for gidx in range(2):
    check(f"team_all_to_all_g{gidx}",
          np.allclose(out[gidx * 4:(gidx + 1) * 4], blk[gidx].T))

# ------------------------------------- heap put/get on a sharded mesh ------
from repro.core import (DART_TEAM_ALL, DartConfig, dart_exit,
                        dart_get_blocking, dart_init, dart_put_blocking,
                        dart_team_memalloc_aligned)

ctx = dart_init(n_units=N, mesh=mesh, unit_axes=("unit",),
                config=DartConfig(non_collective_pool_bytes=4096,
                                  team_pool_bytes=4096))
gp = dart_team_memalloc_aligned(ctx, DART_TEAM_ALL, 256)
for u in range(N):
    dart_put_blocking(ctx, gp.setunit(u), jnp.full((8,), u, jnp.float32))
ok = all(np.all(np.asarray(
    dart_get_blocking(ctx, gp.setunit(u), (8,), jnp.float32)) == u)
    for u in range(N))
check("sharded_heap_putget", ok)
# a row-sharded arena keeps the flat lane plans: window plans would
# all-gather it
stats = ctx.engine.dispatch_stats()
check("sharded_heap_lane_path",
      stats["dispatches"]["ref"] >= 2 * N
      and stats["window_dispatches"] == 0)
shard_rows = {d: s for d, s in zip(
    ctx.state[1].sharding.device_set,
    [None] * N)}
check("heap_is_row_sharded",
      ctx.state[1].sharding.is_equivalent_to(
          NamedSharding(mesh, P(("unit",), None)), 2))
dart_exit(ctx)

# ----------------------- compressed all-reduce (DCN lever) -----------------
from repro.optim.compression import compressed_allreduce_ref

g_global = jnp.asarray(np.random.RandomState(5).randn(N, 64), jnp.float32)


def comp_body(g):
    red, resid = compressed_allreduce_ref(g[0], "unit")
    return red[None], resid[None]


cf = jax.jit(jax.shard_map(comp_body, mesh=mesh,
                           in_specs=P("unit", None),
                           out_specs=(P("unit", None), P("unit", None)),
                           check_vma=False))
red, resid = cf(g_global)
red = np.asarray(red)
truth = np.asarray(g_global).sum(axis=0)
# every unit holds the same reduced value, close to the true sum
for u in range(N):
    assert np.allclose(red[u], red[0])
err = np.abs(red[0] - truth).max()
scale = np.abs(np.asarray(g_global)).max() / 127.0
check("compressed_allreduce_err_bound", err <= N * scale * 0.51 + 1e-6)
# error feedback: residual equals the per-unit quantization error
check("compressed_allreduce_residual_shape",
      np.asarray(resid).shape == (N, 64))

print("ALL:OK", flush=True)
