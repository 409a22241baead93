"""Pallas comm-kernel checks vs pure-jnp oracles (interpret mode, 8 devs).

Sweeps shapes/dtypes per the test instructions; every kernel result is
assert_allclose'd against the ref.py oracle running in the same
shard_map configuration.
"""

import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh

from repro.kernels.ops import (make_rdma_put, make_ring_all_gather,
                               make_ring_reduce_scatter)

N = 8
mesh = make_mesh((N,), ("unit",))

SHAPES = [(8, 128), (16, 256), (5, 128), (32, 512)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]


def check(name, ok):
    assert ok, name
    print(f"CHECK:{name}:OK", flush=True)


def rand(shape, dtype, seed):
    rng = np.random.RandomState(seed)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.randint(-100, 100, size=shape), dtype=dtype)
    return jnp.asarray(rng.randn(*shape), dtype=dtype)


# ------------------------------------------------------------ rdma_put -----
for shape in SHAPES:
    for dtype in DTYPES:
        for offset in (1, 2, -1):
            x = rand((N * shape[0], shape[1]), dtype, 0)
            out = make_rdma_put(mesh, "unit", offset=offset)(x)
            ref = make_rdma_put(mesh, "unit", offset=offset, impl="ref")(x)
            np.testing.assert_allclose(
                np.asarray(out, np.float64), np.asarray(ref, np.float64),
                err_msg=f"rdma_put {shape} {dtype.__name__} off={offset}")
        print(f"CHECK:rdma_put_{shape[0]}x{shape[1]}_{dtype.__name__}:OK",
              flush=True)

# ----------------------------------------------------- ring all-gather -----
for shape in SHAPES:
    for dtype in DTYPES:
        x = rand((N * shape[0], shape[1]), dtype, 1)
        out = make_ring_all_gather(mesh, "unit")(x)
        ref = make_ring_all_gather(mesh, "unit", impl="ref")(x)
        np.testing.assert_allclose(
            np.asarray(out, np.float64), np.asarray(ref, np.float64),
            err_msg=f"ring_ag {shape} {dtype.__name__}")
        # every unit's copy equals the full gathered array
        per_unit = np.asarray(out, np.float64).reshape(N, N * shape[0],
                                                       shape[1])
        full = np.asarray(x, np.float64)
        for u in range(N):
            np.testing.assert_allclose(per_unit[u], full)
        print(f"CHECK:ring_allgather_{shape[0]}x{shape[1]}_"
              f"{dtype.__name__}:OK", flush=True)

# ------------------------------------------------- ring reduce-scatter -----
for shape in [(8, 128), (16, 256)]:
    for dtype in [jnp.float32, jnp.int32]:
        # per-unit contribution: (N*chunk, n); global input (N*N*chunk, n)
        x = rand((N * N * shape[0], shape[1]), dtype, 2)
        out = make_ring_reduce_scatter(mesh, "unit")(x)
        ref = make_ring_reduce_scatter(mesh, "unit", impl="ref")(x)
        # ring accumulation order differs from psum_scatter's tree order:
        # bitwise equality is not expected for floats, closeness is.
        tol = {} if jnp.issubdtype(dtype, jnp.integer) else dict(
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(out, np.float64), np.asarray(ref, np.float64),
            err_msg=f"ring_rs {shape} {dtype.__name__}", **tol)
        # direct oracle: sum of per-unit blocks
        blocks = np.asarray(x, np.float64).reshape(N, N, shape[0], shape[1])
        expect = blocks.sum(axis=0).reshape(N * shape[0], shape[1])
        np.testing.assert_allclose(np.asarray(out, np.float64), expect,
                                   **tol)
        print(f"CHECK:ring_reduce_scatter_{shape[0]}x{shape[1]}_"
              f"{dtype.__name__}:OK", flush=True)

# bf16 reduce-scatter with tolerance (accumulation order differs)
x = rand((N * N * 8, 128), jnp.bfloat16, 3)
out = make_ring_reduce_scatter(mesh, "unit")(x)
ref = make_ring_reduce_scatter(mesh, "unit", impl="ref")(x)
np.testing.assert_allclose(np.asarray(out, np.float64),
                           np.asarray(ref, np.float64), rtol=0.05, atol=0.5)
print("CHECK:ring_reduce_scatter_bf16:OK", flush=True)

print("ALL:OK", flush=True)
