"""Jitted top-level wrappers for the Pallas comm kernels.

Each wrapper closes over a mesh + axis name, shard_maps the SPMD kernel
over it, and jits the result.  ``impl`` selects the Pallas kernel
(``'pallas'``, interpret-mode on CPU / compiled on TPU) or the pure-JAX
oracle (``'ref'``).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
from jax.sharding import PartitionSpec as P

from . import ref as _ref
from .rdma import rdma_get, rdma_put
from .ring_allgather import ring_all_gather
from .ring_reduce_scatter import ring_reduce_scatter

Impl = Literal["pallas", "ref"]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def make_rdma_put(mesh: jax.sharding.Mesh, axis_name: str,
                  offset: int = 1, impl: Impl = "pallas"):
    n = mesh.shape[axis_name]

    def body(x):
        if impl == "ref":
            return _ref.rdma_put_ref(x, axis_name=axis_name,
                                     num_devices=n, offset=offset)
        return rdma_put(x, axis_name=axis_name, num_devices=n,
                        offset=offset, interpret=_interpret_default())

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(axis_name, None),
        out_specs=P(axis_name, None), check_vma=False))


def make_ring_all_gather(mesh: jax.sharding.Mesh, axis_name: str,
                         impl: Impl = "pallas"):
    n = mesh.shape[axis_name]

    def body(x):
        if impl == "ref":
            return _ref.ring_all_gather_ref(x, axis_name=axis_name,
                                            num_devices=n)
        return ring_all_gather(x, axis_name=axis_name, num_devices=n,
                               interpret=_interpret_default())

    # input sharded over units; output replicated (every unit holds all)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(axis_name, None),
        out_specs=P(axis_name, None), check_vma=False))


def make_ring_reduce_scatter(mesh: jax.sharding.Mesh, axis_name: str,
                             impl: Impl = "pallas"):
    n = mesh.shape[axis_name]

    def body(x):
        if impl == "ref":
            return _ref.ring_reduce_scatter_ref(x, axis_name=axis_name,
                                                num_devices=n)
        return ring_reduce_scatter(x, axis_name=axis_name, num_devices=n,
                                   interpret=_interpret_default())

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(axis_name, None),
        out_specs=P(axis_name, None), check_vma=False))
