"""Compile rehearsals for a TPU v5e, without a chip attached.

Every plan the engine builds on the chip smoke's path, with the impl
``CommEngine(impl='auto')`` picks on a TPU, is compiled for a described
v5e: one chip holding the smoke's 8 x 64 MiB arena, and the 2x2 mesh
holding a 4 x 16 MiB arena row-sharded one unit per chip (what
``dart_init(mesh=...)`` builds).  A plan the TPU compiler refuses fails
here instead of on the chip.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler; the persistent compilation cache is
off around these compiles, since a cache entry for a described chip
cannot be read back without one.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import collectives as coll
from repro.core import onesided
from repro.core import shmem_put, team_psum
from repro.kernels import segmented_copy as sc
from repro.launch.mesh import make_mesh

MiB = 1 << 20
#: chip_smoke.py runtime phase: 8 units x 64 MiB on one chip
ONE_CHIP_ARENA = (8, 64 * MiB)
#: chip_smoke.py --chips 4: one 16 MiB row per chip
FOUR_CHIP_ARENA = (4, 16 * MiB)
#: (run-length bucket, segment bucket): a blocking op, and the smoke's
#: coalesced epoch to all 8 units
RUN_SHAPES = [(4, 16), (8, 8192)]
#: the GUPS cell's table on one chip: 4 units of 1 GiB, past the lane
#: plans' int32 index; and its epoch of 1024 updates of 8 bytes
GUPS_ARENA = (4, 1 << 30)
GUPS_RUN = (1024, 16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: cannot describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module", params=["one_chip", "four_chips"])
def arena_spec(request, topo):
    """(arena shape, arena sharding, sharding for small operands)."""
    if request.param == "one_chip":
        one = SingleDeviceSharding(topo.devices[0])
        return ONE_CHIP_ARENA, one, one
    mesh = make_mesh((4,), ("unit",), devices=topo.devices)
    return (FOUR_CHIP_ARENA, NamedSharding(mesh, P(("unit",), None)),
            NamedSharding(mesh, P()))


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_auto_impl_on_tpu_is_ref_and_pallas_refused(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert onesided.CommEngine(impl="auto").impl == "ref"
    with pytest.raises(NotImplementedError, match="pallas"):
        onesided.CommEngine(impl="pallas")
    eng = onesided.CommEngine()
    with pytest.raises(NotImplementedError):
        eng.impl = "pallas"
    assert eng.impl == "ref"


@pytest.mark.parametrize("kb,seg", RUN_SHAPES)
@pytest.mark.parametrize("ordered", [False, True])
def test_scatter_plan_compiles(arena_spec, kb, seg, ordered):
    shape, arena_sh, small = arena_spec
    flat = max(kb * seg + seg, sc.FLAT_FLOOR)
    fn, _ = sc.scatter_plan(shape, kb, seg, flat, ordered=ordered,
                            impl=onesided.CommEngine().impl)
    _compile(fn, _sds(shape, jnp.uint8, arena_sh),
             _sds((kb, sc.DESC_COLS), jnp.int32, small),
             _sds((flat,), jnp.uint8, small))


@pytest.mark.parametrize("kb,seg", RUN_SHAPES)
def test_gather_plan_compiles(arena_spec, kb, seg):
    shape, arena_sh, small = arena_spec
    fn, _ = sc.gather_plan(shape, kb, seg, impl=onesided.CommEngine().impl)
    _compile(fn, _sds(shape, jnp.uint8, arena_sh),
             _sds((kb, sc.DESC_COLS), jnp.int32, small))


#: the only HLO ops a one-chip window plan may apply to an arena-shaped
#: array: it passes through the loop and takes one window update per
#: descriptor, and is never copied, broadcast, padded or reshaped
_WINDOW_ARENA_OPS = {"parameter", "get-tuple-element", "tuple", "while",
                     "dynamic-update-slice"}


def _check_window_plan(compiled, shape, donated=True):
    """A one-chip window plan's program: the arena is never flattened
    (no ``u8[R*P]``) and takes its window updates in place: a donated
    arena is the output's alias, and scratch is far under it."""
    hlo = compiled.as_text()
    rows, pool = shape
    assert f"u8[{rows * pool}]" not in hlo
    arena_ops = set(re.findall(
        rf"= u8\[{rows},{pool}\]\{{[^}}]*\}} ([\w-]+)\(", hlo))
    assert "parameter" in arena_ops and arena_ops <= _WINDOW_ARENA_OPS, \
        arena_ops
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < rows * pool // 64
    if donated:
        assert memory.alias_size_in_bytes == rows * pool


@pytest.mark.parametrize("kb,seg", RUN_SHAPES)
@pytest.mark.parametrize("kind", ["scatter", "gather", "accumulate"])
def test_put_get_plans_the_engine_picks(arena_spec, kb, seg, kind):
    """The engine's choice on each layout, compiled: one chip takes the
    window plans (puts, gets and plain accumulates), which never
    flatten the arena, keep the donated arena in place and ask for
    scratch far under it; the row-sharded four-chip arena keeps the
    lane plans, and neither layout's pick holds an all-gather."""
    shape, arena_sh, small = arena_spec
    arena = _sds(shape, jnp.uint8, arena_sh)
    desc = np.zeros((kb, sc.DESC_COLS), np.int32)
    desc[:, sc.COUNT] = 1
    window = onesided._window_path(desc, seg, arena)
    assert window == (len(arena_sh.device_set) == 1)
    descs = _sds((kb, sc.DESC_COLS), jnp.int32, small)
    if kind == "scatter":
        flat = max(kb * seg + seg, sc.FLAT_FLOOR)
        fn, _ = sc.scatter_plan(shape, kb, seg, flat, ordered=False,
                                window=window)
        compiled = _compile(fn, arena, descs,
                            _sds((flat,), jnp.uint8, small))
    elif kind == "accumulate":
        fn, _ = sc.accumulate_plan(shape, kb, seg, kb * seg, op="bxor",
                                   dtype="uint32", fetch=False,
                                   window=window)
        compiled = _compile(fn, arena,
                            _sds((kb, sc.ACC_DESC_COLS), jnp.int32, small),
                            _sds((kb * seg,), jnp.uint8, small))
    else:
        fn, _ = sc.gather_plan(shape, kb, seg, window=window)
        compiled = _compile(fn, arena, descs)
    assert "all-gather" not in compiled.as_text()
    if window:
        _check_window_plan(compiled, shape, donated=kind != "gather")


def test_window_accumulate_compiles_for_the_gups_table(topo):
    """The GUPS cell's plan at its real size: XOR updates into a 4 GiB
    one-chip arena, which the lane plans' int32 index refuses, compiled
    as a window plan that updates the donated table in place."""
    one = SingleDeviceSharding(topo.devices[0])
    kb, seg = GUPS_RUN
    fn, _ = sc.accumulate_plan(GUPS_ARENA, kb, seg, kb * seg, op="bxor",
                               dtype="uint32", fetch=False, window=True)
    compiled = _compile(fn, _sds(GUPS_ARENA, jnp.uint8, one),
                        _sds((kb, sc.ACC_DESC_COLS), jnp.int32, one),
                        _sds((kb * seg,), jnp.uint8, one))
    _check_window_plan(compiled, GUPS_ARENA)


@pytest.mark.parametrize("op,dtype,fetch,ordered", [
    ("sum", "float32", False, False),
    ("sum", "float32", False, True),
    ("max", "int32", False, True),
    ("sum", "int32", True, False),
])
def test_accumulate_plan_compiles(arena_spec, op, dtype, fetch, ordered):
    shape, arena_sh, small = arena_spec
    kb, seg = RUN_SHAPES[-1]
    fn, _ = sc.accumulate_plan(shape, kb, seg, kb * seg, op=op,
                               dtype=dtype, fetch=fetch, ordered=ordered,
                               impl=onesided.CommEngine().impl)
    _compile(fn, _sds(shape, jnp.uint8, arena_sh),
             _sds((kb, sc.ACC_DESC_COLS), jnp.int32, small),
             _sds((kb * seg,), jnp.uint8, small))


def test_collective_plans_compile(arena_spec):
    shape, arena_sh, small = arena_spec
    arena = _sds(shape, jnp.uint8, arena_sh)
    rows = shape[0]
    fn, _ = coll._reduce_plan(shape, 1024, jnp.float32, "sum", root=False,
                              donate=True)
    _compile(fn, arena, _sds((3,), jnp.int32, small))
    fn, _ = coll._reduce_plan(shape, 256, jnp.int32, "max", root=True,
                              donate=True)
    _compile(fn, arena, _sds((3,), jnp.int32, small))
    fn, _ = coll._bcast_plan(shape, 4096, donate=True)
    _compile(fn, arena, _sds((3,), jnp.int32, small))
    fn, _ = coll._row_gather_typed_plan(shape, jnp.float32, 1024)
    _compile(fn, arena, _sds((2,), jnp.int32, small))
    fn, _ = coll._row_scatter_typed_plan(shape, jnp.float32, 1024,
                                         donate=True)
    _compile(fn, arena, _sds((2,), jnp.int32, small),
             _sds((rows, 1024), jnp.float32, small))


def test_atomic_cell_ops_compile(arena_spec):
    """The blocking get/put pair every heap atomic is built from."""
    shape, arena_sh, small = arena_spec
    arena = _sds(shape, jnp.uint8, arena_sh)
    scalar = _sds((), jnp.int32, small)
    _compile(onesided._arena_write, arena, scalar, scalar,
             _sds((4,), jnp.uint8, small))
    _compile(onesided._arena_read, arena, scalar, scalar, 4)


def test_device_plane_compiles_on_four_chips(topo):
    mesh = make_mesh((4,), ("unit",), devices=topo.devices)
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def body(arena_row, v):
        total = team_psum(v, "unit", [[0, 1], [2, 3]])
        return shmem_put(arena_row, total, 128, ring, "unit")

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(P("unit", None), P("unit", None)),
                               out_specs=P("unit", None)))
    row = NamedSharding(mesh, P("unit", None))
    compiled = _compile(fn, _sds(FOUR_CHIP_ARENA, jnp.uint8, row),
                        _sds((4, 4), jnp.float32, row))
    hlo = compiled.as_text()
    assert "collective-permute" in hlo and "all-reduce" in hlo
