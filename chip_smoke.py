#!/usr/bin/env python3
"""Drive DART's main path once on a TPU and check every result.

    python chip_smoke.py [--seed N]          # one chip: runtime + serve
    python chip_smoke.py --chips 4 [--seed N]  # four chips: sharded heap

Everything runs in this one process, which holds the chip(s) for the
whole run.  Each phase checks its results against a plain numpy model,
and a phase that fails makes the script fail.

* runtime (one chip): ``dart_init(n_units=8)`` with a 64 MiB team pool
  (a 512 MiB arena), driven through the public ``repro.core`` /
  ``GlobalArray`` API: blocking and non-blocking put/get to every unit
  from 1 B to 2 MiB, one coalesced ``put_nb`` epoch, a strided column
  get, accumulates, atomics, an MCS-lock counter and the host-plane
  collectives.  The arenas must equal a numpy mirror byte for byte.
* serve (one chip): ``ContinuousEngine`` on llama3-8b at its published
  widths in bf16 with random weights, depth cut to fit one chip.  Eight
  requests over three prompts, so repeats restore their KV blocks from
  the DART pool by one-sided gets; the greedy outputs must equal those
  of the same engine with the prefix cache off.
* sharded heap (``--chips 4``, and nothing else): one DART unit per
  chip, the arena row-sharded over a 4-chip mesh; put/get/accumulate
  and the host-plane collectives against the numpy mirror, each chip
  holding only its own row, then the device-plane ``shmem_*`` and
  ``team_*`` ops on the real chips.

The last line of stdout is the result, printed only when every phase
passed on a TPU:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU, or without the repository's ``src/`` next to this file,
the script exits non-zero and prints no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import threading
import time

import numpy as np

MiB = 1 << 20

#: the runtime phase's team pool per unit (8 units: a 512 MiB arena,
#: under the segmented-copy kernels' 1 GiB flat-address cap)
RUNTIME_POOL_BYTES = 64 * MiB
#: largest put/get of the sweep (the paper's Figs. 8-15 go to 2 MiB)
SWEEP_MAX_BYTES = 2 * MiB
#: llama3-8b depth on one chip: 4 of 32 layers (~3.6 GiB of bf16 weights)
SERVE_LAYERS = 4
#: the sharded-heap phase's team pool per unit (one unit per chip)
MULTICHIP_POOL_BYTES = 16 * MiB
#: its put/get sizes: segment buckets that compile in seconds (a 1 MiB
#: bucket takes about a minute to compile for a v5e)
MULTICHIP_SIZES = (1, 100, 4096, 16384)


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its numpy model."""


def check(name: str, ok, detail: str = "") -> None:
    if not ok:
        raise SmokeFailure(f"{name} failed{': ' + detail if detail else ''}")


def peak_bytes(device) -> object:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


class Mirror:
    """numpy model of one pool arena: ``(n_rows, pool_bytes)`` bytes,
    addressed the way a ``GlobalArray`` lays its blocks out."""

    def __init__(self, ctx, ga):
        from repro.core import deref
        self.poolid, _, _ = deref(ctx.heap, ctx.teams_by_slot, ga.gptr)
        meta = ctx.heap.pools[self.poolid]
        self.bytes = np.zeros((meta.n_rows, meta.pool_bytes), np.uint8)

    def span(self, ga, unit, lo, n):
        row = ga.team.myid(unit)
        start = ga.gptr.addr + lo * ga.itemsize
        return self.bytes[row, start:start + n * ga.itemsize]

    def write(self, ga, unit, lo, values):
        raw = np.ascontiguousarray(values, ga.dtype).reshape(-1)
        self.span(ga, unit, lo, raw.size)[:] = raw.view(np.uint8)

    def read(self, ga, unit, lo, n):
        return self.span(ga, unit, lo, n).view(ga.dtype).copy()

    def block(self, ga, unit):
        n = int(np.prod(ga.shape))
        return self.read(ga, unit, 0, n).reshape(ga.shape)


def _collectives(ctx, mirror, col, icol, rng) -> None:
    """bcast/gather/scatter/allreduce/reduce on DART_TEAM_ALL against
    the mirror (integer-valued data, so every float sum is exact)."""
    n = len(col.units)
    vals = rng.integers(-1000, 1000, (n,) + col.shape).astype(np.float32)
    col.scatter(vals)
    for i, u in enumerate(col.units):
        mirror.write(col, u, 0, vals[i])
    check("scatter+gather", np.array_equal(np.asarray(col.gather()), vals))
    root = col.units[n // 2]
    col.broadcast(root).wait()
    for u in col.units:
        mirror.write(col, u, 0, vals[n // 2])
    check("bcast", np.array_equal(np.asarray(col.gather()),
                                  np.broadcast_to(vals[n // 2], vals.shape)))
    vals = rng.integers(-1000, 1000, (n,) + col.shape).astype(np.float32)
    col.scatter(vals)
    red = np.asarray(col.allreduce("sum"))
    check("allreduce(sum)", np.array_equal(red, vals.sum(axis=0)))
    for u in col.units:
        mirror.write(col, u, 0, red)
    ivals = rng.integers(-2**20, 2**20, (n,) + icol.shape).astype(np.int32)
    icol.scatter(ivals)
    for i, u in enumerate(icol.units):
        mirror.write(icol, u, 0, ivals[i])
    root = icol.units[-1]
    red = np.asarray(icol.reduce("max", root=root))
    check("reduce(max)", np.array_equal(red, ivals.max(axis=0)))
    mirror.write(icol, root, 0, red)


def _accumulates(ctx, mirror, accf, acci, rng) -> None:
    """f32 sum / i32 max accumulates (coalesced per op, overlapping
    ranges included) and get_accumulate, against the mirror."""
    n = accf.shape[0]
    with ctx.epoch():
        for ga, op, combine in ((accf, "sum", np.add),
                                (acci, "max", np.maximum)):
            for u in ga.units:
                for _ in range(3):
                    lo = int(rng.integers(0, n // 2))
                    k = int(rng.integers(1, n // 2))
                    if ga.dtype == np.float32:
                        v = rng.standard_normal(k).astype(np.float32)
                    else:
                        v = rng.integers(-2**30, 2**30, k).astype(np.int32)
                    ga.at[u, lo:lo + k].accumulate(v, op)
                    mirror.write(ga, u, lo,
                                 combine(mirror.read(ga, u, lo, k), v))
    for u in acci.units:
        iv = rng.integers(-1000, 1000, 64).astype(np.int32)
        old = np.asarray(acci.at[u, 0:64].get_accumulate(iv, "sum"))
        check(f"get_accumulate unit {u}",
              np.array_equal(old, mirror.read(acci, u, 0, 64)))
        mirror.write(acci, u, 0, old + iv)
    for u in accf.units:
        check(f"accumulate unit {u}",
              np.array_equal(np.asarray(accf[u].get()),
                             mirror.block(accf, u))
              and np.array_equal(np.asarray(acci[u].get()),
                                 mirror.block(acci, u)))


def _atomics(ctx, mirror, cells) -> None:
    """fetch_and_add, compare_and_swap and an MCS-lock-protected
    counter (threads doing get + put on one heap cell)."""
    from repro.core import (DART_TEAM_ALL, dart_compare_and_swap,
                            dart_fetch_and_add)
    for u in cells.units:
        total = 0
        for d in (1, 2, 3):
            pre = dart_fetch_and_add(ctx, cells.at[u, 0:1].gptr, d)
            check(f"fetch_and_add unit {u}", pre == total,
                  f"{pre} != {total}")
            total += d
        gp = cells.at[u, 1:2].gptr
        check("cas (match)", dart_compare_and_swap(ctx, gp, 0, 7 + u) == 0)
        check("cas (mismatch)", dart_compare_and_swap(ctx, gp, 0, 99)
              == 7 + u)
        mirror.write(cells, u, 0, np.array([total, 7 + u], np.int32))
    lock = ctx.locks.create_lock(ctx.teams[DART_TEAM_ALL])
    counter = cells.at[cells.units[0], 2:3]
    per_thread = 10

    def worker(u):
        for _ in range(per_thread):
            with ctx.locks.held(lock, u, timeout=60):
                v = int(np.asarray(counter.get())[0])
                counter.put(np.array([v + 1], np.int32))

    threads = [threading.Thread(target=worker, args=(u,))
               for u in cells.units]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    check("MCS lock threads finished", not any(t.is_alive() for t in threads))
    want = per_thread * len(threads)
    got = int(np.asarray(counter.get())[0])
    check("MCS-lock counter", got == want, f"{got} != {want}")
    mirror.write(cells, cells.units[0], 2, np.array([want], np.int32))


def shm_routes(ctx, ga, engine) -> int:
    """The shm plane's routes so far.  An arena in host memory may take
    them; an arena on the chip must take none, and must not even probe
    as host-visible."""
    from repro.core import (Locality, classify_locality, deref,
                            shm_supported, shm_writable)
    poolid, _, _ = deref(ctx.heap, ctx.teams_by_slot, ga.gptr)
    routes = engine.shm_puts + engine.shm_collective_ops
    on_host = all(d.platform == "cpu" for d in ctx.state[poolid].devices())
    if not on_host:
        check("no shm routes on device arenas",
              routes == 0 and not shm_supported(ctx, poolid)
              and not shm_writable(ctx, poolid)
              and classify_locality(ctx, ga.gptr) is Locality.REMOTE,
              f"{routes} shm routes")
    return routes


def runtime_phase(seed: int, *, n_units: int = 8,
                  pool_bytes: int = RUNTIME_POOL_BYTES,
                  max_bytes: int = SWEEP_MAX_BYTES) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import DartConfig, dart_exit, dart_init

    rng = np.random.default_rng(seed)
    ctx = dart_init(n_units=n_units, config=DartConfig(
        team_pool_bytes=pool_bytes, non_collective_pool_bytes=MiB))
    eng = ctx.engine
    try:
        sweep = ctx.alloc((2 * max_bytes,), jnp.uint8)
        mat = ctx.alloc((64, 256), jnp.float32)
        accf = ctx.alloc((4096,), jnp.float32)
        acci = ctx.alloc((4096,), jnp.int32)
        cells = ctx.alloc((4,), jnp.int32)
        col = ctx.alloc((1024,), jnp.float32)
        icol = ctx.alloc((256,), jnp.int32)
        mirror = Mirror(ctx, sweep)
        units = sweep.units
        arena_shape = ctx.state[mirror.poolid].shape
        print(f"runtime: dart_init(n_units={n_units}) team arena "
              f"{arena_shape[0]} x {arena_shape[1]} B "
              f"({arena_shape[0] * arena_shape[1] // MiB} MiB), engine "
              f"impl={eng.impl}", flush=True)

        # blocking + non-blocking put/get, every unit, 1 B .. max_bytes
        sizes = [1 << i for i in range(max_bytes.bit_length())]
        span = 2 * max_bytes
        for n in sizes:
            for u in units:
                for blocking in (True, False):
                    lo = int(rng.integers(0, span - n + 1))
                    data = rng.integers(0, 256, n, dtype=np.uint8)
                    ref = sweep.at[u, lo:lo + n]
                    if blocking:
                        ref.put(data)
                        got = np.asarray(ref.get())
                    else:
                        ref.put_nb(data).wait()
                        got = np.asarray(ref.get_nb().value())
                    mirror.write(sweep, u, lo, data)
                    check(f"put/get {n} B unit {u} blocking={blocking}",
                          np.array_equal(got, data))
        print(f"runtime: blocking + non-blocking put/get to {len(units)} "
              f"units x {len(sizes)} sizes ({sizes[0]} B .. "
              f"{sizes[-1]} B): OK", flush=True)

        # one coalesced put_nb epoch to every unit (mixed sizes)
        d0 = eng.dispatch_count
        with ctx.epoch():
            for i, u in enumerate(units):
                n = 4096 + 512 * i
                lo = int(rng.integers(0, span - n + 1))
                data = rng.integers(0, 256, n, dtype=np.uint8)
                sweep.at[u, lo:lo + n].put_nb(data)
                mirror.write(sweep, u, lo, data)
        check("coalesced epoch is one dispatch",
              eng.dispatch_count - d0 == 1,
              f"{eng.dispatch_count - d0} dispatches")

        # one strided column get per unit
        for u in units:
            m = rng.standard_normal(mat.shape).astype(np.float32)
            mat[u].put(m)
            mirror.write(mat, u, 0, m)
            c = int(rng.integers(0, mat.shape[1]))
            check(f"strided column get unit {u}",
                  np.array_equal(np.asarray(mat.at[u, :, c].get()), m[:, c]))
        print("runtime: coalesced put_nb epoch (1 dispatch) + strided "
              "column gets: OK", flush=True)

        for ga, dt in ((accf, np.float32), (acci, np.int32)):
            for u in units:
                v = (rng.standard_normal(ga.shape) * 100).astype(dt)
                ga[u].put(v)
                mirror.write(ga, u, 0, v)
        _accumulates(ctx, mirror, accf, acci, rng)
        _atomics(ctx, mirror, cells)
        print("runtime: accumulate (f32 sum, i32 max), get_accumulate, "
              "fetch_and_add, CAS, MCS-lock counter: OK", flush=True)
        _collectives(ctx, mirror, col, icol, rng)
        print("runtime: bcast/gather/scatter/allreduce/reduce on "
              "DART_TEAM_ALL: OK", flush=True)

        ctx.engine.flush()
        for poolid, arena in ctx.state.items():
            want = (mirror.bytes if poolid == mirror.poolid
                    else np.zeros(arena.shape, np.uint8))
            same = np.array_equal(np.asarray(arena), want)
            check(f"pool {poolid} arena equals its numpy mirror", same)
        print("runtime: every arena equals its numpy mirror byte for "
              "byte", flush=True)
        routes = shm_routes(ctx, sweep, eng)
        stats = eng.dispatch_stats()
        print(f"runtime: engine impl={stats['impl']} dispatches per impl="
              f"{stats['dispatches']} impl_fallbacks="
              f"{stats['impl_fallbacks']} compile_count="
              f"{stats['compile_count']} plan_cache_hits="
              f"{stats['plan_cache_hits']} shm_routes={routes} "
              f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}",
              flush=True)
    finally:
        dart_exit(ctx)


def serve_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config("llama3-8b"),
                               n_layers=SERVE_LAYERS,
                               param_dtype="bfloat16")


def serve_phase(seed: int, cfg=None, *, max_new_tokens: int = 8) -> None:
    import jax

    from repro.models import api
    from repro.serve import ContinuousEngine

    cfg = serve_config() if cfg is None else cfg
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    n_params = api.param_count(cfg)
    print(f"serve: {cfg.arch_id} d_model {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads}, head_dim {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}, {cfg.param_dtype} random weights "
          f"(seed {seed}); cut: n_layers {cfg.n_layers} "
          f"({n_params / 1e9:.2f}B params)", flush=True)
    rng = np.random.default_rng(seed)
    templates = [rng.integers(1, cfg.vocab, n).astype(np.int32)
                 for n in (12, 20, 29)]
    prompts = [templates[i] for i in (0, 1, 0, 2, 1, 0, 2, 0)]

    def serve(prefix_cache: bool):
        eng = ContinuousEngine(cfg, params, max_batch=8, max_seq=256,
                               block_tokens=8, n_units=4,
                               n_cache_blocks=64, prefix_cache=prefix_cache)
        try:
            reqs = [eng.submit(p, max_new_tokens=max_new_tokens)
                    for p in prompts]
            eng.run_until_idle()
        finally:
            eng.stop()
        for r in reqs:
            check(f"request {r.rid} answered",
                  r.done.is_set() and r.output is not None
                  and len(r.output) == max_new_tokens
                  and np.all((r.output >= 0) & (r.output < cfg.vocab)),
                  f"finish_reason={r.finish_reason}")
        return [r.output for r in reqs], eng

    outs, eng = serve(prefix_cache=True)
    stats = eng.stats()
    prefix = stats["prefix"]
    check("prefix hit served by one-sided gets",
          prefix["hits"] >= 1 and prefix["fetch_get_nb_ops"] >= 1,
          str(prefix))
    dstats = eng.dart.engine.dispatch_stats()
    check("no Pallas fallback in the serve plane",
          dstats["impl_fallbacks"] == 0)
    ref_outs, _ = serve(prefix_cache=False)
    same = all(np.array_equal(a, b) for a, b in zip(outs, ref_outs))
    check("greedy outputs equal the prefix_cache=False run", same)
    print(f"serve: {len(outs)} requests answered, prefix hits "
          f"{prefix['hits']}/{prefix['lookups']} (get_nb ops "
          f"{prefix['fetch_get_nb_ops']}, flushes "
          f"{prefix['fetch_flushes']}), prefills {stats['prefills']}, "
          f"decode steps {stats['decode_steps']}; outputs equal the "
          f"cache-off run: OK", flush=True)
    print(f"serve: DART engine impl={dstats['impl']} dispatches per impl="
          f"{dstats['dispatches']} impl_fallbacks="
          f"{dstats['impl_fallbacks']} peak_bytes_in_use="
          f"{peak_bytes(jax.devices()[0])}", flush=True)


def _device_plane(mesh, n: int) -> None:
    """The device-plane shmem_* / team_* ops inside shard_map."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import (shmem_get, shmem_put, team_all_gather,
                            team_all_to_all, team_psum)

    def smap(f, in_specs, out_specs):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    groups = [list(range(n // 2)), list(range(n // 2, n))]
    ring = [(i, (i + 1) % n) for i in range(n)]
    vals = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)
    arena = jnp.zeros((n, 1024), jnp.uint8)
    put = smap(lambda a, v: shmem_put(a, v, 128, ring, "unit"),
               (P("unit", None), P("unit", None)), P("unit", None))
    arena = put(arena, vals)
    got = np.asarray(arena)[:, 128:144].view(np.float32)
    check("shmem_put ring", np.array_equal(got, np.roll(vals, 1, axis=0)))
    rev = [((i + 1) % n, i) for i in range(n)]
    get = smap(lambda a: shmem_get(a, 128, 16, rev, "unit", (4,),
                                   jnp.float32),
               P("unit", None), P("unit"))
    fetched = np.asarray(get(arena)).reshape(n, 4)
    check("shmem_get ring", np.array_equal(fetched, np.asarray(vals)))
    x = jnp.arange(n, dtype=jnp.float32)
    coll = smap(lambda xi: (team_psum(xi, "unit", groups),
                            team_all_gather(xi, "unit", groups)),
                P("unit"), (P("unit"), P("unit")))
    s, ag = coll(x)
    half = n // 2
    want = [sum(range(half))] * half + [sum(range(half, n))] * half
    check("team_psum", np.array_equal(np.asarray(s), want))
    ag = np.asarray(ag).reshape(n, half)
    check("team_all_gather", np.array_equal(ag[0], np.arange(half))
          and np.array_equal(ag[-1], np.arange(half, n)))
    xs = jnp.arange(n * half, dtype=jnp.float32).reshape(n, half)
    a2a = smap(lambda xi: team_all_to_all(xi[0], "unit", 0, 0, groups)[None],
               P("unit", None), P("unit", None))
    out = np.asarray(a2a(xs))
    blk = np.asarray(xs).reshape(2, half, half)
    check("team_all_to_all", all(
        np.array_equal(out[g * half:(g + 1) * half], blk[g].T)
        for g in range(2)))


def multichip_phase(seed: int, n_chips: int = 4,
                    pool_bytes: int = MULTICHIP_POOL_BYTES) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import DartConfig, dart_exit, dart_init
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(f"{n_chips} devices", len(devices) >= n_chips,
          f"found {len(devices)}")
    mesh = make_mesh((n_chips,), ("unit",), devices=devices[:n_chips])
    rng = np.random.default_rng(seed)
    ctx = dart_init(mesh=mesh, unit_axes=("unit",), config=DartConfig(
        team_pool_bytes=pool_bytes, non_collective_pool_bytes=MiB))
    try:
        buf = ctx.alloc((MiB,), jnp.uint8)
        accf = ctx.alloc((4096,), jnp.float32)
        acci = ctx.alloc((4096,), jnp.int32)
        col = ctx.alloc((1024,), jnp.float32)
        icol = ctx.alloc((256,), jnp.int32)
        mirror = Mirror(ctx, buf)
        units = buf.units
        print(f"chips: dart_init(mesh={n_chips} chips, unit_axes=('unit',))"
              f" -> {ctx.n_units} units, team arena "
              f"{ctx.state[mirror.poolid].shape}, engine impl="
              f"{ctx.engine.impl}", flush=True)
        for n in MULTICHIP_SIZES:
            for u in units:
                lo = int(rng.integers(0, MiB - n + 1))
                data = rng.integers(0, 256, n, dtype=np.uint8)
                buf.at[u, lo:lo + n].put(data)
                mirror.write(buf, u, lo, data)
                check(f"put/get {n} B unit {u}", np.array_equal(
                    np.asarray(buf.at[u, lo:lo + n].get()), data))
        for ga, dt in ((accf, np.float32), (acci, np.int32)):
            for u in units:
                v = (rng.standard_normal(ga.shape) * 100).astype(dt)
                ga[u].put(v)
                mirror.write(ga, u, 0, v)
        _accumulates(ctx, mirror, accf, acci, rng)
        _collectives(ctx, mirror, col, icol, rng)
        print(f"chips: put/get ({MULTICHIP_SIZES[0]} B .. "
              f"{MULTICHIP_SIZES[-1]} B), accumulate, get_accumulate "
              f"and bcast/gather/scatter/allreduce/reduce to every unit: "
              f"OK", flush=True)
        ctx.engine.flush()
        arena = ctx.state[mirror.poolid]
        check("arena equals its numpy mirror",
              np.array_equal(np.asarray(arena), mirror.bytes))
        shard_devices = set()
        for shard in arena.addressable_shards:
            rows = shard.index[0]
            row = rows.start or 0
            check(f"chip {shard.device.id} holds one row",
                  shard.data.shape == (1, arena.shape[1])
                  and (rows.stop or arena.shape[0]) == row + 1)
            check(f"chip {shard.device.id} row {row} equals the mirror",
                  np.array_equal(np.asarray(shard.data)[0],
                                 mirror.bytes[row]))
            shard_devices.add(shard.device)
        check("every chip holds its own row",
              len(shard_devices) == n_chips)
        routes = shm_routes(ctx, buf, ctx.engine)
        stats = ctx.engine.dispatch_stats()
        print(f"chips: every chip holds only its own row; engine impl="
              f"{stats['impl']} dispatches per impl={stats['dispatches']} "
              f"impl_fallbacks={stats['impl_fallbacks']} shm_routes={routes}",
              flush=True)
    finally:
        dart_exit(ctx)
    _device_plane(mesh, n_chips)
    peaks = {d.id: peak_bytes(d) for d in devices[:n_chips]}
    print(f"chips: device plane shmem_put/get, team_psum/all_gather/"
          f"all_to_all: OK; peak_bytes_in_use per device={peaks}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-heap path on 4 chips")
    args = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository is not next to this script "
              f"({src}: {e})", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r})",
              file=sys.stderr)
        return 2

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {platform} {devices[0].device_kind} x {len(devices)}",
          flush=True)
    phases = ([multichip_phase] if args.chips == 4
              else [runtime_phase, serve_phase])
    try:
        for phase in phases:
            t0 = time.perf_counter()
            phase(args.seed)
            print(f"{phase.__name__}: {time.perf_counter() - t0:.1f} s "
                  "wall, compilation included", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
