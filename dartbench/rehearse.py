"""Compile every plan a cell's window dispatches for a described TPU v5e,
without a chip, and print each compile's seconds, memory per device and
the collectives in its program.

    JAX_PLATFORMS=cpu python3 dartbench/rehearse.py [--cells lat-small ...]

A cell's plans are the engine's segmented scatter or gather (each op
kind's ``PLAN``; a kind without one is skipped) at the run-length and
segment buckets of its traffic's warm-up epochs, on its deployment's
arena as the cell's chips place it (as ``systems.DartSystem`` does): on
one chip, or row-sharded one unit per chip over the chips of a
described v5e 2x2.  Each plan is the window or the lane plan, as the
engine's own rule (``onesided._window_path``) picks for contiguous ops
on that arena.  A compile that passes is not a chip run; it shows what
the chip's compiler accepts and the scratch each plan asks for.  Run by
hand; the persistent compilation cache is off, since an entry for a
described chip cannot be read back.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from dartbench import generator  # noqa: E402
from dartbench.run import ROOT, load_cell, load_json  # noqa: E402
from dartbench.trace import COLLECTIVES  # noqa: E402

#: an HLO instruction's opcode, where it is a collective
_COLLECTIVE_OP = re.compile(
    r"\s(" + "|".join(COLLECTIVES) + r")(-start|-done)?\(")


def plan_shapes(traffic) -> list:
    """``(plan, kb, seg)`` of every plan the traffic's epochs use, by the
    engine's bucketing rule."""
    from repro.kernels import segmented_copy as sc
    top = traffic.max_len() * traffic.dtype.itemsize
    ops = traffic.epoch_ops()
    shapes = set()
    for e in traffic.warmup_epochs():
        plan = getattr(traffic.op(e), "PLAN", None)
        if plan is None:
            continue
        shapes.add((plan,
                    sc.bucket_pow2(int(ops[e]), sc.K_FLOOR),
                    sc.bucket_pow2(int(top[e]), sc.SEG_FLOOR)))
    return sorted(shapes)


def collectives(hlo: str) -> dict:
    """How many of each collective opcode a compiled program holds."""
    out: dict = {}
    for op, half in _COLLECTIVE_OP.findall(hlo):
        out[op + half] = out.get(op + half, 0) + 1
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", nargs="*")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.core import onesided
    from repro.kernels import segmented_copy as sc
    from repro.launch.mesh import make_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = load_json(ROOT / "BENCHMARK.json")
    names = args.cells or [w["name"] for w in bench["workloads"]]
    for name in names:
        _, cell, config, mix = load_cell(name)
        traffic = generator.generate(mix, config, 0)
        units = int(config["units"])
        shape = (units, int(config["window_bytes_per_unit"]))
        if int(cell["chips"]) > 1:
            mesh = make_mesh((units,), ("unit",),
                             devices=topo.devices[:units])
            big = NamedSharding(mesh, P("unit", None))
            small = NamedSharding(mesh, P())
        else:
            big = small = SingleDeviceSharding(topo.devices[0])
        arena = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=big)
        for plan, kb, seg in plan_shapes(traffic):
            contiguous = np.zeros((kb, sc.DESC_COLS), np.int32)
            contiguous[:, sc.COUNT] = 1
            window = onesided._window_path(contiguous, seg, arena)
            desc = jax.ShapeDtypeStruct((kb, sc.DESC_COLS), jnp.int32,
                                        sharding=small)
            if plan == "scatter":
                flat = max(kb * seg + seg, sc.FLAT_FLOOR)
                fn, _ = sc.scatter_plan(shape, kb, seg, flat, ordered=False,
                                        window=window)
                call = (arena, desc, jax.ShapeDtypeStruct(
                    (flat,), jnp.uint8, sharding=small))
            else:
                fn, _ = sc.gather_plan(shape, kb, seg, window=window)
                call = (arena, desc)
            t = time.perf_counter()
            compiled = fn.lower(*call).compile()
            seconds = time.perf_counter() - t
            mem = compiled.memory_analysis()
            print(f"{name} {config['name']} {plan} "
                  f"{'window' if window else 'lane'} kb={kb} seg={seg} on "
                  f"{len(big.device_set)} chip(s): {seconds:.1f} s; per "
                  f"device temp={mem.temp_size_in_bytes} "
                  f"argument={mem.argument_size_in_bytes} "
                  f"output={mem.output_size_in_bytes} "
                  f"alias={mem.alias_size_in_bytes}; collectives "
                  f"{collectives(compiled.as_text()) or 'none'}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
