"""Compile every plan a cell's window dispatches for a described TPU v5e,
without a chip, and print each compile's seconds and memory.

    JAX_PLATFORMS=cpu python3 dartbench/rehearse.py [--cells lat-small ...]

A cell's plans are the engine's segmented scatter or gather (each op
kind's ``PLAN``; a kind without one is skipped) at the run-length and
segment buckets of its traffic's warm-up epochs, on its deployment's
arena on one chip.  A compile that passes is not a chip run; it shows
what the chip's compiler accepts and the scratch each plan asks for.
Run by hand; the persistent compilation cache is off, since an entry
for a described chip cannot be read back.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from dartbench import generator  # noqa: E402
from dartbench.run import ROOT, load_cell, load_json  # noqa: E402

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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", nargs="*")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import segmented_copy as sc

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = load_json(ROOT / "BENCHMARK.json")
    names = args.cells or [w["name"] for w in bench["workloads"]]
    for name in names:
        _, cell, config, mix = load_cell(name)
        traffic = generator.generate(mix, config, 0)
        shape = (int(config["units"]), int(config["window_bytes_per_unit"]))
        small = SingleDeviceSharding(topo.devices[0])
        arena = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=small)
        for plan, kb, seg in plan_shapes(traffic):
            desc = jax.ShapeDtypeStruct((kb, sc.DESC_COLS), jnp.int32,
                                        sharding=small)
            if plan == "scatter":
                flat = max(kb * seg + seg, sc.FLAT_FLOOR)
                fn, _ = sc.scatter_plan(shape, kb, seg, flat, ordered=False)
                call = (arena, desc, jax.ShapeDtypeStruct(
                    (flat,), jnp.uint8, sharding=small))
            else:
                fn, _ = sc.gather_plan(shape, kb, seg)
                call = (arena, desc)
            t = time.perf_counter()
            compiled = fn.lower(*call).compile()
            seconds = time.perf_counter() - t
            mem = compiled.memory_analysis()
            print(f"{name} {config['name']} {plan} kb={kb} seg={seg}: "
                  f"{seconds:.1f} s temp={mem.temp_size_in_bytes} "
                  f"argument={mem.argument_size_in_bytes} "
                  f"output={mem.output_size_in_bytes} "
                  f"alias={mem.alias_size_in_bytes}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
