"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 dartbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): the deployment's ``dart_init``, its
window array, the traffic drawn from ``--seed`` on the host, and a
warm-up that issues every dispatch shape the traffic uses.  Then the
window: epochs are issued for ``--seconds`` seconds.  With ``--trace 1``
a stretch of the window is profiled and the cell's per-layer metrics are
read from it; with ``--trace 0`` its end-to-end metrics are read from
the host clock.  After the window the device's peak memory is read, the
system's windows are copied to the host, the runtime is shut down, and
the run is compared with the plain reference (:mod:`dartbench.reference`).

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared, each beside its
limit.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from dartbench import (drive, generator, plugins, reference,  # noqa: E402
                       systems)

#: where the traced stretch starts and how long it lasts, as shares of
#: the window, capped in seconds
TRACE_SKIP = (0.2, 1.0)
TRACE_SPAN = (0.4, 2.0)


class BenchError(RuntimeError):
    """A run that cannot be measured: it prints no result."""


def process_age_s() -> float:
    """Seconds since this process started (the kernel's record), or since
    this module was imported where that record cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - _T_IMPORT


def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT):
    """``(bench, cell, config, mix)`` for the cell named ``name``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"no configuration {cell['config']!r}")
    config = load_json(root / configs[cell["config"]]["file"])
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    name = cell["name"]

    def listed(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m for m in bench["end_to_end"] if listed(m) is not False]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    out = []
    for m in bench["per_layer"]:
        here = listed(m)
        if here or (here is None and m["moves"] in moved):
            out.append(m)
    return out


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return plugins.load("metrics", metric).read


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; a BenchError where there are
    none or too few."""
    import jax
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform "
                         f"{devices[0].platform if devices else None!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


class _CompileCounter:
    """Counts JAX's traces and backend compiles, to show that none
    happens inside the window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


def _stretch_work(traffic, win) -> dict:
    i0, i1 = win.stretch
    eps = win.epoch[i0:i1]
    c0, c1 = win.stretch_counters
    return {"bytes": int(traffic.epoch_bytes()[eps].sum()),
            "ops": int(traffic.epoch_ops()[eps].sum()),
            "dispatches": c1["dispatch_count"] - c0["dispatch_count"]}


class RunView:
    """What a metric reader sees of a run."""

    def __init__(self, traffic, window, setup_s, trace, peaks, stretch):
        self.traffic = traffic
        self.window = window
        self.setup_s = setup_s
        self.trace = trace
        self.peaks = peaks
        #: payload bytes, ops and dispatches of the traced stretch
        self.stretch = stretch


def run_cell(cell: dict, config: dict, mix: dict, metrics: list, *,
             seed: int, seconds: float, trace: bool, devices: list,
             peaks: dict, system_factory=systems.DartSystem) -> dict:
    """Set up, warm up, measure, compare: the result line as a dict."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _CompileCounter()
    traffic = generator.generate(mix, config, seed)
    system = system_factory(config, devices)
    span = drive.trace_span if trace else drive.null_span
    if trace and traffic.blocking:
        entries = {getattr(op, "ENGINE_ENTRY", None) for op in traffic.ops}
        system.instrument(span, sorted(entries - {None}))
    driver = drive.Driver(system, traffic, span)
    try:
        driver.warm_up()
        gc.collect()
        gc.freeze()
        before = system.counters()["compile_count"] + compiles.n
        setup_s = process_age_s()
        tracer = None
        if trace:
            log_dir = tempfile.mkdtemp(prefix="dartbench-trace-")
            tracer = drive.Tracer(
                log_dir, min(TRACE_SKIP[0] * seconds, TRACE_SKIP[1]),
                min(TRACE_SPAN[0] * seconds, TRACE_SPAN[1]))
        win = driver.window(seconds, tracer)
        window_compiles = (system.counters()["compile_count"] + compiles.n
                           - before)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices) if devices else 0
        windows = system.windows()
    finally:
        system.close()
    gc.unfreeze()
    tr = stretch = None
    if tracer is not None:
        from dartbench import trace as trace_mod
        try:
            tr = trace_mod.load(pathlib.Path(tracer.log_dir))
        finally:
            shutil.rmtree(tracer.log_dir, ignore_errors=True)
        stretch = _stretch_work(traffic, win)
    check = reference.compare(traffic, driver.sequence, driver.values,
                              windows, window_compiles)
    view = RunView(traffic, win, setup_s, tr, peaks, stretch)
    values = {}
    for m in metrics:
        v = reader(m["name"])(view)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    window_ops = int(traffic.epoch_ops()[win.epoch].sum())
    device = {"platform": devices[0].platform if devices else "none",
              "kind": devices[0].device_kind if devices else "none",
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": check["correct"], "attempted": window_ops,
              "failed": check["failed_ops"], "metrics": values,
              "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_mean_s()
        device["window_s"] = tr.stretch_s
        result["breakdown"] = tr.breakdown()
        result["busy_per_device_s"] = tr.busy_per_device_s()
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in check["numbers"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, mix = load_cell(args.workload)
        devices = tpu_devices(int(cell["chips"]))
        peaks = peaks_for(devices[0].device_kind)
        result = run_cell(cell, config, mix,
                          metrics_of(bench, cell, bool(args.trace)),
                          seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), devices=devices,
                          peaks=peaks)
    except (BenchError, FileNotFoundError) as e:
        print(f"dartbench: {e}", file=sys.stderr)
        return 2
    dev = result["device"]
    print(f"device: {dev['platform']} {dev['kind']} x {dev['count']}",
          file=sys.stderr)
    for d, s in result.pop("busy_per_device_s", {}).items():
        print(f"busy {d}: {s} s of {dev['window_s']} s", file=sys.stderr)
    for k, c in result["check"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
