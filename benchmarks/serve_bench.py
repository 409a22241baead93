"""Serving-plane benchmark: continuous batching vs the synchronous wave.

    PYTHONPATH=src python -m benchmarks.serve_bench [--quick]

Open-loop protocol: a seeded Poisson arrival process (exponential
inter-arrival gaps — submit times do NOT depend on service progress,
so queueing delay is measured, not hidden) drives the SAME trace of
templated prompts with heterogeneous ``max_new_tokens`` through both
engines:

* :class:`repro.serve.ServeEngine` — the synchronous-wave baseline;
* :class:`repro.serve.ContinuousEngine` — per-step admit/retire over
  fixed slots with the PGAS prefix/KV-block cache.

Each engine first replays the full trace once untimed (warmup: jit
caches, DART dispatch plans, and — for the continuous engine — the
prefix directory go warm), then replays it paced for the timed pass.
Reported per engine: useful tokens/s (emitted tokens / makespan) and
p50/p99 request latency.  For the continuous engine the timed pass
additionally pins the PGAS story: prefix-hit rate, hit traffic served
by one-sided ``get_nb`` + per-target flush (engine dispatch deltas
prove the coalescing plane carried it), and ZERO steady-state
recompiles (jit cache sizes + prefill buckets + DART plan compiles all
flat).

Results merge as the ``serving`` block into
``benchmarks/out/BENCH_engine.json`` (schema BENCH_engine/v8) —
run ``python -m benchmarks.run --quick`` first;
``scripts/check_bench_schema.py`` enforces the acceptance pins.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from .common import OUT_DIR

Trace = List[Tuple[float, np.ndarray, int]]   # (arrival_s, prompt, budget)


def make_trace(rng: np.random.RandomState, *, n_requests: int,
               n_templates: int, rate_rps: float, len_lo: int = 3,
               len_hi: int = 14, budget_lo: int = 4,
               budget_hi: int = 20) -> Trace:
    """Open-loop Poisson trace over repeated prompt templates.

    Few templates + many requests = the repeat traffic real serving
    sees (popular prompts), which is what the prefix cache converts
    into one-sided block reads."""
    templates = [
        rng.randint(1, 400, size=int(rng.randint(len_lo, len_hi + 1)))
        .astype(np.int32)
        for _ in range(n_templates)]
    trace: Trace = []
    t = 0.0
    for _ in range(n_requests):
        t += float(rng.exponential(1.0 / rate_rps))
        tpl = templates[int(rng.randint(n_templates))]
        budget = int(rng.randint(budget_lo, budget_hi + 1))
        trace.append((t, tpl, budget))
    return trace


def play(engine, trace: Trace, *, paced: bool) -> List:
    """Submit the trace (paced = honor the Poisson arrival times,
    open-loop) and wait for every request to finish."""
    reqs = []
    t0 = time.perf_counter()
    for at, prompt, budget in trace:
        if paced:
            delay = at - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
        reqs.append(engine.submit(prompt, max_new_tokens=budget))
    for r in reqs:
        if not r.done.wait(timeout=300):
            raise RuntimeError(f"request {r.rid} never completed")
    return reqs


def summarize(reqs) -> Dict[str, float]:
    lat_ms = np.array([(r.t_done - r.t_submit) * 1e3 for r in reqs])
    tokens = int(sum(len(r.output) for r in reqs))
    makespan = max(r.t_done for r in reqs) - min(r.t_submit for r in reqs)
    return {
        "n_requests": len(reqs),
        "tokens": tokens,
        "makespan_s": round(makespan, 4),
        "tokens_per_s": round(tokens / max(makespan, 1e-9), 2),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "mean_ms": round(float(lat_ms.mean()), 3),
    }


def run(*, quick: bool = False, seed: int = 0) -> Dict[str, object]:
    import jax

    from repro.configs import get_config
    from repro.models import api
    from repro.models.config import reduced_for_smoke
    from repro.serve import ContinuousEngine, ServeEngine

    cfg = reduced_for_smoke(get_config("llama3-8b"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    max_batch = 4
    max_seq = 64
    n_requests = 24 if quick else 96
    rate_rps = 60.0 if quick else 80.0

    rng = np.random.RandomState(seed)
    trace = make_trace(rng, n_requests=n_requests, n_templates=6,
                       rate_rps=rate_rps)

    # -- synchronous-wave baseline --------------------------------------
    wave = ServeEngine(cfg, params, max_batch=max_batch, max_seq=max_seq)
    wave.run_forever()
    play(wave, trace, paced=False)            # warmup (untimed)
    wave_reqs = play(wave, trace, paced=True)
    wave.stop()
    wave_sum = summarize(wave_reqs)

    # -- continuous engine ----------------------------------------------
    cont = ContinuousEngine(cfg, params, max_batch=max_batch,
                            max_seq=max_seq, block_tokens=8,
                            n_cache_blocks=128)
    cont.run_forever()
    play(cont, trace, paced=False)            # warmup (untimed)
    s0 = cont.stats()
    jit0 = (cont._prefill._cache_size() + cont._decode._cache_size()
            + cont._insert._cache_size())
    cont_reqs = play(cont, trace, paced=True)
    s1 = cont.stats()
    jit1 = (cont._prefill._cache_size() + cont._decode._cache_size()
            + cont._insert._cache_size())
    cont.stop()
    cont_sum = summarize(cont_reqs)

    p0, p1 = s0["prefix"], s1["prefix"]
    lookups = p1["lookups"] - p0["lookups"]
    hits = p1["hits"] - p0["hits"]
    recompiles = ((jit1 - jit0)
                  + (s1["prefill_shape_misses"]
                     - s0["prefill_shape_misses"])
                  + (s1["engine_plan_compiles"]
                     - s0["engine_plan_compiles"]))

    serving = {
        "n_requests": n_requests,
        "poisson_rate_rps": rate_rps,
        "seed": seed,
        "max_batch": max_batch,
        "quick": quick,
        "wave": wave_sum,
        "continuous": {
            **cont_sum,
            "decode_steps": s1["decode_steps"] - s0["decode_steps"],
            "prefills": s1["prefills"] - s0["prefills"],
            "recompiles_steady_state": recompiles,
            "engine_dispatches": (s1["engine_dispatches"]
                                  - s0["engine_dispatches"]),
        },
        "speedup_tokens_per_s": round(
            cont_sum["tokens_per_s"]
            / max(wave_sum["tokens_per_s"], 1e-9), 3),
        "prefix_lookups": lookups,
        "prefix_hits": hits,
        "prefix_hit_rate": round(hits / max(lookups, 1), 3),
        "hit_fetch_get_nb_ops": (p1["fetch_get_nb_ops"]
                                 - p0["fetch_get_nb_ops"]),
        "hit_fetch_flushes": p1["fetch_flushes"] - p0["fetch_flushes"],
        "hit_fetch_dispatches": (p1["fetch_dispatches"]
                                 - p0["fetch_dispatches"]),
        "prefix_evictions": p1["evictions"] - p0["evictions"],
    }
    return serving


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smoke trace for CI (24 requests)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    serving = run(quick=args.quick, seed=args.seed)

    jpath = OUT_DIR / "BENCH_engine.json"
    if jpath.exists():
        profile = json.loads(jpath.read_text())
    else:   # standalone run: a serving-only stub (CI runs benchmarks.run
            # first, so the full profile is normally already there)
        profile = {"schema": "BENCH_engine/v8"}
    profile["serving"] = serving
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    with open(jpath, "w") as f:
        json.dump(profile, f, indent=2, sort_keys=True)
        f.write("\n")

    c, w = serving["continuous"], serving["wave"]
    print(f"serving: continuous {c['tokens_per_s']} tok/s "
          f"(p50 {c['p50_ms']}ms p99 {c['p99_ms']}ms) vs wave "
          f"{w['tokens_per_s']} tok/s (p50 {w['p50_ms']}ms p99 "
          f"{w['p99_ms']}ms) -> {serving['speedup_tokens_per_s']}x; "
          f"prefix hit rate {serving['prefix_hit_rate']} "
          f"({serving['hit_fetch_get_nb_ops']} get_nb, "
          f"{serving['hit_fetch_flushes']} per-target flushes, "
          f"{serving['hit_fetch_dispatches']} dispatches), "
          f"{c['recompiles_steady_state']} steady-state recompiles")
    print(f"# wrote {jpath}")


if __name__ == "__main__":
    main()
