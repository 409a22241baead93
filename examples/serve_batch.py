"""Serving example: a small model answering queued requests.

    PYTHONPATH=src python examples/serve_batch.py

Part 1 submits a mixed bag of prompts to the synchronous-wave
ServeEngine (packs waves, prefills, decodes greedily).  Part 2 replays
the same prompts through the ContinuousEngine: per-step admit/retire
over fixed decode slots, with prefill KV state published into the PGAS
prefix/KV-block cache — the repeat pass is served from one-sided block
reads instead of recompute (see repro/serve/ and docs/API.md
"Serving plane").
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import api
from repro.models.config import reduced_for_smoke
from repro.serve import ContinuousEngine, Request, ServeEngine

enable_compile_cache()
cfg = reduced_for_smoke(get_config("llama3-8b"))
params = api.init_params(cfg, jax.random.PRNGKey(0))

engine = ServeEngine(cfg, params, max_batch=4, max_seq=64)

rng = np.random.RandomState(0)
reqs = []
for i in range(10):
    plen = rng.randint(4, 12)
    prompt = rng.randint(0, cfg.vocab, size=plen).astype(np.int32)
    reqs.append(engine.submit(prompt, max_new_tokens=8))

done = engine.drain()
print(f"completed {done} requests in "
      f"{(done + engine.max_batch - 1) // engine.max_batch} waves")
for r in reqs:
    assert r.done.is_set() and r.output is not None
    print(f"  req {r.rid}: prompt[{len(r.prompt)}] -> {r.output.tolist()}")
print("PGAS cache segment gptr:", engine.cache_gptr)

# -- continuous batching + the global prefix cache ---------------------
cont = ContinuousEngine(cfg, params, max_batch=4, max_seq=64,
                        block_tokens=8, n_cache_blocks=64)
prompts = [r.prompt for r in reqs]
creqs = [cont.submit(p, max_new_tokens=8) for p in prompts]
cont.run_until_idle()
# (outputs can differ from the wave engine's: each engine conditions
# on its own left-padding — wave-max vs pow2 bucket)
assert all(r.done.is_set() and r.output.shape == (8,) for r in creqs)
print(f"continuous pass completed {len(creqs)} requests")

again = [cont.submit(p, max_new_tokens=8) for p in prompts]
cont.run_until_idle()
for a, b in zip(creqs, again):
    np.testing.assert_array_equal(a.output, b.output)
st = cont.stats()
print(f"repeat pass: {st['prefix']['hits']} prefix hits, "
      f"{st['prefix']['fetch_get_nb_ops']} one-sided block reads, "
      f"prefills stayed at {st['prefills']}")
cont.stop()
print("OK")
