"""Batched serving with Cheetah pruning on the logit path + request dedup.

Demonstrates: request-queue DISTINCT dedup (repeated prompts hit the
response cache), batched prefill+decode, and per-shard TOP-N logit
pruning replacing the full-vocab gather (exactness property-tested in
tests/test_serve_data.py).

  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_smoke
from repro.models import LM
from repro.serve import RequestCache, ServeEngine


def main():
    compile_cache.enable()
    cfg = get_smoke("qwen3-1.7b")
    lm = LM(cfg)
    params, _ = lm.init(jax.random.key(0))
    eng = ServeEngine(lm, params, n_logit_shards=16, topk=8)
    rc = RequestCache()

    requests = ["tell me about cheetahs", "what is a switch",
                "tell me about cheetahs",           # duplicate → cache hit
                "explain pruning", "what is a switch"]
    fresh, fps = rc.dedup(requests)
    print(f"request dedup: {len(requests)} arrived → {len(fresh)} fresh "
          f"({len(requests) - len(fresh)} pruned by the DISTINCT cache)")

    rng = np.random.default_rng(0)
    B = len(fresh)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (B, 8)).astype(np.int32))
    t0 = time.time()
    out = eng.generate(prompts, max_new=16)
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.1f}s "
          f"({B * 16 / dt:.1f} tok/s) with vocab pruned "
          f"{cfg.vocab}→{16 * 8} candidates per step on the gather path")
    for i, prompt in enumerate(fresh):
        rc.put(rc._fp(prompt), out[i].tolist())
    # duplicates served from cache
    for r in requests:
        hit = rc.get(rc._fp(r))
        print(f"  {r!r}: {'cache' if hit is not None else 'model'} "
              f"→ {hit[:6] if hit else '?'}...")


if __name__ == "__main__":
    main()
