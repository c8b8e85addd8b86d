"""End-to-end training driver: Cheetah-pruned data pipeline → LM training
with checkpoint/restart and gradient compression.

Default preset trains a ~20M-param gemma3-family model for 40 steps on
CPU (~minutes). `--preset full` trains a ~100M-param model for 300 steps
(the deliverable configuration — run it when you have the cycles; it is
the same code path).

  PYTHONPATH=src python examples/train_lm.py [--preset full] [--resume]
"""
import argparse
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs import get_smoke
from repro.data import TokenPipeline
from repro.models import LM
from repro.train import (AdamWConfig, CompressConfig, checkpoint, init_state,
                         make_train_step)

PRESETS = {
    "quick": dict(d_model=256, n_layers=4, d_ff=1024, vocab=4096,
                  seq=128, batch=8, steps=40, microbatches=2),
    "full": dict(d_model=512, n_layers=8, d_ff=2048, vocab=32768,
                 seq=256, batch=16, steps=300, microbatches=4),
}


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="quick", choices=PRESETS)
    ap.add_argument("--ckpt-dir", default="results/ckpt_train_lm")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="cheetah TOP-N gradient compression")
    args = ap.parse_args()
    p = PRESETS[args.preset]

    base = get_smoke("gemma3-1b")
    cfg = dataclasses.replace(
        base, n_layers=p["n_layers"] // len(base.pattern) * len(base.pattern)
        or len(base.pattern), d_model=p["d_model"], d_ff=p["d_ff"],
        vocab=p["vocab"], n_heads=4, n_kv=1, head_dim=p["d_model"] // 4,
        window=64)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {n_params/1e6:.1f}M params ({cfg.n_layers}L "
          f"d={cfg.d_model} ff={cfg.d_ff} V={cfg.vocab})")

    pipe = TokenPipeline(vocab=cfg.vocab, seq_len=p["seq"],
                         batch_size=p["batch"], seed=0)
    docs = pipe.corpus(4000 if args.preset == "quick" else 20000,
                       dup_fraction=0.3)
    print("pipeline built; streaming with DISTINCT-dedup + FILTER pruning")

    ccfg = CompressConfig(density=0.05) if args.compress else None
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=20)
    step_fn = jax.jit(make_train_step(lm, None, ocfg,
                                      microbatches=p["microbatches"],
                                      compress=ccfg))
    state = init_state(lm, params, ocfg, compress=ccfg)

    start = 0
    if args.resume:
        last = checkpoint.latest_step(args.ckpt_dir)
        if last is not None:
            restored = checkpoint.restore(args.ckpt_dir, last,
                                          {"params": params, "opt": state})
            params, state = restored["params"], restored["opt"]
            start = last
            print(f"resumed from step {last}")

    t0 = time.time()
    it = iter(pipe.batches(docs))
    for step in range(start, p["steps"]):
        try:
            batch = next(it)
        except StopIteration:
            it = iter(pipe.batches(docs))
            batch = next(it)
        params, state, stats = step_fn(params, state, batch)
        if step % 10 == 0 or step == p["steps"] - 1:
            tok_s = (step - start + 1) * p["batch"] * p["seq"] / (time.time() - t0)
            extra = (f" kept={float(stats['kept_fraction']):.3f}"
                     if "kept_fraction" in stats else "")
            print(f"step {step:4d} loss={float(stats['loss']):.4f} "
                  f"gnorm={float(stats['grad_norm']):.2f} "
                  f"tok/s={tok_s:.0f}{extra}")
        if step > 0 and step % 50 == 0:
            checkpoint.save(args.ckpt_dir, step,
                            {"params": params, "opt": state}, async_=True)
    checkpoint.save(args.ckpt_dir, p["steps"], {"params": params, "opt": state})
    print(f"done in {time.time()-t0:.0f}s; pipeline stats: "
          f"seen={pipe.stats.seen_docs} deduped={pipe.stats.deduped_docs} "
          f"filtered={pipe.stats.filtered_docs}")


if __name__ == "__main__":
    main()
