"""Continuous-batching serving driver over the paged cache pool.

A thin driver over ``repro.serve.Scheduler``: submit a synthetic
request trace, drain it, and report throughput plus the per-step
ServeStats counters.  The decode loop runs at a fixed (max_batch, 1)
shape — after warmup the jit trace counts stay frozen no matter how
requests churn (printed at the end as the zero-recompile witness).

    PYTHONPATH=src python launch/serve.py --arch minitron-8b --requests 16
    PYTHONPATH=src python launch/serve.py --arch mamba2-1.3b \
        --max-batch 8 --n-blocks 128
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ALIASES, get_reduced
from repro.models import transformer as T
from repro.models.params import tree_materialize
from repro.serve import PoolConfig, Request, Scheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b", choices=list(ALIASES))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16,
                    help="new tokens per request")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--n-blocks", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-pad", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_reduced(args.arch)
    params = tree_materialize(T.model_defs(cfg), jax.random.PRNGKey(0),
                              cfg.param_dtype)
    pc = PoolConfig(
        max_batch=args.max_batch, block_size=args.block_size,
        n_blocks=args.n_blocks, max_len=args.max_len,
        prompt_pad=args.prompt_pad,
    )
    sch = Scheduler(cfg, params, pc, temperature=args.temperature)

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(4, args.prompt_pad + 1))
        kw = {}
        if cfg.family == "encdec":
            kw["enc_embeds"] = np.asarray(jax.random.normal(
                jax.random.PRNGKey(100 + i),
                (cfg.encoder_len, cfg.d_model),
            ))
        reqs.append(Request(
            rid=i, tokens=rng.integers(0, cfg.vocab_size, size=plen),
            max_new_tokens=args.tokens, **kw,
        ))

    t0 = time.time()
    results, stats = sch.run(reqs)
    wall = time.time() - t0

    total = stats.total_tokens + args.requests  # + one token per prefill
    print(f"arch={args.arch} requests={args.requests} "
          f"max_batch={args.max_batch} pool={args.n_blocks}x{args.block_size}")
    print(f"drained in {len(stats.steps)} steps / {wall:.2f}s "
          f"({total / wall:.0f} tok/s)")
    print(f"peak active slots: {stats.peak_active}/{args.max_batch}  "
          f"peak pool occupancy: {stats.peak_occupancy:.2f}  "
          f"preemptions: {stats.preemptions}")
    waits = [r.admitted_s - r.submitted_s for r in stats.requests.values()]
    print(f"queue wait (submit to admission) median: "
          f"{1e3 * float(np.median(waits)):.1f} ms")
    print(f"jit traces (frozen after warmup): {sch.trace_counts}")
    for r in reqs[:2]:
        print(f"  request[{r.rid}] generated ids: {results[r.rid][:12]} ...")


if __name__ == "__main__":
    main()
