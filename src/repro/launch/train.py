"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b \
        --steps 100 --batch 8 --seq 256 --reduced        # CPU-runnable
    PYTHONPATH=src python -m repro.launch.train --arch llama3-405b \
        --mesh single                                     # on a real pod

Wires together: config registry, mesh + sharding, deterministic resumable
data pipeline, AdamW train step (or multi-pod DSBA gossip), async sharded
checkpointing with exact resume, and the XLA latency-hiding flags for
collective/compute overlap on TPU.
"""
from __future__ import annotations

import argparse
import os
import time

# collective/compute overlap (no-ops on CPU; the TPU deployment flags).
# Appended to whatever LIBTPU_INIT_ARGS already holds, never replacing it;
# a flag the environment already names keeps the environment's value.
_OVERLAP_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)
_libtpu_args = os.environ.get("LIBTPU_INIT_ARGS", "")
os.environ["LIBTPU_INIT_ARGS"] = " ".join([_libtpu_args] + [
    f for f in _OVERLAP_FLAGS if f.split("=")[0] + "=" not in _libtpu_args
]).strip()

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import ALIASES, get_config, get_reduced
from repro.data.sharded_loader import LoaderConfig, batch_at
from repro.optim.adam import AdamConfig
from repro.train.step import TrainConfig, init_train_state, train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b", choices=list(ALIASES))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="none", choices=["none", "single", "multi"],
                    help="'none' runs unsharded (CPU); single/multi build the "
                         "production mesh (needs real devices)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    tc = TrainConfig(
        optimizer=AdamConfig(lr=args.lr), microbatches=args.microbatches
    )
    ld = LoaderConfig(cfg.vocab_size, args.batch, args.seq, seed=args.seed)

    if args.mesh != "none":
        from repro.launch.mesh import make_production_mesh
        from repro.models.layers import use_constraint_mesh
        from repro.train.step import make_jitted_train_step

        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        ctx = use_constraint_mesh(mesh)
        ctx.__enter__()
        step_fn = make_jitted_train_step(mesh, cfg, tc)
    else:
        step_fn = jax.jit(lambda s, b: train_step(cfg, tc, s, b))

    mgr = CheckpointManager(args.ckpt_dir)
    state = init_train_state(cfg, tc, jax.random.PRNGKey(args.seed))
    restored, at = mgr.restore(state)
    if restored is not None:
        state = restored
        print(f"resumed from step {at}")

    t0 = time.time()
    start = int(state["step"])
    for i in range(start, args.steps):
        batch = {k: np.asarray(v) for k, v in batch_at(ld, i).items()}
        state, metrics = step_fn(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"({(time.time() - t0) / max(1, i - start + 1):.2f} s/step)",
                  flush=True)
        if args.ckpt_every and i and i % args.ckpt_every == 0:
            mgr.save(i, state, async_=True)
    mgr.wait()
    mgr.save(args.steps, state, async_=False)
    print("done; final checkpoint committed.")


if __name__ == "__main__":
    main()
