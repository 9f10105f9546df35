"""Smoke test of the system's main path on one TPU chip (or a 2x2 host).

    python chip_smoke.py                 # solver + serving phases, one chip
    python chip_smoke.py --four-chips    # comm="sharded" on a v5e:2x2 host

Run it from the repository root: it imports the package from ./src. One
process drives the chip; every array and weight is generated from --seed.

Solver phase — the paper's Section 7 deployment ``ridge_rcv1``
(``configs/dsba_paper.py``): N=10 nodes on ER(0.4), q=100 rows per node,
lambda = 1/(10Q), alpha=0.5, rcv1's shape (d=47,236, 74 nonzeros per row)
in float32. ``solve(..., "dsba")`` runs over ``comm="dense"`` and
``comm="sparse"`` on one index stream. Checks: the compiled relay scan
holds the sparse_axpy kernel (``tpu_custom_call``), dense and sparse
iterates agree within a float32 bound, the mean-operator residual
||F(zbar)|| falls, and the relay receives fewer doubles than the dense
exchange.

Serving phase — ``minitron_8b`` at its published widths, cut to 4 of its
32 layers, bf16 weights from the seed, through ``serve.Scheduler`` and its
paged ``CachePool``. Checks: every request finishes, the jit trace counts
stay frozen after warm-up, the compiled prefill and paged decode step hold
the flash and decode kernels, and one request's prefill and first decode
logits agree with the same model run with kernels off.

--four-chips: a 4-node ring at rcv1 width in float32, ``comm="sharded"``
on ``make_node_mesh(4)`` against ``comm="dense"`` on one device. Checks:
agreement within a float32 bound, measured collective bytes above zero
with ``collective-permute`` in the compiled program, and every device
holding its own node's shard.

The script refuses to run (non-zero exit, before any phase) when JAX finds
no TPU or when ``REPRO_KERNEL_MODE`` would route a phase to an oracle.
Each phase prints its check values on lines of its own; any failed check
exits non-zero. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import importlib.util
import json
import os
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

KERNEL_MARK = "tpu_custom_call"  # how a compiled Pallas kernel shows in HLO
EPS32 = float(np.finfo(np.float32).eps)

_failures: list[str] = []


def check(name: str, ok: bool, detail: str) -> None:
    """Print one check's value; a failure is remembered and fails the run."""
    print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
    if not ok:
        _failures.append(name)


def kernel_calls(hlo_text: str) -> int:
    """Number of compiled Pallas kernel calls in an optimized HLO module."""
    return hlo_text.count(KERNEL_MARK)


def refusal() -> str | None:
    """Why this process must not run the smoke, or None when it may."""
    if importlib.util.find_spec("repro") is None:
        return f"the package is not in {SRC}; run the script from a checkout"
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return (
            f"JAX found no TPU (devices()[0] is {dev.platform!r}); this "
            "smoke measures the chip path only"
        )
    mode = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if mode not in ("auto", "on"):
        return (
            f"REPRO_KERNEL_MODE={mode!r} would route the kernels to an "
            "oracle or the interpreter; unset it or use 'auto'/'on'"
        )
    return None


def peak_bytes() -> int | None:
    """Peak device memory so far, where the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def relay_agreement_bound(steps: int) -> float:
    """Float32 bound on max|z_dense - z_sparse| relative to max|z_dense|.

    Both backends run the same local step; they differ in how the
    neighbour mix is summed (one full-precision matmul against the relay's
    replayed per-source deltas), so they round differently once per step.
    The relay rebuilds each neighbour's iterate by replaying the
    second-order recurrence in (2 z^s - z^{s-1}), whose error propagator
    has a double eigenvalue at 1 along the consensus direction: a rounding
    error made at step s grows linearly over the T - s steps left, and the
    T such errors sum to at most T^2 / 2 float32 epsilons of the iterate's
    scale. (Measured on the CPU at this shape: about a sixth of it.)
    """
    return 0.5 * steps**2 * EPS32


# ---------------------------------------------------------------------------
# solver phase
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def captured_relay_scan():
    """Record the relay scan that ``solve(comm="sparse")`` fetches from the
    runner cache, lowered at the arguments of its call, so its compiled
    program can be inspected. What runs is unchanged."""
    from repro.core import runner_cache

    seen: dict = {}
    cache = runner_cache.SPARSE
    get = cache.get_or_build

    def spy(key, guards, build):
        scan, tb = get(key, guards, build)

        def call(*args):
            seen["lowered"] = scan.lower(*args)
            return scan(*args)

        return call, tb

    cache.get_or_build = spy
    try:
        yield seen
    finally:
        del cache.get_or_build


def solver_phase(seed: int, steps: int = 300, d: int | None = None) -> None:
    """DSBA on ridge_rcv1: dense vs sparse relay, one index stream."""
    from repro.configs.dsba_paper import EXPERIMENTS
    from repro.core import mixing, reference
    from repro.core.dsba import draw_indices
    from repro.core.solvers import make_problem, solve
    from repro.data.synthetic import DATASET_PRESETS, make_regression

    exp = EXPERIMENTS["ridge_rcv1"]
    shape = DATASET_PRESETS[exp.dataset]
    d = shape["d"] if d is None else d
    print(f"solver phase: {exp.task} on {exp.dataset} shape, N={exp.n_nodes}"
          f" ER({exp.er_p}), q={exp.q}, d={d}, k={shape['k']}, float32, "
          f"{steps} steps", flush=True)
    # the solver entry points run with x64 on; the data stays float32,
    # which is what the compiled kernel takes (Mosaic has no float64)
    with jax.enable_x64(True):
        data = make_regression(exp.n_nodes, exp.q, d, shape["k"], seed=seed,
                               dtype=np.float32)
        graph = mixing.erdos_renyi_graph(exp.n_nodes, exp.er_p, seed=seed)
        problem = make_problem(exp.task, data, graph)
        run = functools.partial(
            solve, problem, "dsba", steps=steps, record_every=steps // 3,
            indices=draw_indices(steps, exp.n_nodes, exp.q, seed=seed),
            alpha=exp.alpha, keep_snapshots=True,
        )
        dense = run(comm="dense")
        with captured_relay_scan() as relay:
            sparse = run(comm="sparse")
        relay_hlo = relay["lowered"].compile().as_text()

        F = jax.jit(reference.mean_operator(problem.spec, data, problem.lam))
        z0 = jnp.zeros((d,), jnp.float32)
        resid = [float(jnp.linalg.norm(F(z0)))] + [
            float(jnp.linalg.norm(F(jnp.asarray(z.mean(0))))) for z in sparse.zs
        ]

    n_kernels = kernel_calls(relay_hlo)
    check("relay kernel compiled", n_kernels > 0,
          f"{KERNEL_MARK} x{n_kernels} in the compiled relay scan")
    scale = float(np.abs(dense.z).max())
    err = float(np.abs(dense.z - sparse.z).max()) / scale
    bound = relay_agreement_bound(steps)
    check("dense == sparse iterates",
          bool(np.isfinite(sparse.z).all()) and err <= bound,
          f"max|dz|/max|z| = {err:.3e} <= {bound:.3e} (0.5 T^2 eps32), "
          f"max|z| = {scale:.4e}, dtype {sparse.z.dtype}")
    falling = all(b < a for a, b in zip(resid, resid[1:]))
    check("residual falls", falling,
          "||F(zbar)|| at iters "
          f"{[0, *sparse.iters.tolist()]}: {[f'{r:.6e}' for r in resid]}")
    sd, dd = sparse.doubles_received[-1], dense.doubles_received[-1]
    check("relay sends fewer doubles", bool((sd < dd).all()),
          f"per-node totals sparse {int(sd.sum())} vs dense {int(dd.sum())} "
          f"(ratio {sd.sum() / dd.sum():.4f})")
    print(f"  peak_bytes_in_use after solver phase: {peak_bytes()}",
          flush=True)


# ---------------------------------------------------------------------------
# serving phase
# ---------------------------------------------------------------------------

# Logit agreement, kernels on vs off, relative to the largest |logit| of the
# kernels-off run. bf16 keeps 8 significant bits, so each rounding is off
# by up to 2^-9 of its value. The two paths round at different places: the
# kernels hold attention in float32 and round its output once, the jnp
# path rounds the attention probabilities and output to bf16 on the way;
# every layer then re-rounds the residual stream, and the unembedding sums
# d_model such terms. Allowing a few such roundings per layer across the
# 4 layers gives 2^-9 * 4 layers * 4 = 3.1e-2.
SERVE_LOGIT_TOL = 2.0**-9 * 4 * 4


def paged_logits(cfg, params, pc, prompt, n_decode, feed=None):
    """Prefill ``prompt`` into a fresh CachePool slot and decode n_decode
    tokens (``feed`` if given, else greedy), compiling prefill and the paged
    decode step exactly as the scheduler calls them.

    Returns (prefill HLO, decode HLO, logits rows (1 + n_decode, vocab),
    fed tokens).
    """
    from repro.models import transformer as T
    from repro.serve import CachePool

    pool = CachePool(cfg, pc)
    slot = pool.alloc_slot()
    assert pool.ensure(slot, len(prompt))
    padded = np.zeros((1, pc.prompt_pad), np.int32)
    padded[0, : len(prompt)] = prompt
    args = (params, jnp.asarray(padded), T.init_cache(cfg, 1, pc.prompt_pad))
    kw = {"valid_len": jnp.asarray([len(prompt)], jnp.int32)}
    prefill = jax.jit(functools.partial(T.prefill, cfg)).lower(
        *args, **kw).compile()
    cache, lg = prefill(*args, **kw)
    pool.write_prefill(slot, cache)
    pool.set_length(slot, len(prompt))
    rows = [np.asarray(lg[0], np.float32)]
    toks, decode = [], None
    for i in range(n_decode):
        toks.append(int(np.argmax(rows[-1])) if feed is None else feed[i])
        assert pool.ensure(slot, int(pool.lengths[slot]) + 1)
        tok = np.zeros((pc.max_batch, 1), np.int32)
        tok[slot, 0] = toks[-1]
        dargs = (params, jnp.asarray(tok), pool.pools, pool.device_table(),
                 pool.device_lengths())
        if decode is None:
            decode = jax.jit(functools.partial(T.decode_step_paged, cfg)).lower(
                *dargs).compile()
        pool.pools, lg = decode(*dargs)
        pool.bump_lengths([slot])
        rows.append(np.asarray(lg[slot], np.float32))
    return prefill.as_text(), decode.as_text(), np.stack(rows), toks


def serving_phase(
    seed: int, *, cfg=None, n_layers: int = 4, n_requests: int = 10,
    max_prompt: int = 512, new_tokens: int = 32, max_batch: int = 8,
    block_size: int = 16, n_parity_steps: int = 4,
) -> None:
    """minitron_8b (depth cut) through the continuous-batching scheduler."""
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.params import tree_materialize
    from repro.serve import PoolConfig, Request, Scheduler

    if cfg is None:
        cfg = dataclasses.replace(
            get_config("minitron_8b"), n_layers=n_layers,
            param_dtype=jnp.bfloat16,
        )
    print(f"serving phase: {cfg.name} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}, {cfg.n_layers} layers, "
          f"bf16 weights; attention_kernel={cfg.attention_kernel} "
          f"decode_kernel={cfg.decode_kernel}", flush=True)
    params = jax.jit(
        lambda key: tree_materialize(T.model_defs(cfg), key, cfg.param_dtype)
    )(jax.random.PRNGKey(seed))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f"  parameters: {n_params:,} ({2 * n_params / 1e9:.2f} GB bf16)",
          flush=True)

    max_len = max_prompt + new_tokens
    pages = -(-max_len // block_size)
    pc = PoolConfig(max_batch=max_batch, block_size=block_size,
                    n_blocks=max_batch * pages + 1, max_len=max_len,
                    prompt_pad=max_prompt)
    rng = np.random.default_rng(seed)
    lens = rng.integers(max_prompt // 8, max_prompt + 1, size=n_requests)
    lens[0] = max_prompt
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, size=n),
                    max_new_tokens=new_tokens) for i, n in enumerate(lens)]

    sch = Scheduler(cfg, params, pc)
    for r in reqs:
        sch.submit(r)
    sch.step()  # warm-up: compiles prefill and the decode step
    warm = dict(sch.trace_counts)
    sch.run()
    finished = {rid: len(t) for rid, t in sch.results.items()}
    check("every request finishes",
          finished == {r.rid: new_tokens for r in reqs},
          f"{len(finished)}/{len(reqs)} requests, tokens each "
          f"{sorted(set(finished.values()))}, {len(sch.stats.steps)} steps, "
          f"peak active {sch.stats.peak_active}")
    check("trace counts frozen after warm-up",
          sch.trace_counts == warm and warm["prefill"] == 1
          and warm["decode"] == 1,
          f"after warm-up {warm}, at the end {sch.trace_counts}")

    prompt = reqs[0].tokens
    pre_on, dec_on, on, toks = paged_logits(cfg, params, pc, prompt,
                                            n_parity_steps)
    off_cfg = dataclasses.replace(cfg, attention_kernel="jnp",
                                  decode_kernel="off")
    _, _, off, _ = paged_logits(off_cfg, params, pc, prompt, n_parity_steps,
                                feed=toks)
    n_pre, n_dec = kernel_calls(pre_on), kernel_calls(dec_on)
    check("prefill holds the flash kernel", n_pre > 0,
          f"{KERNEL_MARK} x{n_pre} in the compiled prefill")
    check("paged decode holds the decode kernel", n_dec > 0,
          f"{KERNEL_MARK} x{n_dec} in the compiled decode step")
    scale = float(np.abs(off).max())
    err = np.abs(on - off).max(axis=1) / scale
    check("kernel logits == kernels-off logits",
          bool(np.isfinite(on).all()) and float(err.max()) <= SERVE_LOGIT_TOL,
          f"max|dlogit|/max|logit| per row (prefill, then {n_parity_steps} "
          f"decode steps): {[f'{e:.2e}' for e in err]} <= "
          f"{SERVE_LOGIT_TOL:.2e}; max|logit| = {scale:.3f}")
    print(f"  peak_bytes_in_use after serving phase: {peak_bytes()}",
          flush=True)


# ---------------------------------------------------------------------------
# four chips: comm="sharded"
# ---------------------------------------------------------------------------


def sharded_phase(seed: int, steps: int = 300, d: int | None = None) -> None:
    """4-node ring, rcv1 width: sharded on a 4-device mesh vs dense."""
    from repro.core import mixing
    from repro.core.dsba import draw_indices
    from repro.core.solvers import make_problem, solve
    from repro.data.synthetic import DATASET_PRESETS, make_regression
    from repro.launch.mesh import make_node_mesh

    n, q = 4, 100
    shape = DATASET_PRESETS["rcv1"]
    d = shape["d"] if d is None else d
    print(f"sharded phase: ridge, {n}-node ring, q={q}, d={d}, "
          f"k={shape['k']}, float32, {steps} steps", flush=True)
    with jax.enable_x64(True):
        data = make_regression(n, q, d, shape["k"], seed=seed,
                               dtype=np.float32)
        problem = make_problem("ridge", data, mixing.ring_graph(n))
        mesh = make_node_mesh(n)
        run = functools.partial(
            solve, problem, "dsba", steps=steps, record_every=steps // 3,
            indices=draw_indices(steps, n, q, seed=seed), alpha=0.5,
        )
        dense = run(comm="dense")
        sharded = run(comm="sharded", comm_options={"mesh": mesh})

    scale = float(np.abs(dense.z).max())
    err = float(np.abs(dense.z - sharded.z).max()) / scale
    bound = relay_agreement_bound(steps)
    check("dense == sharded iterates",
          bool(np.isfinite(sharded.z).all()) and err <= bound,
          f"max|dz|/max|z| = {err:.3e} <= {bound:.3e} (0.5 T^2 eps32: the "
          "neighbour sum's order differs, as for the relay), "
          f"max|z| = {scale:.4e}")
    coll = sharded.extras["collectives"]
    permutes = coll["count_by_op"].get("collective-permute", 0)
    measured = float(sharded.measured_collective_bytes[-1])
    check("collectives measured", measured > 0 and permutes > 0,
          f"measured_collective_bytes = {measured:.0f} per device over "
          f"{steps} iters, collective-permute x{permutes} per iter, "
          f"{coll['bytes_per_iter']:.0f} bytes/iter")
    z = sharded.state.z
    shards = sorted(z.addressable_shards, key=lambda s: s.index[0].start)
    mesh_devs = list(mesh.devices.flat)
    placed = [
        (s.device == mesh_devs[i] and s.index[0] == slice(i, i + 1)
         and np.array_equal(np.asarray(s.data)[0], sharded.z[i]))
        for i, s in enumerate(shards)
    ]
    check("each device holds its node", len(shards) == n and all(placed)
          and len({s.device for s in shards}) == n,
          f"node -> device {[(i, s.device.id) for i, s in enumerate(shards)]}"
          f", shard shape {shards[0].data.shape}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only comm='sharded' on 4 chips against dense")
    args = ap.parse_args()

    why = refusal()
    if why:
        print(f"chip_smoke: refusing to run: {why}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if args.four_chips and len(devs) != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {len(devs)}",
              file=sys.stderr)
        return 2
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)

    if args.four_chips:
        sharded_phase(args.seed)
    else:
        solver_phase(args.seed)
        serving_phase(args.seed)

    if _failures:
        print(f"chip_smoke: failed checks: {_failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
