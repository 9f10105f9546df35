"""The plain references against the program at small sizes on the CPU,
where both compute in high precision and must agree."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import inputs, lm_weights
from chipbench.references import dense_lm, ridge
from chipbench.systems import serve_lm


def small_ridge(d=60, n=4, q=10, k=5):
    idx, val, y = inputs.regression(n, q, d, k, 0.1, seed=3,
                                    dtype=np.float64)
    edges = inputs.erdos_renyi_edges(n, 0.6, 1)
    return idx, val, y, edges, inputs.laplacian_mixing(n, edges)


def test_ridge_dual_root_matches_the_programs_newton_root():
    from repro.core import reference
    from repro.core.operators import OperatorSpec
    from repro.data.synthetic import SparseDataset

    idx, val, y, _, _ = small_ridge()
    lam = 1.0 / (10 * 40)
    ours = ridge.root(idx, val, y, 60, lam)
    theirs = reference.solve_root(OperatorSpec("ridge"),
                                  SparseDataset(idx, val, y, 60), lam)
    assert np.abs(ours - theirs).max() <= 1e-10 * np.abs(theirs).max()


def test_dsba_trajectory_matches_the_program_in_float64():
    from repro.core.mixing import Graph
    from repro.core.solvers import make_problem, solve
    from repro.data.synthetic import SparseDataset

    idx, val, y, edges, w = small_ridge()
    lam = 1.0 / (10 * 40)
    stream = inputs.index_stream(200, 4, 10, np.random.SeedSequence([5, 0]))
    ref = ridge.dsba_trajectory(idx, val, y, 60, w, lam, 0.5, stream)
    prob = make_problem("ridge", SparseDataset(idx, val, y, 60),
                        Graph(4, edges), w=w, lam=lam)
    for comm in ("dense", "sparse"):
        got = solve(prob, "dsba", comm=comm, steps=200, record_every=200,
                    indices=stream, alpha=0.5).z
        assert np.abs(got - ref).max() <= 1e-9 * np.abs(ref).max(), comm
    # and it converges towards the root
    z_star = ridge.root(idx, val, y, 60, lam)
    assert ridge.rel_dist2(ref, z_star) < 0.5



TINY = dict(name="tiny", family="dense", **serve_lm.BLOCK, n_layers=2,
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
            vocab_size=256,
            rope_theta=10000.0, norm_eps=1e-6, dtype="float32",
            serving={"max_batch": 4, "block_size": 16, "prompt_pad": 64,
                     "max_len": 96})


def test_dense_reference_matches_the_programs_prefill():
    from repro.models import transformer as T

    mcfg = serve_lm.model_config(TINY)
    params = lm_weights.make(TINY, 11, "float32")
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, 40).astype(np.int32)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :40] = tokens
    with jax.default_matmul_precision("highest"):
        _, logits = jax.jit(functools.partial(T.prefill, mcfg))(
            params, jnp.asarray(padded), T.init_cache(mcfg, 1, 64),
            valid_len=jnp.asarray([40], jnp.int32))
    model = dense_lm.LM(2, 64, 1e-6, 10000.0)
    x = dense_lm.hidden(params, jnp.asarray(padded[0]), model)
    h = dense_lm._rms(x[39], params["final_norm"], 1e-6)
    ref = np.asarray(h @ params["lm_head"])
    got = np.asarray(logits[0])
    assert np.abs(got - ref).max() <= 1e-4 * np.abs(ref).max()


def test_served_tokens_of_the_program_have_no_gap_in_float32():
    """Greedy tokens served by the scheduler through prefill and the paged
    decode step, computed in float32, are the reference's first choice."""
    from repro.serve import PoolConfig, Request, Scheduler

    mcfg = serve_lm.model_config(TINY)
    params = lm_weights.make(TINY, 12, "float32")
    sch = Scheduler(mcfg, params, PoolConfig(max_batch=4, block_size=16,
                                             n_blocks=25, max_len=96,
                                             prompt_pad=64))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (20, 64, 7)]
    with jax.default_matmul_precision("highest"):
        results, _ = sch.run([Request(i, p, 24) for i, p in
                              enumerate(prompts)])
    model = dense_lm.LM(2, 64, 1e-6, 10000.0)
    for i, p in enumerate(prompts):
        seq = np.zeros(96, np.int32)
        seq[: len(p)] = p
        seq[len(p): len(p) + 24] = results[i]
        gaps = dense_lm.served_gaps(params, jnp.asarray(seq), len(p), 32,
                                    model)
        assert float(np.max(np.asarray(gaps)[:24])) <= 1e-4
        ctl = dense_lm.choice_gaps(params, jnp.asarray(seq), len(p), 32,
                                   model, "fp8")
        assert np.all(np.asarray(ctl) >= 0)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_rel_dist2(dtype):
    z_star = np.array([3.0, 4.0])
    z = np.array([[3.0, 4.0], [0.0, 0.0]], dtype)
    assert ridge.rel_dist2(z, z_star) == pytest.approx(0.5)


def test_the_program_serves_only_its_own_dense_block():
    published = dict(TINY, mlp="squared_relu", norm="layernorm",
                     rotary_fraction=0.5)
    with pytest.raises(ValueError, match="dense block"):
        serve_lm.model_config(published)
