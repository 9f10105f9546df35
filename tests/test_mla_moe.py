"""Latent attention (MLA) and the held experts' dropless MoE layer, at
``kimi_k2.reduced()`` size on seeded weights (float32, CPU).

  * prefill plus paged decode through the Scheduler gives the logits of a
    full forward over the same tokens;
  * the absorbed decode (W_UK into the query, W_UV into the output, one
    latent row as key and value) equals the expanded attention;
  * every share of the experts, the shared expert counted once, sums to
    the uncut layer;
  * sigmoid-plus-bias selection and its weights match a NumPy oracle;
  * no token is dropped at any batch, whatever the routing;
  * ``kimi_k2.CONFIG`` holds the published numbers.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import kimi_k2
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.params import tree_materialize
from repro.serve import PoolConfig, Request, Scheduler


def _cfg(**over):
    return dataclasses.replace(kimi_k2.reduced(), compute_dtype=jnp.float32,
                               **over)


def _params(cfg, seed=0):
    return tree_materialize(T.model_defs(cfg), jax.random.PRNGKey(seed),
                            jnp.float32)


def _moe_params(cfg, seed=1):
    """One MoE layer's params (router, bias, shared) and its experts."""
    p = tree_materialize(L.moe_defs(cfg), jax.random.PRNGKey(seed),
                         jnp.float32)
    experts = {k: p.pop(k)[None] for k in ("wg", "wu", "wd")}
    return p, experts


class _Recording(Scheduler):
    """A scheduler that keeps every logits row it samples from."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.rows = []

    def _sample(self, row):
        self.rows.append(np.array(row))
        return super()._sample(row)


@pytest.mark.parametrize("kernels", ["off", "interpret"])
def test_scheduler_prefill_and_paged_decode_match_full_forward(
        kernels, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", kernels)  # the expert matmul
    cfg = _cfg(decode_kernel=kernels)
    params = _params(cfg)
    pc = PoolConfig(max_batch=2, block_size=8, n_blocks=12, max_len=40,
                    prompt_pad=16)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, 11)
    n_new = 14  # past the prompt's page and the next
    with jax.default_matmul_precision("highest"):
        sch = _Recording(cfg, params, pc)
        results, _ = sch.run([Request(0, prompt, n_new)])
        seq = np.concatenate([prompt, results[0][:-1]])
        full = T.forward(cfg, params, jnp.asarray(seq)[None])[0]
    want = np.asarray(full[len(prompt) - 1:])
    got = np.stack(sch.rows)
    assert got.shape == want.shape == (n_new, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_absorbed_decode_equals_expanded_attention():
    cfg = _cfg(decode_kernel="interpret")
    p = tree_materialize(L.mla_defs(cfg), jax.random.PRNGKey(5), jnp.float32)
    S, bs = 21, 8
    x = jax.random.normal(jax.random.PRNGKey(6), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    with jax.default_matmul_precision("highest"):
        want, _ = L.mla_attention(dataclasses.replace(
            cfg, attention_kernel="jnp"), p, x, pos)
        # the first S - 1 latent rows in pages 3, 1, 4 of layer 1 of a pool
        _, _, lat = L.mla_project(cfg, p, x, pos)
        table = jnp.asarray([[3, 1, 4, 0]], jnp.int32)
        pool = jnp.zeros((2, 6, bs, cfg.latent_dim))
        for t in range(S - 1):
            pool = pool.at[1, table[0, t // bs], t % bs].set(lat[0, t])
        got, pool = L.mla_paged_attention(
            cfg, p, x[:, -1:], pos[:, -1:], pool, 1, table,
            jnp.asarray([S - 1], jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(want[0, -1]),
                               rtol=1e-5, atol=1e-5)
    # the new token's row landed at its page and offset
    np.testing.assert_allclose(np.asarray(pool[1, 4, (S - 1) % bs]),
                               np.asarray(lat[0, -1]), rtol=1e-6, atol=1e-6)


def test_expert_shares_sum_to_the_uncut_layer(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    uncut = _cfg(n_experts_held=0, expert_offset=0, capacity_factor=8.0)
    p = tree_materialize(L.moe_defs(uncut), jax.random.PRNGKey(7),
                         jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 5, uncut.d_model))
    with jax.default_matmul_precision("highest"):
        want = L.moe(uncut, p, x)  # capacity 8: nothing dropped at 15 tokens
        shared = L.mlp(uncut, p["shared"], x)
        held, total, routes = 2, 0.0, []
        for off in range(0, uncut.n_experts, held):
            cfg = dataclasses.replace(uncut, n_experts_held=held,
                                      expert_offset=off)
            experts = {k: p[k][None, off:off + held]
                       for k in ("wg", "wu", "wd")}
            y, r = L.held_experts(cfg, p, experts, 0, x)
            total = total + y - shared  # the shared expert counted once
            routes.append(np.asarray(r))
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.concatenate(routes).sum() == 15 * uncut.experts_per_token


def _np_route(xt, router, bias, k, scaling):
    s = 1.0 / (1.0 + np.exp(-(xt @ router)))
    top = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, top, -1)
    return w / w.sum(-1, keepdims=True) * scaling, top


def test_sigmoid_bias_routing_matches_numpy():
    cfg = _cfg()
    p, _ = _moe_params(cfg)
    xt = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                      (40, cfg.d_model)), np.float64)
    # a bias large enough to change the choice for most tokens
    bias = np.linspace(-0.3, 0.3, cfg.n_experts)
    p = dict(p, router_bias=jnp.asarray(bias, jnp.float32))
    w, idx = L.route(cfg, p, jnp.asarray(xt, jnp.float32))
    want_w, want_i = _np_route(xt, np.asarray(p["router"], np.float64), bias,
                               cfg.experts_per_token, 2.827)
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_i, -1))
    order = np.argsort(np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1),
        np.take_along_axis(want_w, np.argsort(want_i, -1), -1), rtol=1e-5)
    plain = _np_route(xt, np.asarray(p["router"], np.float64), 0 * bias,
                      cfg.experts_per_token, 2.827)[1]
    assert not np.array_equal(np.sort(plain, -1), np.sort(want_i, -1))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.827, rtol=1e-5)


@pytest.mark.parametrize("batch", [1, 3, 16, 64])
def test_no_token_is_dropped_at_any_batch(batch, monkeypatch):
    """Every token routed to held experts only (a bias of 10 on them):
    the layer equals every held expert applied densely with its routing
    weight, and counts k routes a token."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg = _cfg()
    p, experts = _moe_params(cfg)
    E, off = cfg.n_experts_held, cfg.expert_offset
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[off:off + E] = 10.0
    p = dict(p, router_bias=jnp.asarray(bias))
    x = jax.random.normal(jax.random.PRNGKey(batch), (batch, 1, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, routes = L.held_experts(cfg, p, experts, 0, x)
        w, idx = L.route(cfg, p, x.reshape(batch, -1))
        dense = jnp.zeros((batch, E))
        dense = dense.at[jnp.arange(batch)[:, None], idx - off].set(w)
        xt = x.reshape(batch, -1)
        h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, experts["wg"][0])) * \
            jnp.einsum("td,edf->tef", xt, experts["wu"][0])
        out = jnp.einsum("tef,efd->ted", h, experts["wd"][0])
        want = jnp.einsum("te,ted->td", dense, out) + L.mlp(
            cfg, p["shared"], xt)
    assert int(routes.sum()) == batch * cfg.experts_per_token
    np.testing.assert_allclose(np.asarray(y.reshape(batch, -1)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_scheduler_counts_routes_of_prefill_and_decode():
    """Holding every expert, each prompt token and each decoded token
    makes k routes in every MoE layer."""
    cfg = _cfg(n_experts_held=8, expert_offset=0)
    params = _params(cfg)
    pc = PoolConfig(max_batch=3, block_size=8, n_blocks=16, max_len=32,
                    prompt_pad=16)
    rng = np.random.default_rng(4)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, n), 6)
            for i, n in enumerate((5, 16, 9))]
    sch = Scheduler(cfg, params, pc)
    _, stats = sch.run(reqs)
    k = cfg.experts_per_token
    assert stats.prefill_routes.shape == (cfg.n_moe_layers, 8)
    np.testing.assert_array_equal(stats.prefill_routes.sum(1),
                                  k * (5 + 16 + 9))
    np.testing.assert_array_equal(stats.decode_routes.sum(1),
                                  k * stats.total_tokens)
    assert sch.trace_counts["decode"] == 1


def test_yarn_frequencies_and_scale():
    cfg = kimi_k2.CONFIG
    f = 1.0 / 50_000.0 ** (np.arange(0, 64, 2) / 64)
    inv = L.rope_inv_freq(cfg, 64)
    # beta_fast = beta_slow = 1 over 4,096 positions: pairs 0-19 keep the
    # base frequency, pairs from 20 on are interpolated by 1/32
    np.testing.assert_allclose(inv[:20], f[:20])
    np.testing.assert_allclose(inv[20:], f[20:] / 32)
    m = 0.1 * math.log(32) + 1
    assert L.mla_scale(cfg) == pytest.approx(192**-0.5 * m * m)


def test_config_holds_the_published_numbers():
    c = kimi_k2.CONFIG
    assert kimi_k2.SOURCE_URL.endswith("Kimi-K2-Instruct/blob/main/config.json")
    published = dict(
        n_layers=61, d_model=7168, d_ff=18432, n_dense_layers=1, n_heads=64,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_experts=384,
        experts_per_token=8, moe_d_ff=2048, shared_expert_d_ff=2048,
        router_scoring="sigmoid", routed_scaling=2.827, rope_theta=50000.0,
        yarn_factor=32.0, yarn_original_len=4096, yarn_beta_fast=1.0,
        yarn_beta_slow=1.0, yarn_mscale=1.0, norm_eps=1e-6,
        vocab_size=163840, tie_embeddings=False, n_experts_held=0,
    )
    assert {k: getattr(c, k) for k in published} == published
    assert c.latent_dim == 576 and c.is_mla
    # 1.04T parameters, 32B active (the model card's counts)
    assert abs(c.param_count() / 1.04e12 - 1) < 0.02
    assert abs(c.active_param_count() / 32.9e9 - 1) < 0.05
