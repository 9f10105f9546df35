"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests).

These are also the GRADIENT oracles: each oracle is plain differentiable
jnp, so ``jax.grad`` through it is the reference the registry's
``parity_check(..., grads=True)`` compares the custom_vjp blocked backward
kernels against (kernels/ops.py grad-tolerance policies).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_scores(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """THE definition of the attention score semantics: grouped-GQA
    (B, Hkv, g, S, Sk) f32 scores (scaled, softcapped) + (S, Sk) bool mask.

    Shared by the oracle forward below and the flash-attention custom_vjp
    backward (kernels/flash_attention.py), so a semantics change cannot
    drift between the forward and its gradient.
    """
    B, Hq, S, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, S, D).astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg, k.astype(jnp.float32))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qp = jnp.arange(S)
    kp = jnp.arange(Sk)
    mask = jnp.ones((S, Sk), bool)
    if causal:
        mask &= qp[:, None] >= kp[None, :]
    if window is not None:
        mask &= qp[:, None] - kp[None, :] < window
    return s, mask


def attention_ref(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Dense softmax-attention oracle: (B, Hq, S, D) output in q.dtype."""
    B, Hq, S, D = q.shape
    s, mask = attention_scores(q, k, causal=causal, window=window,
                               softcap=softcap)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, S, D).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # (B, Hq, D) — one query token per sequence
    k_pool: jax.Array,  # (n_blocks, block_size, Hkv, D)
    v_pool: jax.Array,
    table: jax.Array,  # (B, n_pages) int32
    lengths: jax.Array,  # (B,) int32 — valid tokens incl. the current one
    *,
    window: int | None = None,
    softcap: float | None = None,
) -> jax.Array:
    """Paged single-query attention oracle: jnp gather through the block
    table, then masked GQA softmax-attention over the flattened pages.
    Rows with ``lengths == 0`` (scheduler padding lanes) return zeros, to
    match the kernel's ``max(l, eps)`` guard."""
    B, Hq, D = q.shape
    block_size, Hkv = k_pool.shape[1], k_pool.shape[2]
    g = Hq // Hkv
    L = table.shape[1] * block_size
    k = k_pool[table].reshape(B, L, Hkv, D).astype(jnp.float32)
    v = v_pool[table].reshape(B, L, Hkv, D).astype(jnp.float32)
    qg = q.reshape(B, Hkv, g, D).astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(L)
    mask = pos[None, :] < lengths[:, None]  # (B, L)
    if window is not None:
        # the single query sits at position lengths - 1
        mask &= pos[None, :] >= lengths[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows would softmax to uniform; zero them instead
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v)
    return o.reshape(B, Hq, D).astype(q.dtype)


def mla_decode_attention_ref(
    q: jax.Array,  # (B, H, R) absorbed queries
    pool: jax.Array,  # (n_layers, n_blocks, block_size, R)
    layer,  # the layer's index into the pool
    table: jax.Array,  # (B, n_pages) int32
    lengths: jax.Array,  # (B,) int32 — valid tokens incl. the current one
    *,
    scale: float,
    value_dim: int,
) -> jax.Array:
    """Paged latent attention oracle: the layer's pages gathered through
    the table; keys are whole latent rows, values their first value_dim
    columns. Empty slots return zeros, as the kernel does."""
    B = q.shape[0]
    block_size, R = pool.shape[2], pool.shape[3]
    L = table.shape[1] * block_size
    rows = pool[layer][table].reshape(B, L, R).astype(jnp.float32)
    s = jnp.einsum("bhr,btr->bht", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(L)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jnp.where(mask[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bht,btc->bhc", p, rows[..., :value_dim])
    return o.astype(q.dtype)


def expert_matmul_ref(x, w, tile_group, n_active, *, tm: int):
    """Grouped matmul oracle: each tm-row tile of x times its group's
    matrix; tiles at or past n_active give zeros."""
    M, K = x.shape
    nt = M // tm
    xt = x.reshape(nt, tm, K).astype(jnp.float32)
    out = jnp.einsum("tmk,tkn->tmn", xt, w[tile_group].astype(jnp.float32))
    live = (jnp.arange(nt) < n_active)[:, None, None]
    return jnp.where(live, out, 0.0).reshape(M, -1).astype(x.dtype)


def ssd_chunk_ref(xdt, cum, Bc, Cc):
    """Within-chunk SSD: (y_intra, chunk states). Shapes as ssd_chunk_fwd."""
    Q = xdt.shape[2]
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,i,j,nh)
    # mask BEFORE the exp: above-diagonal diffs can overflow exp to inf,
    # and jax.grad(where(tri, exp(diff), 0)) then propagates inf * 0 = NaN
    # cotangents through the masked-out lanes (the exp VJP multiplies the
    # zero upstream cotangent by the inf primal)
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    scores = jnp.einsum("bcis,bcjs->bcij", Cc, Bc)
    y = jnp.einsum("bcij,bcijh,bcjhd->bcihd", scores, decay, xdt)
    dte = jnp.exp(cum[:, :, -1:, :] - cum)
    st = jnp.einsum("bcjs,bcjh,bcjhd->bchsd", Bc, dte, xdt)
    return y, st


def sparse_dot_ref(psi, idx, val):
    """Per-node sparse dot oracle: out[n] = sum_k val[n,k] * psi[n, idx[n,k]]."""
    # f32 floor matches the TPU kernel's MXU accumulation; f64 inputs stay
    # f64 so the interpret-mode parity policy (1e-12) is meetable
    ct = jnp.promote_types(psi.dtype, jnp.float32)
    return jax.vmap(lambda p, i, v: jnp.sum(v * p[i]))(
        psi.astype(ct), idx, val.astype(ct)
    )


def sparse_axpy_ref(psi, idx, val, coef, rho):
    """Sparse AXPY oracle: out[n] = rho[n] * psi[n] + coef[n] * scatter(val)."""

    def one(p, i, v, c, r):
        return (r * p).at[i].add(c * v)

    return jax.vmap(one)(psi, idx, val, coef.astype(psi.dtype),
                         rho.astype(psi.dtype))


def block_topk_ref(x, k):
    """Per-block top-k-by-|value| oracle via lax.top_k: (vals, int32 idx)."""

    def one(row):
        _, i = jax.lax.top_k(jnp.abs(row), k)
        return row[i], i.astype(jnp.int32)

    return jax.vmap(one)(x)
