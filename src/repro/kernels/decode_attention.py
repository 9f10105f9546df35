"""Pallas TPU paged-cache decode attention (single-query, block tables).

Serving decodes one token per sequence per step against a PAGED KV cache:
K/V live in a preallocated block pool ``(n_blocks, block_size, Hkv, D)``
shared by every sequence, and each sequence owns an int32 block-table row
naming which pool pages hold its history. The kernel is the cache-aware hot
path: it gathers exactly the referenced pages — cost scales with the LIVE
tokens, not the dense worst case (the same active-set argument as the
paper's DSBA-s sparse relay).

The gather is expressed in the grid spec, not in kernel-body DMAs: the
block table and per-sequence lengths ride in scalar-prefetch position
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps can
read ``table[b, i]`` and point page ``i`` of sequence ``b`` straight at its
pool page. Pallas then pipelines one whole page per grid step — all its
kv heads, viewed as one (block_size * Hkv, D) tile — and unreferenced pool
pages are never touched.

Grid: ``(B, n_pages)`` with the page axis innermost and sequential; an
online-softmax carry (m / l / acc) persists in VMEM scratch across the
page axis, exactly like the q-block carry in kernels/flash_attention.py.
Pages past a sequence's length are skipped (``pl.when``); partial last
pages are masked by position, never read out of bounds. Empty slots
(length 0 — the scheduler's padding lanes) produce an all-zero output row
via the ``max(l, eps)`` guard.

GQA is a mask: one program instance scores all Hq query heads against the
page's block_size * Hkv rows in a single matmul and masks the pairs whose
kv head is not the query's. Every block then spans whole array dimensions
in its last two axes (the TPU's tiling rule), K/V are never replicated,
and the extra score columns are free at decode's tiny query count.

Validated against kernels/ref.py ``decode_attention_ref`` in interpret
mode; dispatch and tolerance policy live in kernels/ops.py
(``ModelConfig.decode_kernel`` routes the serving path through it).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


NEG_INF = -1e30


def _decode_kernel(
    table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref, *,
    block_size: int, n_kv: int, n_pages: int, window: int | None,
    softcap: float | None, scale: float,
):
    """One (sequence, page) program instance.

    table_ref/len_ref: scalar-prefetch refs (full (B, n_pages) / (B,));
    q_ref: (Hq, D) — every query head of this sequence;
    k_ref/v_ref: (block_size * Hkv, D) — the pool page the index map
    gathered through the block table, row r holding token r // Hkv of kv
    head r % Hkv; o_ref: (Hq, D);
    acc/m/l: VMEM online-softmax carry persisting across the page axis.
    """
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = len_ref[b]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages at or past the sequence length hold no valid tokens: skip the
    # matmul entirely (the index map already pointed them at page 0).
    @pl.when(i * block_size < length)
    def _update():
        q = q_ref[...].astype(jnp.float32) * scale  # (Hq, D)
        k = k_ref[...].astype(jnp.float32)  # (block_size * Hkv, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (Hq, block_size * Hkv)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        n_q = q.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        q_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        tok = row // n_kv
        pos = i * block_size + tok
        # GQA as a mask: query head h attends kv head h // group only
        mask = (row - tok * n_kv == q_head // (n_q // n_kv)) & (pos < length)
        if window is not None:
            # the single query sits at position length - 1
            mask &= pos >= length - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (Hq, 1)
        m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_cur

    @pl.when(i == n_pages - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _pad_last(x: jax.Array, to: int) -> jax.Array:
    pad = (-x.shape[-1]) % to
    if not pad:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


def decode_attention(
    q: jax.Array,  # (B, Hq, D) — one query token per sequence
    k_pool: jax.Array,  # (n_blocks, block_size, Hkv, D) shared page pool
    v_pool: jax.Array,
    table: jax.Array,  # (B, n_pages) int32 — pool page ids per sequence
    lengths: jax.Array,  # (B,) int32 — valid tokens incl. the current one
    *,
    window: int | None = None,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Paged single-query attention launch -> (B, Hq, D) in q.dtype.

    ``lengths[b]`` counts the tokens already written to sequence b's pages
    (including the token being decoded, at position ``lengths[b] - 1``);
    page ``i`` covers positions ``[i * block_size, (i+1) * block_size)``.
    Unused table entries may point anywhere in range (the scheduler points
    them at the reserved null page 0) — they are masked, never read beyond
    a DMA the carry ignores. D is zero-padded to the 128 lane width; padded
    columns contribute nothing and are sliced off.
    """
    B, Hq, D = q.shape
    n_blocks, block_size, Hkv, _ = k_pool.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    n_pages = table.shape[1]
    scale = 1.0 / math.sqrt(D)

    qp = _pad_last(q, 128)
    kp = _pad_last(k_pool, 128)
    vp = _pad_last(v_pool, 128)
    Dp = qp.shape[-1]
    # a page's (block_size, Hkv) rows are contiguous: viewing them as one
    # (block_size * Hkv, D) tile is free and makes each block's last two
    # dimensions whole array dimensions, as the TPU's tiling requires
    rows = block_size * Hkv
    kp = kp.reshape(n_blocks, rows, Dp)
    vp = vp.reshape(n_blocks, rows, Dp)

    page_spec = pl.BlockSpec(
        (pl.Squeezed(), rows, Dp), lambda b, i, t, le: (t[b, i], 0, 0)
    )
    seq_spec = pl.BlockSpec(
        (pl.Squeezed(), Hq, Dp), lambda b, i, t, le: (b, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[seq_spec, page_spec, page_spec],
        out_specs=seq_spec,
        scratch_shapes=[
            pltpu.VMEM((Hq, Dp), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_size=block_size, n_kv=Hkv, n_pages=n_pages,
        window=window, softcap=softcap, scale=scale,
    )
    out = pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dp), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), qp, kp, vp)
    return out[..., :D]


# ---------------------------------------------------------------------------
# latent mode (MLA decode against a paged latent pool)
# ---------------------------------------------------------------------------

def _mla_decode_kernel(
    pair_b_ref, pair_c_ref, table_ref, len_ref, layer_ref, q_ref, *refs,
    block_size: int, pages_per_step: int, value_dim: int, scale: float,
):
    """One (slot, chunk of pages_per_step pages) program instance.

    q_ref (H, R): every head's absorbed query of the slot; refs: the
    chunk's pages_per_step latent pages (block_size, R) each, then o_ref
    (H, value_dim), then the online-softmax carry acc/m/l in VMEM. Keys
    are whole latent rows, values their first value_dim columns: each
    page is read once."""
    del table_ref, layer_ref  # read by the index maps
    pages, o_ref = refs[:pages_per_step], refs[pages_per_step]
    acc_ref, m_ref, l_ref = refs[pages_per_step + 1:]
    g = pl.program_id(0)
    b, c = pair_b_ref[g], pair_c_ref[g]
    length = len_ref[b]
    chunk = block_size * pages_per_step
    last = jnp.maximum((length + chunk - 1) // chunk, 1) - 1

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...]
    for j, page in enumerate(pages):
        start = c * chunk + j * block_size

        @pl.when(start < length)
        def _update():
            kv = page[...]  # (block_size, R)
            s = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (H, block_size)
            pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = pos < length
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]
            m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(kv.dtype), kv[:, :value_dim],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_cur

    @pl.when(c == last)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def mla_decode_attention(
    q: jax.Array,  # (B, H, R) — absorbed queries, one token per slot
    pool: jax.Array,  # (n_layers, n_blocks, block_size, R) latent pool
    layer,  # int32 scalar — the layer's index into the pool
    table: jax.Array,  # (B, n_pages) int32 — pool page ids per slot
    lengths: jax.Array,  # (B,) int32 — valid tokens incl. the current one
    *,
    scale: float,
    value_dim: int,
    pages_per_step: int = 4,
    interpret: bool = False,
) -> jax.Array:
    """Paged single-query latent attention -> (B, H, value_dim) in q.dtype.

    Scores are ``scale * q . row`` over every cached latent row of the
    slot; the output is the softmax-weighted sum of the rows' first
    ``value_dim`` columns. The grid runs over (slot, chunk) pairs that
    hold tokens — ``max(ceil(length / chunk), 1)`` per slot, a dynamic
    count — so a step's work follows the live context, not max_len; an
    empty slot gets one pass that writes zeros. Each pass reads
    ``pages_per_step`` pages through the block table, one operand each
    (the same pool, no copy), and the layer's pages are found in place in
    the whole pool by the layer index.
    """
    B, H, R = q.shape
    _, _, block_size, R2 = pool.shape
    assert R == R2, (q.shape, pool.shape)
    n_pages = table.shape[1]
    P = pages_per_step
    chunk = block_size * P
    n_chunks = -(-n_pages // P)
    lengths = lengths.astype(jnp.int32)
    per_slot = jnp.maximum((lengths + chunk - 1) // chunk, 1)
    ends = jnp.cumsum(per_slot, dtype=jnp.int32)
    g = jnp.arange(B * n_chunks, dtype=jnp.int32)
    pair_b = jnp.minimum(jnp.searchsorted(ends, g, side="right"), B - 1)
    pair_c = g - (ends - per_slot)[pair_b]
    pair_b, pair_c = pair_b.astype(jnp.int32), pair_c.astype(jnp.int32)

    def page_spec(j):
        def index(g, pb, pc, t, le, ly):
            i = jnp.minimum(pc[g] * P + j, n_pages - 1)
            return ly[0], t[pb[g], i], 0, 0
        return pl.BlockSpec(
            (pl.Squeezed(), pl.Squeezed(), block_size, R), index)

    slot_spec = lambda width: pl.BlockSpec(
        (pl.Squeezed(), H, width), lambda g, pb, pc, t, le, ly: (pb[g], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ends[-1],),
        in_specs=[slot_spec(R)] + [page_spec(j) for j in range(P)],
        out_specs=slot_spec(value_dim),
        scratch_shapes=[
            pltpu.VMEM((H, value_dim), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_decode_kernel, block_size=block_size, pages_per_step=P,
        value_dim=value_dim, scale=scale,
    )
    return pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_decode_attention",
        interpret=interpret,
    )(pair_b, pair_c, table.astype(jnp.int32), lengths,
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.astype(pool.dtype), *([pool] * P))

