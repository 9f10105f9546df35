"""Pallas TPU flash attention: GQA + causal + sliding window + softcap.

Blocked online-softmax attention — the S x S score matrix never
materializes; the working set is one (block_q, head_dim) query tile plus
streamed K/V tiles, sized for VMEM, with MXU-aligned (128-multiple) matmul
dims. GQA is expressed in the BlockSpec index maps: the kv specs map query
head h -> kv head h // group_size, so no K/V replication is staged.

Layout: q (B, Hq, S, D), k/v (B, Hkv, S, D) — heads-major so a (S, D) tile
per head streams contiguously from HBM.

K/V streaming uses the current Pallas ref-indexing semantics
(``ref[0, 0, pl.ds(start, size), :]``); ragged sequence lengths are handled
by padding q/k/v to block multiples in the wrapper (zero pad + in-kernel
validity masks), so no dynamic slice ever reads out of bounds.

The forward kernel also emits the per-row log-sum-exp, which
``flash_attention`` (a ``jax.custom_vjp``) saves as a residual.

Residual contract: the forward saves (q, k, v, o, lse) and NOTHING that is
O(S^2). The backward is the blocked flash-attention gradient — two Pallas
kernels that recompute the probabilities per (q-block, kv-block) TILE from
the saved log-sum-exp (p = exp(s - lse)), so no S x S probability matrix
ever materializes in either direction:

  dq kernel   grid (B, Hq, q-blocks): holds one dq tile, streams K/V
  dk/dv kernel  grid (B, Hq, kv-blocks): holds one dk/dv tile, streams
              Q/dO/lse/delta; per-q-head partials are group-summed into
              kv heads by the wrapper (GQA)

delta = rowsum(dO * O) — the softmax-gradient row correction — is a cheap
O(S) jnp precomputation shared by both kernels.

Validated against kernels/ref.py in interpret mode (tests/test_kernels.py,
tests/test_kernel_grads.py asserts vjp==ref-autodiff and the no-S^2
property); dispatch and tolerance policy live in kernels/ops.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *,
    block_q: int, block_k: int, seq_k: int, causal: bool,
    window: int | None, softcap: float | None, scale: float,
):
    """One (batch, q-head, q-block) program instance.

    q_ref: (1, 1, block_q, D); k_ref/v_ref: (1, 1, seq_k_pad, D);
    o_ref: (1, 1, block_q, D); lse_ref: (1, 1, block_q, 1).
    Row statistics (m, l, lse) are (block_q, 1) columns throughout.
    """
    q_blk = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (block_q, D)
    D = q.shape[-1]
    q_pos = _positions(q_blk, block_q, 0)  # (block_q, 1)

    num_k_blocks = pl.cdiv(seq_k, block_k)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        # seq_k_pad is a multiple of block_k (wrapper zero-pads), so the
        # dynamic slice is always in bounds; pad rows are masked below.
        k_tile = k_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(
            jnp.float32
        )
        v_tile = v_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(
            jnp.float32
        )
        k_pos = _positions(i, block_k, 1)  # (1, block_k)
        s = _dot_nt(q, k_tile)  # (block_q, block_k)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = _mask(q_pos, k_pos, seq_q=None, seq_k=seq_k, causal=causal,
                     window=window)
        s = jnp.where(mask, s, NEG_INF)

        m_cur = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + p.sum(-1, keepdims=True)
        acc = acc * alpha + _dot(p, v_tile)
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    if causal:
        # only stream kv blocks that intersect the causal/window band
        hi = jnp.minimum(
            num_k_blocks, (q_blk + 1) * block_q // block_k + 1
        )
    else:
        hi = num_k_blocks
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (q_blk * block_q - window) // block_k)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


def _positions(blk, size: int, axis: int) -> jax.Array:
    """Sequence positions of a block: a (size, 1) column (axis 0) or a
    (1, size) row (axis 1). Kernel vectors stay 2-D, as the TPU needs."""
    shape = (size, 1) if axis == 0 else (1, size)
    return blk * size + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _mask(q_pos, k_pos, *, seq_q, seq_k, causal, window):
    """(block_q, block_k) validity of score tiles from position vectors."""
    mask = jnp.broadcast_to(k_pos < seq_k, (q_pos.shape[0], k_pos.shape[1]))
    if seq_q is not None:
        mask &= q_pos < seq_q
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


def _dot(a, b):
    """a @ b with f32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nt(a, b):
    """a @ b.T with f32 accumulation (no transposed copy of b)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    """a.T @ b with f32 accumulation (no transposed copy of a)."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _pad_seq(x: jax.Array, to: int) -> jax.Array:
    pad = (-x.shape[2]) % to
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def flash_attention_fwd(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    return_lse: bool = False,
):
    """Forward kernel launch. Returns o, or (o, lse (B, Hq, S) f32).

    The kernel writes lse as (B, Hq, S, 1) so that its block's last two
    dimensions are (block_q, whole axis), which the TPU's tiling accepts.
    """
    B, Hq, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)

    # zero-pad ragged sequences to block multiples: every q block and every
    # streamed K/V slice is full-sized, and validity is a mask, not an OOB
    # read (padded q rows are fully masked -> finite garbage, sliced off).
    qp = _pad_seq(q, block_q)
    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    Sp, Skp = qp.shape[2], kp.shape[2]

    grid = (B, Hq, Sp // block_q)
    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, seq_k=Sk,
        causal=causal, window=window, softcap=softcap, scale=scale,
    )
    o, lse = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Skp, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, Skp, D), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sp, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sp, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qp, kp, vp)
    o = o[:, :, :S]
    if return_lse:
        return o, lse[:, :, :S, 0]
    return o


# ---------------------------------------------------------------------------
# blocked backward kernels
#
# Both recompute the (block_q, block_k) probability tile from the saved lse
# (p = exp(s - lse); masked entries are NEG_INF before the subtraction, so
# they reconstruct to exactly 0 — including the zero-padded rows, whose
# padded lse of 0 is never reached by a live probability). The score/mask
# semantics mirror the forward kernel body above tile for tile, so the
# gradient cannot drift from the forward.
# ---------------------------------------------------------------------------


def _bwd_tile(q, k, v, do, lse, delta, q_pos, k_pos, *,
              seq_q, seq_k, causal, window, softcap):
    """Shared per-tile math: (p, ds) from one (block_q, block_k) tile.

    q is pre-scaled; all operands f32; lse/delta/q_pos are (block_q, 1)
    columns and k_pos a (1, block_k) row. Invalid (masked / padded) pairs
    yield p = ds = 0 exactly.
    """
    s = _dot_nt(q, k)  # (block_q, block_k), pre-softcap
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    mask = _mask(q_pos, k_pos, seq_q=seq_q, seq_k=seq_k, causal=causal,
                 window=window)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)  # rebuilt from the residual, <= 1
    dp = _dot_nt(do, v)  # (block_q, block_k)
    ds = p * (dp - delta)
    if softcap is not None:
        # d/dx softcap*tanh(x/softcap) = 1 - tanh^2 = 1 - (s/softcap)^2
        ds = ds * jnp.where(mask, 1.0 - jnp.square(s / softcap), 0.0)
    return p, ds


def _attn_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, *,
    block_q: int, block_k: int, seq_q: int, seq_k: int, causal: bool,
    window: int | None, softcap: float | None, scale: float,
):
    """dq for one (batch, q-head, q-block): stream KV tiles, accumulate.

    q/do/dq refs: (1, 1, block_q, D); k/v refs: (1, 1, seq_k_pad, D);
    lse/dl refs: (1, 1, block_q, 1).
    """
    q_blk = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = dl_ref[0, 0].astype(jnp.float32)
    D = q.shape[-1]
    q_pos = _positions(q_blk, block_q, 0)
    num_k_blocks = pl.cdiv(seq_k, block_k)

    def body(i, acc):
        k_tile = k_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(
            jnp.float32
        )
        v_tile = v_ref[0, 0, pl.ds(i * block_k, block_k), :].astype(
            jnp.float32
        )
        k_pos = _positions(i, block_k, 1)
        _, ds = _bwd_tile(
            q, k_tile, v_tile, do, lse, delta, q_pos, k_pos,
            seq_q=seq_q, seq_k=seq_k, causal=causal, window=window,
            softcap=softcap,
        )
        return acc + _dot(ds, k_tile)

    if causal:
        hi = jnp.minimum(num_k_blocks, (q_blk + 1) * block_q // block_k + 1)
    else:
        hi = num_k_blocks
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (q_blk * block_q - window) // block_k)
    acc = jax.lax.fori_loop(lo, hi, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0, 0] = (scale * acc).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref, *,
    block_q: int, block_k: int, seq_q: int, seq_k: int, causal: bool,
    window: int | None, softcap: float | None, scale: float,
):
    """dk/dv (per q head) for one (batch, q-head, kv-block): stream Q tiles.

    k/v/dk/dv refs: (1, 1, block_k, D); q/do refs: (1, 1, seq_q_pad, D);
    lse/dl refs: (1, 1, seq_q_pad, 1). GQA group-sum happens in the
    wrapper.
    """
    k_blk = pl.program_id(2)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    D = k.shape[-1]
    k_pos = _positions(k_blk, block_k, 1)
    num_q_blocks = pl.cdiv(seq_q, block_q)

    def body(i, carry):
        dk_acc, dv_acc = carry
        q_tile = q_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32
        ) * scale
        do_tile = do_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(
            jnp.float32
        )
        rows = pl.ds(i * block_q, block_q)
        lse = lse_ref[0, 0, rows, :].astype(jnp.float32)
        delta = dl_ref[0, 0, rows, :].astype(jnp.float32)
        q_pos = _positions(i, block_q, 0)
        p, ds = _bwd_tile(
            q_tile, k, v, do_tile, lse, delta, q_pos, k_pos,
            seq_q=seq_q, seq_k=seq_k, causal=causal, window=window,
            softcap=softcap,
        )
        return dk_acc + _dot_tn(ds, q_tile), dv_acc + _dot_tn(p, do_tile)

    # only q blocks intersecting the causal/window band see this kv tile
    lo = k_blk * block_k // block_q if causal else 0
    hi = num_q_blocks
    if window is not None:
        hi = jnp.minimum(
            num_q_blocks, ((k_blk + 1) * block_k - 1 + window) // block_q + 1
        )
    zeros = jnp.zeros((block_k, D), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(lo, hi, body, (zeros, zeros))
    # q_tile is pre-scaled, so ds^T @ q_tile already carries the 1/sqrt(D)
    dk_ref[0, 0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)


def flash_attention_bwd(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,
    o: jax.Array,  # (B, Hq, S, D)   saved forward output
    lse: jax.Array,  # (B, Hq, S) f32  saved log-sum-exp
    do: jax.Array,  # (B, Hq, S, D)   output cotangent
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked backward launch: (dq, dk, dv) from the saved residuals.

    Two tiled ``pl.pallas_call`` grids (dq over q blocks, dk/dv over kv
    blocks) with the same causal/window/softcap statics as the forward;
    per-q-head dk/dv partials are summed over each GQA group here.
    """
    B, Hq, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)

    # delta = rowsum(do * o): the softmax-gradient row term, O(S) memory
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (B, Hq, S)

    qp, dop = _pad_seq(q, block_q), _pad_seq(do, block_q)
    kp, vp = _pad_seq(k, block_k), _pad_seq(v, block_k)
    # row statistics go in as (B, Hq, S_pad, 1) columns (see the forward)
    pad_q = ((0, 0), (0, 0), (0, qp.shape[2] - S))
    lsep = jnp.pad(lse, pad_q)[..., None]
    deltap = jnp.pad(delta, pad_q)[..., None]
    Sp, Skp = qp.shape[2], kp.shape[2]

    statics = dict(
        block_q=block_q, block_k=block_k, seq_q=S, seq_k=Sk, causal=causal,
        window=window, softcap=softcap, scale=scale,
    )
    dq = pallas_call(
        functools.partial(_attn_bwd_dq_kernel, **statics),
        grid=(B, Hq, Sp // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Skp, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, Skp, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sp, D), jnp.float32),
        name="flash_bwd_dq",
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    dkq, dvq = pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, **statics),
        grid=(B, Hq, Skp // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, Sp, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, Sp, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Sp, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Sp, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Skp, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Skp, D), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    # GQA: sum the per-q-head partials into their kv head
    dk = dkq.reshape(B, Hkv, group, Skp, D).sum(2)[:, :, :Sk]
    dv = dvq.reshape(B, Hkv, group, Skp, D).sum(2)[:, :, :Sk]
    return (
        dq[:, :, :S].astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    )


# ---------------------------------------------------------------------------
# custom VJP: forward = the Pallas kernel (saving lse), backward = the
# blocked Pallas gradient above. The ref oracle's autodiff
# (jax.grad of kernels/ref.py attention_ref) is the gradient ground truth
# the parity harness compares against.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q, k, v, causal=True, window=None, softcap=None,
    block_q=128, block_k=128, interpret=False,
):
    """Differentiable flash attention (positional statics for custom_vjp)."""
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _fa_fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    """custom_vjp forward: run the kernel, save (q, k, v, o, lse)."""
    o, lse = flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
        return_lse=True,
    )
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, window, softcap, block_q, block_k, interpret, res, do):
    """custom_vjp backward: dispatch the blocked Pallas gradient kernels."""
    q, k, v, o, lse = res
    return flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


flash_attention.defvjp(_fa_fwd, _fa_bwd)
