"""Pallas TPU kernel for the Mamba2/SSD WITHIN-CHUNK computation.

The chunked SSD algorithm (models/ssm.py) splits into:
  (a) within-chunk: y_intra = ((C B^T) .* L) (x dt)  and the per-chunk state
      contribution  S_c = B^T (decay-to-end .* x dt)  — all dense matmuls
      over (Q, ds, hd) tiles -> MXU work. THIS kernel.
  (b) across-chunk: a length-nc linear recurrence + rank-1 read-out —
      negligible FLOPs, kept in jnp (lax.scan).

This split is the TPU-native adaptation of the paper's GPU kernel: the
within-chunk part is blocked to VMEM with (Q x Q) decay tiles built on the
VPU and contracted on the MXU.

Grid: (batch, n_chunks, head_blocks). Per-instance working set:
  xdt (Q, hb, hd), cum (Q, hb), B/C (Q, ds), out y (Q, hb, hd),
  states (hb, ds, hd)  — for Q=128, hb=4, hd=64, ds=128: ~0.5 MB. VMEM-safe.

``ssd_chunk`` is the differentiable entry point (``jax.custom_vjp``).
Residual contract: the forward saves only the INPUTS (xdt, cum, Bc, Cc) —
no (Q x Q) tile survives the forward. The backward is one chunked Pallas
kernel over the same grid that recomputes each chunk's decay tile and
score matrix from the saved residuals and emits (dxdt, dcum, dB, dC);
dB/dC are shared across head blocks, so the kernel accumulates them across
the (sequentially iterated) head-block grid axis into a revisited output
block. `cum` is the caller-side inclusive cumsum, so its cotangent is
w.r.t. the cumsum output (models/ssm.py's autodiff handles the chain to
the raw decays).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


def _ssd_chunk_kernel(xdt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref, *,
                      head_block: int):
    """One (batch, chunk, head-block) instance.

    xdt_ref: (1, 1, Q, hb, hd)   x * dt, fp32
    cum_ref: (1, 1, Q, hb)       inclusive cumsum of log-decay
    b_ref:   (1, 1, Q, ds)
    c_ref:   (1, 1, Q, ds)
    y_ref:   (1, 1, Q, hb, hd)   intra-chunk output
    st_ref:  (1, 1, hb, ds, hd)  chunk state contribution
    """
    xdt = xdt_ref[0, 0].astype(jnp.float32)  # (Q, hb, hd)
    cum = cum_ref[0, 0].astype(jnp.float32)  # (Q, hb)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (Q, ds)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (Q, ds)
    Q = xdt.shape[0]

    scores = Cm @ Bm.T  # (Q, Q) shared across heads in the block
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )

    for h in range(head_block):  # static unroll over the head block
        ch = cum[:, h]
        decay = jnp.exp(ch[:, None] - ch[None, :])
        L = jnp.where(tri, decay, 0.0)
        y_h = (scores * L) @ xdt[:, h, :]  # (Q, hd)
        y_ref[0, 0, :, h, :] = y_h.astype(y_ref.dtype)
        dte = jnp.exp(ch[-1] - ch)  # decay to end of chunk
        st_h = (Bm * dte[:, None]).T @ xdt[:, h, :]  # (ds, hd)
        st_ref[0, 0, h] = st_h.astype(st_ref.dtype)


def ssd_chunk_fwd(
    xdt: jax.Array,  # (B, nc, Q, nh, hd) fp32
    cum: jax.Array,  # (B, nc, Q, nh)
    Bc: jax.Array,  # (B, nc, Q, ds)
    Cc: jax.Array,  # (B, nc, Q, ds)
    *,
    head_block: int = 4,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y_intra (B,nc,Q,nh,hd), states (B,nc,nh,ds,hd))."""
    B, nc, Q, nh, hd = xdt.shape
    ds = Bc.shape[-1]
    head_block = min(head_block, nh)
    assert nh % head_block == 0
    hb_count = nh // head_block

    kernel = functools.partial(_ssd_chunk_kernel, head_block=head_block)
    grid = (B, nc, hb_count)
    y, st = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, head_block, hd),
                         lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, head_block), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, head_block, hd),
                         lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, head_block, ds, hd),
                         lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, Q, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, nh, ds, hd), jnp.float32),
        ],
        name="ssd_chunk",
        interpret=interpret,
    )(xdt, cum, Bc, Cc)
    return y, st


def _ssd_bwd_kernel(xdt_ref, cum_ref, b_ref, c_ref, dy_ref, dst_ref,
                    dxdt_ref, dcum_ref, db_ref, dc_ref, *, head_block: int):
    """Backward of one (batch, chunk, head-block) instance.

    Recomputes the (Q, Q) decay tile and score matrix per head from the
    saved inputs — mirror of the forward body, transposed. dB/dC blocks are
    revisited across the head-block grid axis: initialized at h == 0, then
    accumulated (the axis is innermost, so revisits are consecutive).
    """
    h_blk = pl.program_id(2)
    xdt = xdt_ref[0, 0].astype(jnp.float32)  # (Q, hb, hd)
    cum = cum_ref[0, 0].astype(jnp.float32)  # (Q, hb)
    Bm = b_ref[0, 0].astype(jnp.float32)  # (Q, ds)
    Cm = c_ref[0, 0].astype(jnp.float32)  # (Q, ds)
    dy = dy_ref[0, 0].astype(jnp.float32)  # (Q, hb, hd)
    dst = dst_ref[0, 0].astype(jnp.float32)  # (hb, ds, hd)
    Q = xdt.shape[0]

    scores = Cm @ Bm.T  # (Q, Q)
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )

    dscores = jnp.zeros((Q, Q), jnp.float32)
    dB = jnp.zeros_like(Bm)
    for h in range(head_block):  # static unroll over the head block
        ch = cum[:, h]
        decay = jnp.exp(ch[:, None] - ch[None, :])
        L = jnp.where(tri, decay, 0.0)
        X = xdt[:, h, :]  # (Q, hd)
        dy_h = dy[:, h, :]
        dst_h = dst[h]  # (ds, hd)

        # y_h = (scores * L) @ X
        dM = dy_h @ X.T  # (Q, Q)
        dX = (scores * L).T @ dy_h
        dscores = dscores + dM * L
        dLL = dM * scores * L  # d cum via L = tri * exp(ch_i - ch_j)
        dch = dLL.sum(1) - dLL.sum(0)

        # st_h = (Bm * dte)^T @ X,  dte = exp(ch[Q-1] - ch)
        dte = jnp.exp(ch[Q - 1] - ch)
        dX = dX + (Bm * dte[:, None]) @ dst_h
        dBw = X @ dst_h.T  # (Q, ds)
        dB = dB + dBw * dte[:, None]
        ddte_dte = jnp.sum(dBw * Bm, axis=1) * dte  # (Q,)
        dch = dch - ddte_dte
        dch = dch.at[Q - 1].add(ddte_dte.sum())

        dxdt_ref[0, 0, :, h, :] = dX.astype(dxdt_ref.dtype)
        dcum_ref[0, 0, :, h] = dch.astype(dcum_ref.dtype)

    @pl.when(h_blk == 0)
    def _init():
        db_ref[0, 0] = jnp.zeros_like(db_ref[0, 0])
        dc_ref[0, 0] = jnp.zeros_like(dc_ref[0, 0])

    db_ref[0, 0] += (dscores.T @ Cm + dB).astype(db_ref.dtype)
    dc_ref[0, 0] += (dscores @ Bm).astype(dc_ref.dtype)


def ssd_chunk_bwd(
    xdt: jax.Array,
    cum: jax.Array,
    Bc: jax.Array,
    Cc: jax.Array,
    dy: jax.Array,  # (B, nc, Q, nh, hd) cotangent of y_intra
    dst: jax.Array,  # (B, nc, nh, ds, hd) cotangent of chunk states
    *,
    head_block: int = 4,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Chunked backward launch: (dxdt, dcum, dBc, dCc). Shapes as forward."""
    B, nc, Q, nh, hd = xdt.shape
    ds = Bc.shape[-1]
    head_block = min(head_block, nh)
    assert nh % head_block == 0
    hb_count = nh // head_block

    kernel = functools.partial(_ssd_bwd_kernel, head_block=head_block)
    grid = (B, nc, hb_count)
    dxdt, dcum, dB, dC = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, head_block, hd),
                         lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, head_block), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, head_block, hd),
                         lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, head_block, ds, hd),
                         lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, head_block, hd),
                         lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, head_block), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, ds), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, Q, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, Q, nh), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, Q, ds), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, Q, ds), jnp.float32),
        ],
        name="ssd_chunk_bwd",
        interpret=interpret,
    )(xdt, cum, Bc, Cc, dy, dst)
    return (
        dxdt.astype(xdt.dtype), dcum.astype(cum.dtype),
        dB.astype(Bc.dtype), dC.astype(Cc.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_chunk(xdt, cum, Bc, Cc, head_block=4, interpret=False):
    """Differentiable within-chunk SSD (positional statics for custom_vjp)."""
    return ssd_chunk_fwd(
        xdt, cum, Bc, Cc, head_block=head_block, interpret=interpret
    )


def _ssd_fwd(xdt, cum, Bc, Cc, head_block, interpret):
    """custom_vjp forward: run the kernel, save only the inputs."""
    out = ssd_chunk_fwd(
        xdt, cum, Bc, Cc, head_block=head_block, interpret=interpret
    )
    return out, (xdt, cum, Bc, Cc)


def _ssd_bwd(head_block, interpret, res, cts):
    """custom_vjp backward: dispatch the chunked Pallas gradient kernel."""
    xdt, cum, Bc, Cc = res
    dy, dst = cts
    return ssd_chunk_bwd(
        xdt, cum, Bc, Cc, dy, dst, head_block=head_block, interpret=interpret
    )


ssd_chunk.defvjp(_ssd_fwd, _ssd_bwd)
