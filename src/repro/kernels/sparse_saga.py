"""Pallas TPU kernels for the DSBA per-iteration sparse row update.

The paper's per-node hot loop with linear predictors is:
  (1) s   = x^T psi            sparse gather-dot   (nnz = k elements)
  (2) g   = resolvent scalar   (O(1), stays in jnp)
  (3) z   = rho psi - a g x    sparse AXPY          (k elements)

GPUs do (1)/(3) with native gather/scatter; TPUs have no efficient VMEM
gather, so the TPU-native adaptation processes the d-dimensional model row
in VMEM blocks and matches indices against the block's columns: the gather
is a ONE-HOT MATMUL on the MXU (DESIGN.md §5), the scatter a one-hot select
summed over k on the VPU. Cost per node: O(k * d_block) per block,
O(k * d) total — the same O(rho d) as the paper.

sparse_dot's grid is (N nodes, d blocks) and accumulates per-node partial
dots in an output block revisited across the d axis; sparse_axpy's grid is
the d blocks alone, each cell covering every node.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


def _dot_kernel(psi_ref, idx_ref, val_ref, out_ref, *, block_d: int, d: int,
                compute_dtype):
    """Accumulate sum(val * psi[idx]) for indices landing in this d-block."""
    j = pl.program_id(1)
    psi = psi_ref[0].astype(compute_dtype)  # (block_d,)
    idx = idx_ref[0]  # (k,)
    val = val_ref[0].astype(compute_dtype)  # (k,)
    lo = j * block_d
    # ragged last block: out-of-range pad columns read garbage/NaN -> zero
    col = lo + jax.lax.iota(jnp.int32, block_d)
    psi = jnp.where(col < d, psi, 0.0)
    local = idx - lo
    in_blk = (local >= 0) & (local < block_d)
    # one-hot (k, block_d) match -> gather as a matvec on the MXU
    onehot = (
        local[:, None]
        == jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1)
    ) & in_blk[:, None]
    gathered = (onehot.astype(compute_dtype) @ psi[:, None])[:, 0]  # (k,)
    partial = jnp.sum(val * gathered)

    @pl.when(j == 0)
    def _init():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    out_ref[0] += partial.astype(out_ref.dtype)


def sparse_dot(
    psi: jax.Array,  # (N, D)
    idx: jax.Array,  # (N, k) int32
    val: jax.Array,  # (N, k)
    *,
    block_d: int = 512,
    interpret: bool = False,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Per-node sparse dot products: out[n] = sum_k val[n,k] * psi[n, idx[n,k]].

    compute_dtype: accumulation dtype inside the kernel. float32 is the TPU
    MXU-native default; pass psi.dtype (e.g. float64 in interpret mode on
    CPU) when the caller needs bit-exact agreement with a f64 reference.
    """
    N, D = psi.shape
    k = idx.shape[1]
    block_d = min(block_d, D)
    grid = (N, pl.cdiv(D, block_d))
    kernel = functools.partial(
        _dot_kernel, block_d=block_d, d=D, compute_dtype=compute_dtype
    )
    return pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_d), lambda n, j: (n, j)),
            pl.BlockSpec((1, k), lambda n, j: (n, 0)),
            pl.BlockSpec((1, k), lambda n, j: (n, 0)),
        ],
        out_specs=pl.BlockSpec((1,), lambda n, j: (n,)),
        out_shape=jax.ShapeDtypeStruct((N,), compute_dtype),
        name="saga_sparse_dot",
        interpret=interpret,
    )(psi, idx.astype(jnp.int32), val)


def _axpy_kernel(psi_ref, idx_ref, val_ref, coef_ref, rho_ref, out_ref, *,
                 block_d: int, compute_dtype):
    """out_block = rho * psi_block + coef * scatter(val at idx) in-block.

    One grid cell holds every node's (N, block_d) tile; a loop over the
    node rows builds each row's scatter as a (k, block_d) one-hot select
    reduced over k on the VPU. The index and value rows arrive as (k, 1)
    columns, so the one-hot compares a column against a lane iota and no
    in-kernel transpose, batched dot or MXU rounding is involved: the sum
    has at most one nonzero term per column (distinct indices per row), so
    it is exact in any dtype.
    """
    j = pl.program_id(0)
    k = idx_ref.shape[1]
    cols = j * block_d + jax.lax.broadcasted_iota(jnp.int32, (k, block_d), 1)

    def row(r, carry):
        idx = idx_ref[r]  # (k, 1)
        val = val_ref[r].astype(compute_dtype)  # (k, 1)
        hit = idx == cols  # (k, block_d); out-of-block indices never match
        scat = jnp.sum(jnp.where(hit, val, 0.0), axis=0, keepdims=True)
        rows = pl.ds(r, 1)
        psi = psi_ref[rows, :].astype(compute_dtype)  # (1, block_d)
        rho = rho_ref[rows, :].astype(compute_dtype)  # (1, 1)
        coef = coef_ref[rows, :].astype(compute_dtype)
        out_ref[rows, :] = (rho * psi + coef * scat).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[0], row, 0)


def sparse_axpy(
    psi: jax.Array,  # (N, D)
    idx: jax.Array,  # (N, k)
    val: jax.Array,  # (N, k)
    coef: jax.Array,  # (N,)   e.g. -a_eff * g_n
    rho: jax.Array,  # (N,)   e.g. 1/(1+alpha lam)
    *,
    block_d: int = 512,
    interpret: bool = False,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """out[n] = rho[n] * psi[n] + coef[n] * x_n (sparse row scatter).

    compute_dtype: in-kernel arithmetic dtype (see sparse_dot). The output
    keeps psi.dtype either way.

    The grid runs over d blocks only; each cell covers all N nodes, so every
    block's second-minor dimension is the whole node axis (legal on the TPU
    for any N) and the emulated grid stays small in interpret mode. The
    per-node operands are laid out as columns: idx/val as (N, k, 1),
    coef/rho as (N, 1).
    """
    N, D = psi.shape
    k = idx.shape[1]
    if not interpret and jnp.float64 in (psi.dtype, jnp.dtype(compute_dtype)):
        raise ValueError(
            "the compiled TPU kernel has no float64 (Mosaic lacks it): pass "
            "float32 data, or use the jnp reference (use_pallas='off')"
        )
    block_d = min(block_d, D)
    if block_d < D and block_d % 128:
        raise ValueError(f"block_d={block_d} must be a multiple of 128")
    kernel = functools.partial(
        _axpy_kernel, block_d=block_d, compute_dtype=compute_dtype
    )
    return pallas_call(
        kernel,
        grid=(pl.cdiv(D, block_d),),
        in_specs=[
            pl.BlockSpec((N, block_d), lambda j: (0, j)),
            pl.BlockSpec((N, k, 1), lambda j: (0, 0, 0)),
            pl.BlockSpec((N, k, 1), lambda j: (0, 0, 0)),
            pl.BlockSpec((N, 1), lambda j: (0, 0)),
            pl.BlockSpec((N, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((N, D), psi.dtype),
        name="saga_sparse_axpy",
        interpret=interpret,
    )(
        psi, idx.astype(jnp.int32)[..., None], val[..., None],
        coef.reshape(N, 1), rho.reshape(N, 1),
    )
