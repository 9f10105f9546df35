"""Pallas TPU kernel: block-local top-k magnitude compression.

The gossip delta streams (core/gossip.py) need top-k over 10^8..10^11
element parameter leaves. A global sort is O(n log n) and serializes; the
production scheme is BLOCK-LOCAL top-k: reshape to (blocks, block_size),
keep k_b entries per block. Wire format stays fixed-size (values + local
indices), selection is embarrassingly parallel, and quality is within a few
percent of exact global top-k for heavy-tailed gradients.

In-kernel selection is k_b rounds of (argmax, mask) on the VPU — no sort.
Grid: one program per block row-group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int, block: int):
    x = x_ref[0].astype(jnp.float32)  # (block,)
    mag = jnp.abs(x)
    iota = jax.lax.iota(jnp.int32, block)

    def body(i, carry):
        mag_c, = carry
        j = jnp.argmax(mag_c)
        vals_ref[0, i] = x[j].astype(vals_ref.dtype)
        idx_ref[0, i] = j.astype(jnp.int32)
        return (jnp.where(iota == j, -1.0, mag_c),)

    jax.lax.fori_loop(0, k, body, (mag,))


def block_topk(
    x: jax.Array,  # (n_blocks, block)
    k: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-block top-k by |value|: (vals (nb, k), local idx (nb, k) int32)."""
    nb, block = x.shape
    kernel = functools.partial(_topk_kernel, k=k, block=block)
    return pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, k), x.dtype),
            jax.ShapeDtypeStruct((nb, k), jnp.int32),
        ],
        name="block_topk",
        interpret=interpret,
    )(x)
