"""Pallas TPU grouped matmul over the experts a chip holds (dropless MoE).

The rows routed to the held experts arrive laid out expert by expert, each
expert's rows padded to whole tiles of ``tm`` rows, so that every row tile
belongs to one expert: ``tile_group[t]`` names the weight matrix of tile
``t``, and only the first ``n_active`` tiles hold rows. The grid is
``(n_active, N // tn, K // tk)`` with the tile count dynamic (a scalar),
so tiles past the routed rows cost nothing and an expert with no route is
never read; an expert's weights are read once per tile of its rows.

``w`` is the stacked weights of every group the caller may name, e.g. all
MoE layers' held experts flattened to (layers x held, K, N): the index map
picks the tile's matrix, so one layer's weights are never sliced out (a
slice would be a copy of the whole layer's experts on every call).

Rows of tiles at or past ``n_active`` are left unwritten: undefined.

Validated against kernels/ref.py ``expert_matmul_ref`` in interpret mode;
dispatch and tolerance policy live in kernels/ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _block(n: int, most: int = 1024) -> int:
    """Largest multiple of 128 up to ``most`` that divides n (n itself if
    n is not a multiple of 128: a block may span a whole dimension)."""
    if n % 128:
        return n
    return max(b for b in range(128, min(n, most) + 1, 128) if n % b == 0)


def _kernel(group_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (row tile, column block, contraction block) program instance:
    x_ref (tm, tk), w_ref (tk, tn) of the tile's expert, o_ref (tm, tn)."""
    del group_ref  # read by the index maps
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def expert_matmul(
    x: jax.Array,  # (M, K) rows, tm-row tiles of one expert each
    w: jax.Array,  # (G, K, N) stacked expert matrices
    tile_group: jax.Array,  # (M // tm,) int32 — matrix of each row tile
    n_active,  # int32 scalar — tiles that hold rows (a prefix)
    *,
    tm: int,
    interpret: bool = False,
) -> jax.Array:
    """out[t*tm:(t+1)*tm] = x[t*tm:(t+1)*tm] @ w[tile_group[t]] for every
    tile t < n_active, (M, N) in x.dtype; other rows undefined."""
    M, K = x.shape
    _, K2, N = w.shape
    assert K == K2 and M % tm == 0, (x.shape, w.shape, tm)
    tk, tn = _block(K), _block(N)
    grid = (jnp.asarray(n_active, jnp.int32), N // tn, K // tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda t, n, k, g: (t, k)),
            pl.BlockSpec((pl.Squeezed(), tk, tn),
                         lambda t, n, k, g: (g[t], k, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda t, n, k, g: (t, n)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pallas_call(
        functools.partial(_kernel, n_k=K // tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="expert_matmul",
        interpret=interpret,
    )(tile_group.astype(jnp.int32), x, w.astype(x.dtype))
