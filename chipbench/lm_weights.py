"""Seeded weights of a dense decoder in the layout ``repro.models`` serves.

One jitted call makes every leaf on the device, in the dtype it is served
in, from the seed: normal entries scaled by 1/sqrt(fan-in), the norms
ones. The embedding has entries of scale 1/sqrt(d_model), so that after
the model's sqrt(d_model) input scaling the residual stream starts at
unit scale and attention and the MLP add to it on the same scale: with
unit-scale embeddings the residual would be the current token's
embedding, and a fault in attention or the cache would hardly move the
logits. The reference makes the same weights again
from the same seed rather than take them from the program.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def shapes(m: dict) -> dict:
    """{path: (shape, stddev or None for ones)} of every leaf."""
    d, h, kv, hd, ff, v, n = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                              m["head_dim"], m["d_ff"], m["vocab_size"],
                              m["n_layers"])
    return {
        "embed": ((v, d), d**-0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, v), d**-0.5),
        "blocks/ln1": ((n, d), None),
        "blocks/ln2": ((n, d), None),
        "blocks/attn/wq": ((n, d, h, hd), d**-0.5),
        "blocks/attn/wk": ((n, d, kv, hd), d**-0.5),
        "blocks/attn/wv": ((n, d, kv, hd), d**-0.5),
        "blocks/attn/wo": ((n, h, hd, d), (h * hd) ** -0.5),
        "blocks/mlp/wg": ((n, d, ff), d**-0.5),
        "blocks/mlp/wu": ((n, d, ff), d**-0.5),
        "blocks/mlp/wd": ((n, ff, d), ff**-0.5),
    }


def key_of(seed: int) -> int:
    """A 31-bit PRNG seed from any whole number (seeds may pass 32 bits)."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0] >> 1)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *heads, last = path.split("/")
        for part in heads:
            node = node.setdefault(part, {})
        node[last] = leaf
    return out


@functools.lru_cache(maxsize=None)
def _maker(items: tuple, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def make(seed):
        key = jax.random.PRNGKey(seed)
        flat = {}
        for i, (path, shape, std) in enumerate(items):
            if std is None:
                flat[path] = jnp.ones(shape, dtype)
            else:
                k = jax.random.fold_in(key, i)
                flat[path] = (std * jax.random.normal(k, shape, jnp.float32)
                              ).astype(dtype)
        return _nest(flat)

    return jax.jit(make)


def make(m: dict, seed: int, dtype_name: str = "bfloat16") -> dict:
    """The nested params dict of configuration ``m``, on the device."""
    items = tuple((p, s, std) for p, (s, std) in shapes(m).items())
    return _maker(items, dtype_name)(key_of(seed))


def n_params(m: dict) -> int:
    """Parameter count of configuration ``m``."""
    return sum(math.prod(s) for s, _ in shapes(m).values())
