"""Seeded weights of Kimi-K2's block (MLA + held experts) in the layout
``repro.models`` serves, and the configuration's numbers read from the
catalog keys of its file.

Every leaf is made on the device in the dtype it is served in, one jitted
call per leaf (no leaf's float32 draw outlives its cast), from the seed:
normal entries scaled by 1/sqrt(fan-in), the norms ones, the embedding at
1/sqrt(d_model) (the program scales it by sqrt(d_model), so the residual
starts at unit scale). The router's selection bias stands for a trained
one, which keeps the experts' loads even: normal at ``BIAS_STD``, then
each layer's held experts set to 0 and the other experts' mean taken out,
so that the held experts sit at the layer's mean. It changes the choice of
about two tokens in three and leaves the held experts their even share,
top_k / n_experts of the routes, on every seed (a wider bias hands them a
share that depends on the seed). The reference makes the same weights
again from the same seed rather than take them from the program.
"""
from __future__ import annotations

import functools
import math

from chipbench.lm_weights import key_of

BIAS_STD = 0.01  # of the selection bias; router scores have unit scale


def dims(m: dict) -> dict:
    """The configuration's sizes under short names, from the catalog keys
    (``n_layers`` and ``n_experts_held`` are the chip's cut)."""
    if (m["n_group"], m["topk_group"], m["scoring_func"], m["topk_method"],
            m["norm_topk_prob"], m["hidden_act"], m["moe_layer_freq"],
            m["num_nextn_predict_layers"], m["tie_word_embeddings"],
            m["attention_bias"], m["rope_scaling"]["type"]) != (
            1, 1, "sigmoid", "noaux_tc", True, "silu", 1, 0, False, False,
            "yarn"):
        raise ValueError("configuration outside the DeepSeek-V3 block served")
    ys = m["rope_scaling"]
    if ys["mscale"] != ys["mscale_all_dim"]:
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos/sin")
    return dict(
        n_layers=m["n_layers"], n_dense=m["first_k_dense_replace"],
        d=m["hidden_size"], heads=m["num_attention_heads"],
        q_lora=m["q_lora_rank"], kv_lora=m["kv_lora_rank"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        v=m["v_head_dim"], ff=m["intermediate_size"],
        f=m["moe_intermediate_size"],
        shared=m["moe_intermediate_size"] * m["n_shared_experts"],
        experts=m["n_routed_experts"], held=m["n_experts_held"],
        offset=m["expert_offset"], top_k=m["num_experts_per_tok"],
        scaling=m["routed_scaling_factor"], vocab=m["vocab_size"],
        theta=float(m["rope_theta"]), eps=m["rms_norm_eps"],
        yarn_factor=float(ys["factor"]),
        yarn_original=ys["original_max_position_embeddings"],
        yarn_beta_fast=float(ys["beta_fast"]),
        yarn_beta_slow=float(ys["beta_slow"]), yarn_mscale=float(ys["mscale"]),
    )


def shapes(m: dict) -> dict:
    """{path: (shape, stddev or None for ones)} of every leaf."""
    k = dims(m)
    d, h, ql, kl = k["d"], k["heads"], k["q_lora"], k["kv_lora"]
    qk, f = k["nope"] + k["rope"], k["f"]

    def attn(n, pre):
        return {
            f"{pre}/ln1": ((n, d), None),
            f"{pre}/ln2": ((n, d), None),
            f"{pre}/attn/wq_a": ((n, d, ql), d**-0.5),
            f"{pre}/attn/q_norm": ((n, ql), None),
            f"{pre}/attn/wq_b": ((n, ql, h, qk), ql**-0.5),
            f"{pre}/attn/wkv_a": ((n, d, kl + k["rope"]), d**-0.5),
            f"{pre}/attn/kv_norm": ((n, kl), None),
            f"{pre}/attn/wkv_b": ((n, kl, h, k["nope"] + k["v"]), kl**-0.5),
            f"{pre}/attn/wo": ((n, h, k["v"], d), (h * k["v"]) ** -0.5),
        }

    nd, nm, e, s = k["n_dense"], k["n_layers"] - k["n_dense"], k["held"], \
        k["shared"]
    out = {
        "embed": ((k["vocab"], d), d**-0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, k["vocab"]), d**-0.5),
        **attn(nm, "blocks"),
        "blocks/moe/router": ((nm, d, k["experts"]), d**-0.5),
        "blocks/moe/router_bias": ((nm, k["experts"]), BIAS_STD),
        "blocks/moe/wg": ((nm, e, d, f), d**-0.5),
        "blocks/moe/wu": ((nm, e, d, f), d**-0.5),
        "blocks/moe/wd": ((nm, e, f, d), f**-0.5),
        "blocks/moe/shared/wg": ((nm, d, s), d**-0.5),
        "blocks/moe/shared/wu": ((nm, d, s), d**-0.5),
        "blocks/moe/shared/wd": ((nm, s, d), s**-0.5),
    }
    if nd:
        out.update(attn(nd, "dense_blocks"))
        out.update({
            "dense_blocks/mlp/wg": ((nd, d, k["ff"]), d**-0.5),
            "dense_blocks/mlp/wu": ((nd, d, k["ff"]), d**-0.5),
            "dense_blocks/mlp/wd": ((nd, k["ff"], d), k["ff"] ** -0.5),
        })
    return out


@functools.lru_cache(maxsize=None)
def _leaf(shape: tuple, std, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    if std is None:
        return lambda key: jnp.ones(shape, dtype)
    return jax.jit(lambda key: (std * jax.random.normal(
        key, shape, jnp.float32)).astype(dtype))


def _held_at_mean(bias, offset: int, held: int):
    """Each layer's bias (n, experts) with experts [offset, offset + held)
    at 0 and the others' mean taken out: every row's mean is 0."""
    import jax.numpy as jnp

    b = bias.astype(jnp.float32)
    col = jnp.arange(b.shape[-1])
    mine = (col >= offset) & (col < offset + held)
    b = jnp.where(mine, 0.0, b)
    b = b - b.sum(-1, keepdims=True) / (b.shape[-1] - held)
    return jnp.where(mine, 0.0, b).astype(bias.dtype)


def make(m: dict, seed: int, dtype_name: str = "bfloat16") -> dict:
    """The nested params dict of configuration ``m``, on the device."""
    import jax

    key = jax.random.PRNGKey(key_of(seed))
    k = dims(m)
    out: dict = {}
    for i, (path, (shape, std)) in enumerate(sorted(shapes(m).items())):
        leaf = _leaf(shape, std, dtype_name)(jax.random.fold_in(key, i))
        if path == "blocks/moe/router_bias":
            leaf = _held_at_mean(leaf, k["offset"], k["held"])
        node = out
        *heads, last = path.split("/")
        for part in heads:
            node = node.setdefault(part, {})
        node[last] = leaf
    return out


def n_params(m: dict) -> int:
    """Parameter count of configuration ``m`` as served."""
    return sum(math.prod(s) for s, _ in shapes(m).values())
