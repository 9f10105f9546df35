"""Operations and bytes of Kimi-K2's kernels and model steps, from shapes
and the program's route counters.

Least work the algorithm needs, not what an implementation moves: they set
the roofline's least time. ``k`` is ``mla_moe_weights.dims`` of the
configuration.
"""
from __future__ import annotations


def mla_decode(ctx_tokens: int, b: int, heads: int, latent: int,
               value: int, itemsize: int) -> tuple[float, float]:
    """Absorbed latent decode attention over ``ctx_tokens`` cached rows (the
    summed context of the b slots): each row's ``latent`` values read once,
    scored by every head (latent-wide dot) and summed into its output
    (value-wide); q (b, heads, latent) read, o (b, heads, value) written."""
    flops = 2.0 * ctx_tokens * heads * (latent + value)
    nbytes = itemsize * (ctx_tokens * latent + b * heads * (latent + value))
    return flops, float(nbytes)


def expert_matmul(routes: int, experts_hit: int, d: int, f: int,
                  itemsize: int) -> tuple[float, float]:
    """The held experts' SwiGLU over ``routes`` routed rows: 6 d f FLOPs a
    route (gate, up, down); each expert with a route (``experts_hit``
    (layer, expert) pairs) read once, three d x f matrices; each routed
    row's activations read and its output written."""
    flops = 6.0 * d * f * routes
    nbytes = itemsize * (3.0 * d * f * experts_hit + 2.0 * d * routes)
    return flops, nbytes


def _attn_params(k: dict) -> int:
    d, h, qk = k["d"], k["heads"], k["nope"] + k["rope"]
    return (d * k["q_lora"] + k["q_lora"] * h * qk + d * (k["kv_lora"]
            + k["rope"]) + k["kv_lora"] * h * (k["nope"] + k["v"])
            + h * k["v"] * d)


def token_flops(k: dict) -> float:
    """Model FLOPs of one token through every layer, routed experts left
    out (they are counted by route): the attention projections, the dense
    layers' SwiGLU, the MoE layers' router and shared expert, and the
    output head."""
    d, n_moe = k["d"], k["n_layers"] - k["n_dense"]
    return 2.0 * (k["n_layers"] * _attn_params(k)
                  + k["n_dense"] * 3 * d * k["ff"]
                  + n_moe * (3 * d * k["shared"] + d * k["experts"]))


def attn_flops(k: dict) -> float:
    """Attention FLOPs of one query against one key, over every layer: the
    model's (expanded) form, q.k over qk_head_dim and p.v over v."""
    return 2.0 * k["heads"] * (k["nope"] + k["rope"] + k["v"]) * k["n_layers"]


def decode_flops(k: dict, tokens: int, ctx_tokens: int, routes: int) -> float:
    """Useful model FLOPs of decode: ``tokens`` through every layer and the
    output head, attending to ``ctx_tokens`` (summed context), and the
    ``routes`` routed rows through their held experts."""
    head = 2.0 * k["d"] * k["vocab"]
    return ((token_flops(k) + head) * tokens + attn_flops(k) * ctx_tokens
            + 6.0 * k["d"] * k["f"] * routes)


def prefill_flops(k: dict, prompt_tokens: int, prompt_sq: int, prefills: int,
                  routes: int) -> float:
    """Useful model FLOPs of prefill: the prompts' own tokens (not their
    padding) through every layer, causal attention over each prompt
    (``prompt_sq`` the sum of squared prompt lengths), the routed rows
    through their held experts, and one output row per prefill."""
    head = 2.0 * k["d"] * k["vocab"]
    return (token_flops(k) * prompt_tokens + attn_flops(k) * 0.5 * prompt_sq
            + 6.0 * k["d"] * k["f"] * routes + head * prefills)
