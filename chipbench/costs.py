"""Operations and bytes of each kernel and of a model step, from shapes.

These are the least work the algorithm needs for a call, not what an
implementation happens to move: they set the roofline's least time.
"""
from __future__ import annotations


def sparse_axpy(n: int, d: int, k: int, itemsize: int) -> tuple[None, float]:
    """out[n] = rho[n] psi[n] + coef[n] x_n over an (n, d) psi and rows of
    k nonzeros: psi read and out written whole, the rows' indices (int32)
    and values read, coef and rho read. Float32 work has no published peak
    on the v5e, so no FLOP count is returned and the bound is by bytes."""
    nbytes = 2 * n * d * itemsize + n * k * (4 + itemsize) + 2 * n * itemsize
    return None, float(nbytes)


def flash_attention(b: int, hq: int, hkv: int, s: int, dh: int,
                    itemsize: int, causal: bool = True) -> tuple[float, float]:
    """Self-attention forward over s positions: QK^T and PV, 2 FLOPs per
    multiply-add each, halved under a causal mask; q, k, v read and o
    written once."""
    flops = 4.0 * b * hq * s * s * dh * (0.5 if causal else 1.0)
    nbytes = itemsize * b * s * dh * (2 * hq + 2 * hkv)
    return flops, float(nbytes)


def decode_attention(ctx_tokens: int, b: int, hq: int, hkv: int, dh: int,
                     itemsize: int) -> tuple[float, float]:
    """One token per slot against the cached keys and values: ``ctx_tokens``
    is the summed context over the b slots. K and V of every context token
    read once, q read and o written."""
    flops = 4.0 * ctx_tokens * hq * dh
    nbytes = itemsize * (2 * ctx_tokens * hkv * dh + 2 * b * hq * dh)
    return flops, float(nbytes)


def layer_params(m: dict) -> int:
    """Matmul parameters of one dense decoder layer (attention + SwiGLU)."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    return attn + 3 * d * m["d_ff"]


def prefill_flops(m: dict, prompt_tokens: int, prompt_sq: int,
                  prefills: int) -> float:
    """Model FLOPs of useful prefill work: the prompts' own tokens (not
    their padding) through every layer, causal attention over the prompt
    (``prompt_sq`` is the sum of squared prompt lengths), and one output
    row per prefill."""
    per_tok = 2.0 * layer_params(m) * m["n_layers"]
    attn = 4.0 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    head = 2.0 * m["d_model"] * m["vocab_size"]
    return per_tok * prompt_tokens + attn * 0.5 * prompt_sq + head * prefills


def decode_flops(m: dict, decode_tokens: int, ctx_tokens: int) -> float:
    """Model FLOPs of useful decode work: one token per active slot through
    every layer and the output head, attending to its context
    (``ctx_tokens`` is the summed context of those tokens)."""
    per_tok = 2.0 * layer_params(m) * m["n_layers"]
    attn = 4.0 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    head = 2.0 * m["d_model"] * m["vocab_size"]
    return (per_tok + head) * decode_tokens + attn * ctx_tokens
