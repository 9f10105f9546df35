"""Plain float32 reference of the dense decoder the serving cells run.

The architecture is the one ``repro.models`` serves for ``family="dense"``
(written here from its description, importing nothing of the program):
token embedding scaled by sqrt(d_model); per layer a pre-RMSNorm GQA
self-attention with rotary positions (the two halves of each head rotated
against each other, theta ``rope_theta``), causal softmax at
head_dim^-1/2, and a pre-RMSNorm SwiGLU MLP, each added to the residual;
a final RMSNorm and an untied output head.

``served_gaps`` runs one sequence (prompt + served tokens, right-padded to
a fixed length so one program serves every request) in float32 under
``default_matmul_precision("highest")``, one layer at a time, and returns,
for each served token, by how much its logit lies below the reference's
largest logit at that position. ``choice_gaps`` is the control: the same
model with every matmul operand first rounded to float8 e4m3 under a
per-tensor scale (the precision below bf16), and at each position the
reference's gap of the token the fp8 model puts first.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class LM:
    """The scalars of a configuration the reference needs (hashable)."""

    n_layers: int
    d_model: int
    norm_eps: float
    rope_theta: float


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(quant):
    if quant == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, _fp8(a), _fp8(b))
    return lambda eq, a, b: jnp.einsum(eq, a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, H, Dh): rotate the two halves of each head by position."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("model", "quant"))
def _layer(x, p, *, model, quant):
    """One decoder layer over x (S, d) in float32."""
    mm = _mm(quant)
    eps, theta = model.norm_eps, model.rope_theta
    f32 = lambda a: a.astype(jnp.float32)
    h = _rms(x, f32(p["ln1"]), eps)
    q = _rope(mm("sd,dhk->shk", h, f32(p["wq"])), theta)
    k = _rope(mm("sd,dhk->shk", h, f32(p["wk"])), theta)
    v = mm("sd,dhk->shk", h, f32(p["wv"]))
    s, hq, dh = q.shape
    g = hq // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = mm("shk,thk->hst", q, k) * dh**-0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = mm("hst,thk->shk", probs, v)
    x = x + mm("shk,hkd->sd", o, f32(p["wo"]))
    h = _rms(x, f32(p["ln2"]), eps)
    gate = mm("sd,df->sf", h, f32(p["wg"]))
    up = mm("sd,df->sf", h, f32(p["wu"]))
    return x + mm("sf,fd->sd", jax.nn.silu(gate) * up, f32(p["wd"]))


@functools.partial(jax.jit, static_argnames=("model",))
def _embed(embed, tokens, *, model):
    return embed[tokens].astype(jnp.float32) * jnp.float32(model.d_model**0.5)


@functools.partial(jax.jit, static_argnames=("model", "n_out"))
def _served_gaps(x, final_norm, lm_head, tokens, start, *, model, n_out):
    """Gaps of the served tokens at positions start-1 .. start+n_out-2."""
    xs = jax.lax.dynamic_slice_in_dim(x, start - 1, n_out, 0)
    served = jax.lax.dynamic_slice_in_dim(tokens, start, n_out, 0)
    logits = _rms(xs, final_norm.astype(jnp.float32), model.norm_eps) @ (
        lm_head.astype(jnp.float32))
    at = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return logits.max(-1) - at


@functools.partial(jax.jit, static_argnames=("model", "quant", "n_out"))
def _choice_gaps(x_ref, x_ctl, final_norm, lm_head, start, *, model, quant,
                 n_out):
    """Reference gaps of the tokens the ``quant`` model puts first."""
    def head(x, mm):
        xs = jax.lax.dynamic_slice_in_dim(x, start - 1, n_out, 0)
        h = _rms(xs, final_norm.astype(jnp.float32), model.norm_eps)
        return mm("sd,dv->sv", h, lm_head.astype(jnp.float32))

    ref = head(x_ref, _mm(None))
    top = jnp.argmax(head(x_ctl, _mm(quant)), -1)
    return ref.max(-1) - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]


def hidden(params, tokens, model: LM, quant: str | None = None):
    """Final hidden states (S, d) of one right-padded sequence (S,); the
    pad positions come after every compared one, so causal attention
    keeps them out."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens, model=model)
        blocks = params["blocks"]
        for i in range(model.n_layers):
            p = jax.tree_util.tree_map(lambda a: a[i], blocks)
            p = {**p["attn"], **p["mlp"], "ln1": p["ln1"], "ln2": p["ln2"]}
            x = _layer(x, p, model=model, quant=quant)
        return x


def served_gaps(params, tokens, prompt_len: int, n_out: int, model: LM):
    """For each served token, by how much its logit lies below the float32
    reference's largest logit at its position: the first n served entries
    of an array of fixed length ``n_out`` (one compiled program)."""
    x = hidden(params, tokens, model)
    with jax.default_matmul_precision("highest"):
        return _served_gaps(x, params["final_norm"], params["lm_head"],
                            tokens, jnp.int32(prompt_len), model=model,
                            n_out=n_out)


def choice_gaps(params, tokens, prompt_len: int, n_out: int, model: LM,
                quant: str):
    """The control: at the same positions, the reference's gap of the
    token that the model computed in ``quant`` puts first."""
    x_ref = hidden(params, tokens, model)
    x_ctl = hidden(params, tokens, model, quant)
    with jax.default_matmul_precision("highest"):
        return _choice_gaps(x_ref, x_ctl, params["final_norm"],
                            params["lm_head"], jnp.int32(prompt_len),
                            model=model, quant=quant, n_out=n_out)
