"""Plain float32 reference of Kimi-K2's block as the ``kimi_k2`` cell runs it.

Written from the published layer equations (DeepSeek-V3, arXiv:2412.19437
sec. 2.1; Kimi-K2-Instruct's config), importing nothing of the program,
under ``default_matmul_precision("highest")``. h is a layer's normed
residual, t a position, i a head:

  queries   c_q = RMSNorm(h W_DQ); [q_nope_i | q_pe_i] = c_q W_UQ
  keys      [a | k_pe] = h W_DKV; c_kv = RMSNorm(a);
            [k_nope_i | v_i] = c_kv W_UKV; k_pe is one key shared by heads
  rotary    q_pe and k_pe rotate at YaRN frequencies f/factor * r +
            f * (1 - r), r DeepSeek's linear ramp between the correction
            range of beta_fast and beta_slow rotations; the two halves of
            the rope dims rotate against each other (the interleaved pairs
            of the published model are a fixed permutation of q_pe's and
            k_pe's columns of random weights)
  scores    (q_nope_i . k_nope_i + q_pe_i . k_pe) qk^-1/2 m^2, m = 0.1
            mscale ln(factor) + 1; causal softmax; o_i = sum p v_i; the
            output concat_i(o_i) W_O — in the expanded form, not the
            absorbed one the program decodes with, and in blocks of
            queries, so that 5,120 positions at 64 heads fit
  gating    s = sigmoid(h W_r); the top-k experts by s + b (b the
            selection bias); weights s_top / sum(s_top) * routed_scaling
  output    y = sum over the chosen held experts of w_e SwiGLU_e(h), plus
            the shared expert: every held expert is applied to every
            token, weighted by its routing weight (0 where not chosen)

The layers are the leading dense layer(s), SwiGLU of intermediate_size,
then the MoE layers; RMSNorm before each half; a final RMSNorm and the
untied output head over the vocabulary slice. The embedding is scaled by
sqrt(d_model), as the program does: with entries of scale 1/sqrt(d_model)
that is the published unscaled embedding with unit-scale entries.
The chip holds experts [offset, offset + held) of the router's
n_experts; the rest add nothing here, as in the program.

``served_gaps`` runs one sequence (prompt + served tokens, right-padded to
a fixed length so one program serves every request) one layer at a time
and returns, for each served token, by how much its logit lies below the
reference's largest, and the same gap of the next token id in its place
(what a served token altered by one id would read). ``choice_gaps`` is the control: the same model with
every matmul operand rounded to float8 e4m3 under a per-tensor scale (the
precision below bf16), and the reference's gap of the token it puts first.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn
Q_BLOCK = 512  # queries per attention block


@dataclasses.dataclass(frozen=True)
class LM:
    """The sizes and scalars the reference needs (hashable)."""

    n_layers: int
    n_dense: int
    d: int
    heads: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    experts: int
    held: int
    offset: int
    top_k: int
    scaling: float
    theta: float
    eps: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float

    @classmethod
    def of(cls, dims: dict) -> "LM":
        """From ``mla_moe_weights.dims`` of a configuration."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in dims.items() if k in names})


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


def yarn_freqs(m: LM) -> np.ndarray:
    """The rope's rope // 2 frequencies under YaRN."""
    dim, base = m.rope, m.theta
    f = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def corr(rot):  # pair index at which the original context turns rot times
        return dim * math.log(m.yarn_original / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(m.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(m.yarn_beta_slow)), dim - 1)
    high = high + 0.001 if high == low else high
    r = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f / m.yarn_factor * r + f * (1.0 - r)


def _rotate(x, freqs):
    """x (S, ..., rope): the halves rotate against each other by position."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (freqs.shape[0],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, m: LM, mm):
    """The expanded latent attention of one layer over x (S, d)."""
    f32 = lambda a: a.astype(jnp.float32)
    freqs = yarn_freqs(m)
    c_q = _rms(mm("sd,dr->sr", x, f32(p["wq_a"])), f32(p["q_norm"]), m.eps)
    q = mm("sr,rhk->shk", c_q, f32(p["wq_b"]))
    q_nope, q_pe = q[..., :m.nope], _rotate(q[..., m.nope:], freqs)
    kv = mm("sd,dr->sr", x, f32(p["wkv_a"]))
    c_kv = _rms(kv[:, :m.kv_lora], f32(p["kv_norm"]), m.eps)
    k_pe = _rotate(kv[:, m.kv_lora:], freqs)
    kv = mm("sc,chk->shk", c_kv, f32(p["wkv_b"]))
    k_nope, v = kv[..., :m.nope], kv[..., m.nope:]
    mscale = 0.1 * m.yarn_mscale * math.log(m.yarn_factor) + 1.0
    scale = (m.nope + m.rope) ** -0.5 * mscale**2
    S = x.shape[0]
    t = jnp.arange(S)

    def block(qs):  # one block of Q_BLOCK queries starting at qs
        qn = jax.lax.dynamic_slice_in_dim(q_nope, qs, Q_BLOCK, 0)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, qs, Q_BLOCK, 0)
        s = (mm("shk,thk->hst", qn, k_nope) + mm("shk,tk->hst", qp, k_pe))
        rows = qs + jnp.arange(Q_BLOCK)
        s = jnp.where(rows[:, None] >= t[None, :], s * scale, -jnp.inf)
        return mm("hst,thv->shv", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, jnp.arange(0, S, Q_BLOCK))
    o = o.reshape(S, m.heads, m.v)
    return mm("shv,hvd->sd", o, f32(p["wo"]))


def _swiglu(x, wg, wu, wd, mm):
    f32 = lambda a: a.astype(jnp.float32)
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, f32(wg)))
              * mm("sd,df->sf", x, f32(wu)), f32(wd))


def _held_experts(h, p, m: LM, mm):
    """The held experts' part of the MoE output plus the shared expert."""
    f32 = lambda a: a.astype(jnp.float32)
    s = jax.nn.sigmoid(mm("sd,de->se", h, f32(p["router"])))  # (S, experts)
    _, top = jax.lax.top_k(s + f32(p["router_bias"]), m.top_k)
    w_top = jnp.take_along_axis(s, top, -1)
    w_top = w_top / w_top.sum(-1, keepdims=True) * m.scaling
    local = top - m.offset
    w = jnp.zeros((h.shape[0], m.held)).at[
        jnp.arange(h.shape[0])[:, None], jnp.where(
            (local >= 0) & (local < m.held), local, m.held)].set(
        w_top, mode="drop")  # (S, held), 0 where not chosen
    g = jax.nn.silu(mm("sd,edf->sef", h, f32(p["wg"])))
    u = mm("sd,edf->sef", h, f32(p["wu"]))
    y = mm("sef,efd->sd", g * u * w[..., None], f32(p["wd"]))
    sh = p["shared"]
    return y + _swiglu(h, sh["wg"], sh["wu"], sh["wd"], mm)


@functools.partial(jax.jit, static_argnames=("m", "quant", "dense"))
def _layer(x, p, *, m: LM, quant, dense):
    """One decoder layer over x (S, d) in float32."""
    mm = _mm(quant)
    f32 = lambda a: a.astype(jnp.float32)
    x = x + _attention(_rms(x, f32(p["ln1"]), m.eps), p["attn"], m, mm)
    h = _rms(x, f32(p["ln2"]), m.eps)
    if dense:
        ml = p["mlp"]
        return x + _swiglu(h, ml["wg"], ml["wu"], ml["wd"], mm)
    return x + _held_experts(h, p["moe"], m, mm)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(embed, tokens, *, m: LM):
    return embed[tokens].astype(jnp.float32) * jnp.float32(m.d**0.5)


def hidden(params, tokens, m: LM, quant: str | None = None):
    """Final hidden states (S, d) of one right-padded sequence (S,); S a
    multiple of Q_BLOCK. Pad positions come after every compared one, so
    causal attention keeps them out."""
    assert tokens.shape[0] % Q_BLOCK == 0, tokens.shape
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens, m=m)
        for i in range(m.n_layers):
            dense = i < m.n_dense
            blocks = params["dense_blocks" if dense else "blocks"]
            j = i if dense else i - m.n_dense
            p = jax.tree_util.tree_map(lambda a: a[j], blocks)
            x = _layer(x, p, m=m, quant=quant, dense=dense)
        return x


@functools.partial(jax.jit, static_argnames=("m", "n_out"))
def _served_gaps(x, final_norm, lm_head, tokens, start, *, m: LM, n_out):
    """Gaps of the served tokens at positions start-1 .. start+n_out-2, and
    of the next token id at each."""
    xs = jax.lax.dynamic_slice_in_dim(x, start - 1, n_out, 0)
    served = jax.lax.dynamic_slice_in_dim(tokens, start, n_out, 0)
    logits = _rms(xs, final_norm.astype(jnp.float32), m.eps) @ (
        lm_head.astype(jnp.float32))
    top = logits.max(-1)

    def gap(ids):
        return top - jnp.take_along_axis(logits, ids[:, None], -1)[:, 0]

    return gap(served), gap((served + 1) % logits.shape[-1])


@functools.partial(jax.jit, static_argnames=("m", "quant", "n_out"))
def _choice_gaps(x_ref, x_ctl, final_norm, lm_head, start, *, m: LM, quant,
                 n_out):
    """Reference gaps of the tokens the ``quant`` model puts first."""
    def head(x, mm):
        xs = jax.lax.dynamic_slice_in_dim(x, start - 1, n_out, 0)
        h = _rms(xs, final_norm.astype(jnp.float32), m.eps)
        return mm("sd,dv->sv", h, lm_head.astype(jnp.float32))

    ref = head(x_ref, _mm(None))
    top = jnp.argmax(head(x_ctl, _mm(quant)), -1)
    return ref.max(-1) - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]


def served_gaps(params, tokens, prompt_len: int, n_out: int, m: LM):
    """For each served token, by how much its logit lies below the float32
    reference's largest logit at its position, and the same for the next
    token id in its place: two arrays of fixed length ``n_out`` (one
    compiled program) whose first n entries are the n served tokens'."""
    x = hidden(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return _served_gaps(x, params["final_norm"], params["lm_head"],
                            tokens, jnp.int32(prompt_len), m=m, n_out=n_out)


def choice_gaps(params, tokens, prompt_len: int, n_out: int, m: LM,
                quant: str):
    """The control: at the same positions, the reference's gap of the
    token that the model computed in ``quant`` puts first."""
    x_ref = hidden(params, tokens, m)
    x_ctl = hidden(params, tokens, m, quant)
    with jax.default_matmul_precision("highest"):
        return _choice_gaps(x_ref, x_ctl, params["final_norm"],
                            params["lm_head"], jnp.int32(prompt_len), m=m,
                            quant=quant, n_out=n_out)
