"""Core transformer layers: norm, RoPE, GQA and latent attention, MLP, MoE.

Pure-functional: every layer is (cfg, params_subtree, activations) -> out.
Forward math runs in cfg.compute_dtype; softmax/norm statistics in fp32.
Activation sharding hints go through `shard_act` (no-op without a mesh).

The jnp attention here is the reference path; kernels/flash_attention.py is
the TPU Pallas version (validated against this in interpret mode). Dispatch
is by config (`ModelConfig.attention_kernel`) — the CPU dry-run and
numerics tests use this path. The registry-dispatched kernel is a
custom_vjp, so when a config routes attention through it the TRAINING
BACKWARD also runs the blocked Pallas gradient kernels (dq + dk/dv tiles
recomputed from the saved log-sum-exp) — no S x S probability matrix in
either direction.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig, kernel_mode
from repro.models.params import ParamDef

# ---------------------------------------------------------------------------
# activation sharding
# ---------------------------------------------------------------------------

ACT_RULES: dict[str, str | tuple | None] = {
    "batch": "data",
    "seq": None,
    "heads": "model",
    "kv_heads": "model",
    "embed": None,
    "mlp": "model",
    "expert": "model",
    "capacity": "data",
    "vocab": "model",
    None: None,
}


# The mesh used for activation constraints. `with mesh:` does NOT set the
# abstract mesh that with_sharding_constraint needs (jax 0.8), so launchers
# register it explicitly via use_constraint_mesh().
_CONSTRAINT_MESH = None
_ACT_OVERRIDES: dict | None = None


class use_constraint_mesh:
    """Context manager: activation shard_act constraints target this mesh.

    act_overrides: optional {logical_axis: mesh_axis} overrides — e.g.
    {'embed': 'model'} turns on residual-stream sharding
    (ModelConfig.shard_residual_embed).
    """

    def __init__(self, mesh, act_overrides: dict | None = None):
        self.mesh = mesh
        self.overrides = act_overrides
        self.prev = None

    def __enter__(self):
        global _CONSTRAINT_MESH, _ACT_OVERRIDES
        self.prev = (_CONSTRAINT_MESH, _ACT_OVERRIDES)
        _CONSTRAINT_MESH = self.mesh
        _ACT_OVERRIDES = self.overrides
        return self.mesh

    def __exit__(self, *exc):
        global _CONSTRAINT_MESH, _ACT_OVERRIDES
        _CONSTRAINT_MESH, _ACT_OVERRIDES = self.prev
        return False


def shard_act(x: jax.Array, *axes: str | None) -> jax.Array:
    """with_sharding_constraint by logical axis names.

    No-op without a registered mesh; axes that don't exist on the mesh or
    don't divide the dim evenly degrade to unsharded (small models on big
    meshes).
    """
    mesh = _CONSTRAINT_MESH
    if mesh is None:
        return x
    spec = []
    for dim, a in zip(x.shape, axes):
        r = (_ACT_OVERRIDES or {}).get(a, ACT_RULES.get(a))
        if r is None or r not in mesh.axis_names or dim % mesh.shape[r] != 0:
            spec.append(None)
        else:
            spec.append(r)
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# norm / rope / embedding
# ---------------------------------------------------------------------------

def rms_norm_def(d: int) -> ParamDef:
    return ParamDef((d,), (None,), init="ones")


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: np.ndarray | None = None) -> jax.Array:
    """x: (..., S, H, Dh) rotated pairwise; positions: (..., S).

    The two halves of each head rotate against each other at ``theta``'s
    frequencies, or at ``inv_freq`` (Dh // 2 of them) where given."""
    dh = x.shape[-1]
    half = dh // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def rope_inv_freq(cfg: ModelConfig, dim: int) -> np.ndarray:
    """The dim // 2 rotation frequencies of a rope of width ``dim``.

    Without YaRN (``yarn_factor`` 1) they are theta^(-2j/dim). With it,
    DeepSeek's form: f/factor * r + f * (1 - r), where r ramps linearly
    from 0 to 1 between the pair indices at which the original context
    holds ``yarn_beta_fast`` and ``yarn_beta_slow`` rotations."""
    base = cfg.rope_theta
    f = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.yarn_factor == 1.0:
        return f

    def corr(rotations):
        return dim * math.log(
            cfg.yarn_original_len / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f / cfg.yarn_factor * ramp + f * (1.0 - ramp)


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale of latent attention: qk_head_dim^-1/2, times m^2 with
    YaRN's m = 0.1 * mscale * ln(factor) + 1."""
    m = 1.0
    if cfg.yarn_factor > 1.0:
        m = 0.1 * cfg.yarn_mscale * math.log(cfg.yarn_factor) + 1.0
    return cfg.qk_head_dim ** -0.5 * m * m


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    # explicit fan-in scales: these projections contract over d (q/k/v) and
    # over heads x head_dim (o), which the default (the second-to-last dim)
    # would miss — leaving attention scores ~d/heads times too large
    proj_in, proj_out = d**-0.5, (cfg.n_heads * hd) ** -0.5
    defs = {
        "wq": ParamDef((d, cfg.n_heads, hd), ("embed", "heads", None),
                       scale=proj_in),
        "wk": ParamDef((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", None),
                       scale=proj_in),
        "wv": ParamDef((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", None),
                       scale=proj_in),
        "wo": ParamDef((cfg.n_heads, hd, d), ("heads", None, "embed"),
                       scale=proj_out),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((cfg.n_heads, hd), ("heads", None), init="zeros")
        defs["bk"] = ParamDef((cfg.n_kv_heads, hd), ("kv_heads", None), init="zeros")
        defs["bv"] = ParamDef((cfg.n_kv_heads, hd), ("kv_heads", None), init="zeros")
    return defs


def _attn_scores_mask(q_pos, k_pos, window, causal):
    """(S_q, S_k) boolean mask: True = attend.

    `window` is always STATIC (None or a python int): gemma2-style
    local/global alternation is expressed by the pair scan in
    models/transformer.py, not by threading a traced per-layer scalar.
    """
    m = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def multi_head_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (B, S)
    *,
    kv_x: jax.Array | None = None,  # cross-attention source
    kv_positions: jax.Array | None = None,
    causal: bool = True,
    window: int | None = None,
    use_rope: bool = True,
    cache: dict | None = None,  # {'k','v': (B, L, KV, Dh), 'pos': ()} decode
) -> tuple[jax.Array, dict | None]:
    dt = cfg.compute_dtype
    B, S, _ = x.shape
    kv_src = x if kv_x is None else kv_x
    kv_pos = positions if kv_positions is None else kv_positions

    q = jnp.einsum("bsd,dhq->bshq", x.astype(dt), p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    if cache is not None and "k" in cache and kv_x is not None:
        # cross-attention decode: reuse precomputed enc K/V
        k, v = cache["k"], cache["v"]
    else:
        k = jnp.einsum("bsd,dhq->bshq", kv_src.astype(dt), p["wk"].astype(dt))
        v = jnp.einsum("bsd,dhq->bshq", kv_src.astype(dt), p["wv"].astype(dt))
        if "bk" in p:
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
        if use_rope:
            k = rope(k, kv_pos, cfg.rope_theta)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)

    new_cache = None
    # a cache whose position is the STATIC 0 is being filled from empty
    # (transformer.prefill): the new tokens attend to themselves only, so
    # they take the full-sequence path, kernel routing included
    fresh = (
        cache is not None and kv_x is None
        and isinstance(cache["pos"], int) and cache["pos"] == 0
    )
    if cache is not None and kv_x is None:
        # self-attention decode: insert current K/V at position `pos`
        pos = cache["pos"]  # scalar int
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(dt), pos, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(dt), pos, 1)
        new_cache = {"k": ck, "v": cv, "pos": pos + S}
        if not fresh:
            k, v = ck, cv
            kv_pos = jnp.broadcast_to(
                jnp.arange(ck.shape[1])[None], (B, ck.shape[1])
            )
    elif cache is not None:
        new_cache = cache

    use_kernel = (
        cfg.attention_kernel != "jnp" and (cache is None or fresh)
        and kv_x is None and not cfg.blockwise_attention
    )
    if not use_kernel:
        # GQA grouping
        G = cfg.n_heads // cfg.n_kv_heads
        if cfg.shard_q_heads and G > 1:
            # expand K/V per group so the attention einsum is sharded by Q
            # heads ('heads' -> model) instead of replicated when
            # kv_heads < |model| (per-device KV bytes unchanged: the
            # expansion is sharded away)
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
            k = shard_act(k, "batch", "seq", "heads", None)
            v = shard_act(v, "batch", "seq", "heads", None)
            qg = q.reshape(B, q.shape[1], cfg.n_heads, 1, cfg.head_dim)
            qg = shard_act(qg, "batch", "seq", "heads", None, None)
        else:
            k = shard_act(k, "batch", "seq", "kv_heads", None)
            v = shard_act(v, "batch", "seq", "kv_heads", None)
            qg = q.reshape(B, q.shape[1], cfg.n_kv_heads, G, cfg.head_dim)
            qg = shard_act(qg, "batch", "seq", "kv_heads", None, None)
        scale = cfg.head_dim ** -0.5

        q_pos_row = positions[0] if cache is None else (
            jnp.arange(S) + (cache["pos"] if kv_x is None else 0)
        )
        k_pos_row = kv_pos[0]

    if use_kernel:
        # Registry-dispatched flash attention (kernels/ops.py): heads-major
        # (B, H, S, D) layout, GQA via the kernel's head->kv_head index map,
        # custom_vjp backward. Full-sequence self-attention only (positions
        # here are arange(S) for every no-cache caller).
        from repro.kernels import ops as KO

        o = KO.flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), causal=causal, window=window,
            softcap=cfg.attn_softcap, use_pallas=cfg.attention_kernel,
        )
        out = jnp.swapaxes(o, 1, 2).astype(dt)  # (B, S, H, Dh)
    elif cfg.blockwise_attention:
        out = _blockwise_attention(
            qg * scale, k, v, q_pos_row, k_pos_row,
            causal=causal and kv_x is None, window=window,
            softcap_v=cfg.attn_softcap,
            block_k=cfg.attention_block_k,
            valid_len=(cache["pos"] + S)
            if (cache is not None and kv_x is None) else None,
        ).astype(dt)
        out = out.reshape(B, q.shape[1], cfg.n_heads, cfg.head_dim)
    else:
        scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) * scale
        scores = shard_act(scores, "batch", "kv_heads", None, None, None)
        scores = softcap(scores.astype(jnp.float32), cfg.attn_softcap)
        mask = _attn_scores_mask(
            q_pos_row, k_pos_row, window, causal and kv_x is None,
        )
        if cache is not None and kv_x is None:
            # only cache slots already written are valid
            mask &= (jnp.arange(k.shape[1]) < cache["pos"] + S)[None, :]
        scores = jnp.where(mask, scores, -1e30)

        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        probs = shard_act(probs, "batch", "kv_heads", None, None, None)
        out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
        out = out.reshape(B, q.shape[1], cfg.n_heads, cfg.head_dim)
    y = jnp.einsum("bshq,hqd->bsd", out, p["wo"].astype(dt))
    return shard_act(y, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# paged attention (serving decode against a shared KV block pool)
# ---------------------------------------------------------------------------

def paged_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, 1, d) — one new token per sequence slot
    positions: jax.Array,  # (B, 1) — rope position of the new token
    pool_k: jax.Array,  # (n_blocks, block_size, KV, Dh) shared page pool
    pool_v: jax.Array,
    table: jax.Array,  # (B, n_pages) int32 — pool page ids per slot
    lengths: jax.Array,  # (B,) int32 — tokens already cached per slot
    *,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token self-attention against a paged KV pool (serving decode).

    The new token's K/V are written in place at page ``table[b, len//bs]``
    offset ``len % bs``, then attention runs over ``lengths + 1`` tokens
    through the registry's decode_attention kernel
    (``cfg.decode_kernel`` picks the backend; 'jnp' degrades to the
    jnp-gather oracle). Inactive slots (length 0, all-null table rows)
    write to the reserved null page and read back zeros — padding lanes
    cost one masked page, not a recompile.

    Returns (y (B, 1, d), new_pool_k, new_pool_v).
    """
    from repro.kernels import ops as KO

    dt = cfg.compute_dtype
    B = x.shape[0]
    xc = x.astype(dt)
    q = jnp.einsum("bsd,dhq->bshq", xc, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhq->bshq", xc, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhq->bshq", xc, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    block_size = pool_k.shape[1]
    page = table[jnp.arange(B), lengths // block_size]  # (B,)
    off = lengths % block_size
    pool_k = pool_k.at[page, off].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[page, off].set(v[:, 0].astype(pool_v.dtype))

    mode = "off" if cfg.decode_kernel == "jnp" else cfg.decode_kernel
    o = KO.decode_attention(
        q[:, 0], pool_k, pool_v, table, lengths + 1,
        window=window, softcap=cfg.attn_softcap, use_pallas=mode,
    )  # (B, Hq, Dh)
    y = jnp.einsum("bhq,hqd->bd", o.astype(dt), p["wo"].astype(dt))
    return y[:, None], pool_k, pool_v


# ---------------------------------------------------------------------------
# multi-head latent attention (MLA, DeepSeek-V3 sec. 2.1)
# ---------------------------------------------------------------------------

def mla_defs(cfg: ModelConfig) -> dict:
    d, h, ql, kl = cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "wq_a": ParamDef((d, ql), ("embed", None), scale=d**-0.5),
        "q_norm": rms_norm_def(ql),
        "wq_b": ParamDef((ql, h, cfg.qk_head_dim), (None, "heads", None),
                         scale=ql**-0.5),
        "wkv_a": ParamDef((d, cfg.latent_dim), ("embed", None),
                          scale=d**-0.5),
        "kv_norm": rms_norm_def(kl),
        "wkv_b": ParamDef((kl, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
                          (None, "heads", None), scale=kl**-0.5),
        "wo": ParamDef((h, cfg.v_head_dim, d), ("heads", None, "embed"),
                       scale=(h * cfg.v_head_dim) ** -0.5),
    }


def mla_project(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    """x (B, S, d) -> q_nope (B, S, H, nope), rotated q_pe (B, S, H, rope)
    and the latent row (B, S, kv_lora_rank + rope) a cache holds:
    RMSNorm(a) | rotated k_pe, from [a | k_pe] = x W_DKV."""
    dt = cfg.compute_dtype
    nope, kl = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    inv = rope_inv_freq(cfg, cfg.qk_rope_head_dim)
    xc = x.astype(dt)
    c_q = rms_norm(xc @ p["wq_a"].astype(dt), p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", c_q, p["wq_b"].astype(dt))
    q = shard_act(q, "batch", "seq", "heads", None)
    q_pe = rope(q[..., nope:], positions, cfg.rope_theta, inv)
    kv = xc @ p["wkv_a"].astype(dt)
    c_kv = rms_norm(kv[..., :kl], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, kl:], positions, cfg.rope_theta, inv)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], -1)


def mla_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (B, S)
    *,
    cache: dict | None = None,  # {'latent': (B, L, R), 'pos': ()} decode
) -> tuple[jax.Array, dict | None]:
    """Causal latent attention over a whole sequence (train, prefill and the
    contiguous decode cache), in the expanded form: each head's keys and
    values are rebuilt from the latent rows, k = [c_kv W_UK | k_pe]."""
    dt = cfg.compute_dtype
    B, S, _ = x.shape
    nope, kl = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, lat = mla_project(cfg, p, x, positions)
    fresh = cache is not None and isinstance(cache["pos"], int) \
        and cache["pos"] == 0
    new_cache, keys, valid = None, lat, None
    if cache is not None:
        pos = cache["pos"]
        full = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat.astype(cache["latent"].dtype), pos, 1)
        new_cache = {"latent": full, "pos": pos + S}
        if not fresh:
            keys, valid = full.astype(dt), pos + S
    kv = jnp.einsum("btc,chk->bthk", keys[..., :kl], p["wkv_b"].astype(dt))
    kv = shard_act(kv, "batch", "seq", "heads", None)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = keys[..., kl:]
    scale = mla_scale(cfg)
    if cfg.attention_kernel != "jnp" and valid is None:
        # the flash kernel takes one head width and scales by its
        # 1/sqrt: q carries the remaining m^2, v is zero-padded to the
        # query/key width and sliced back
        from repro.kernels import ops as KO

        H, dq, dv = cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim
        q = jnp.concatenate([q_nope, q_pe], -1) * jnp.asarray(
            scale * dq**0.5, dt)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, dq - nope))],
            -1)
        vp = jnp.pad(v, ((0, 0),) * 3 + ((0, dq - dv),))
        o = KO.flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(vp, 1, 2), causal=True,
            use_pallas=cfg.attention_kernel,
        )
        out = jnp.swapaxes(o, 1, 2)[..., :dv].astype(dt)
    else:
        scores = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
                  + jnp.einsum("bshk,btk->bhst", q_pe, k_pe))
        scores = scores.astype(jnp.float32) * scale
        q_pos = positions[0]
        k_pos = jnp.arange(keys.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if valid is not None:
            mask &= (k_pos < valid)[None, :]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), -1).astype(dt)
        out = jnp.einsum("bhst,bthv->bshv", probs, v)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(dt))
    return shard_act(y, "batch", "seq", "embed"), new_cache


def mla_paged_attention(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, 1, d) one new token per slot
    positions: jax.Array,  # (B, 1)
    pool: jax.Array,  # (n_layers, n_blocks, block_size, >= R) latent pool
    layer,  # this layer's index into the pool
    table: jax.Array,  # (B, n_pages)
    lengths: jax.Array,  # (B,) tokens already cached per slot
) -> tuple[jax.Array, jax.Array]:
    """Single-token latent attention against the paged latent pool, in the
    absorbed form: W_UK folds into the query and W_UV into the output, so
    every head attends to the same 576-wide latent row (keys) and its
    first kv_lora_rank columns (values), read once per page by the
    registry's mla_decode_attention kernel. Exact algebra, not an
    approximation. The new token's row is written in place at page
    ``table[b, len // bs]``, offset ``len % bs``. Returns (y, pool)."""
    from repro.kernels import ops as KO

    dt = cfg.compute_dtype
    B = x.shape[0]
    nope, kl = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, lat = mla_project(cfg, p, x, positions)
    bs, pad = pool.shape[2], pool.shape[3] - cfg.latent_dim
    page = table[jnp.arange(B), lengths // bs]
    pool = pool.at[layer, page, lengths % bs].set(
        jnp.pad(lat[:, 0], ((0, 0), (0, pad))).astype(pool.dtype))
    wkv_b = p["wkv_b"].astype(dt)
    q_lat = jnp.einsum("bhk,chk->bhc", q_nope[:, 0], wkv_b[..., :nope])
    q = jnp.pad(jnp.concatenate([q_lat, q_pe[:, 0]], -1),
                ((0, 0), (0, 0), (0, pad)))  # the pool's zero columns
    mode = "off" if cfg.decode_kernel == "jnp" else cfg.decode_kernel
    o = KO.mla_decode_attention(
        q, pool, jnp.asarray(layer, jnp.int32), table, lengths + 1,
        scale=mla_scale(cfg), value_dim=kl, use_pallas=mode,
    )  # (B, H, kv_lora_rank)
    o = jnp.einsum("bhc,chv->bhv", o.astype(dt), wkv_b[..., nope:])
    y = jnp.einsum("bhv,hvd->bd", o, p["wo"].astype(dt))
    return y[:, None], pool


# ---------------------------------------------------------------------------
# blockwise attention (online softmax over KV blocks — the jnp twin of
# kernels/flash_attention.py). No (S_q x S_k) buffer ever materializes:
# the working set is one KV block per scan step. This is the §Perf
# optimization for the memory-dominated train/prefill cells; enable with
# ModelConfig.blockwise_attention.
# ---------------------------------------------------------------------------

def _blockwise_attention(
    qg: jax.Array,  # (B, Sq, KV, G, Dh) — pre-scaled queries
    k: jax.Array,  # (B, Sk, KV, Dh)
    v: jax.Array,  # (B, Sk, KV, Dh)
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,)
    *,
    causal: bool,
    window: int | None,
    softcap_v: float | None,
    block_k: int,
    valid_len: jax.Array | None = None,  # decode: cache fill level
) -> jax.Array:
    B, Sq, KV, G, Dh = qg.shape
    Sk = k.shape[1]
    block_k = min(block_k, Sk)
    pad = (-Sk) % block_k
    if pad:
        zp = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        k, v = zp(k), zp(v)
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-10**9)
    nb = k.shape[1] // block_k

    qf = qg.astype(jnp.float32)
    kb = k.reshape(B, nb, block_k, KV, Dh)
    vb = v.reshape(B, nb, block_k, KV, Dh)
    pb = k_pos.reshape(nb, block_k)

    def body(carry, inp):
        acc, m_prev, l_prev = carry
        k_t, v_t, p_t = inp
        s = jnp.einsum("bskgh,btkh->bkgst", qf, k_t.astype(jnp.float32))
        if softcap_v is not None:
            s = softcap_v * jnp.tanh(s / softcap_v)
        mask = jnp.ones((Sq, block_k), bool)
        if causal:
            mask &= q_pos[:, None] >= p_t[None, :]
        if window is not None:
            mask &= q_pos[:, None] - p_t[None, :] < window
        mask &= (p_t >= 0)[None, :]
        if valid_len is not None:
            mask &= (p_t < valid_len)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p, v_t.astype(jnp.float32)
        )
        return (acc, m_cur, l_cur), None

    acc0 = jnp.zeros((B, KV, G, Sq, Dh), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), pb),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, KV, G, Sq, Dh)
    return jnp.moveaxis(out, 3, 1)  # (B, Sq, KV, G, Dh)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wg": ParamDef((d, f), ("embed", "mlp")),
        "wu": ParamDef((d, f), ("embed", "mlp")),
        "wd": ParamDef((f, d), ("mlp", "embed")),
    }


def mlp(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    dt = cfg.compute_dtype
    h = jax.nn.silu(x @ p["wg"].astype(dt)) * (x @ p["wu"].astype(dt))
    h = shard_act(h, "batch", "seq", "mlp")
    return shard_act(h @ p["wd"].astype(dt), "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based top-k dispatch, EP over 'model')
# ---------------------------------------------------------------------------

def moe_defs(cfg: ModelConfig) -> dict:
    # EP: experts over 'model', intra-expert matrices FSDP over 'data'.
    # (expert AND mlp cannot both map to 'model' in one spec.)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=0.02),
        "wg": ParamDef((e, d, f), ("expert", "embed", None)),
        "wu": ParamDef((e, d, f), ("expert", "embed", None)),
        "wd": ParamDef((e, f, d), ("expert", None, "embed")),
    }
    if cfg.router_scoring == "sigmoid":
        defs["router_bias"] = ParamDef((e,), (None,), scale=0.1)
    if cfg.n_experts_held:
        for k in ("wg", "wu", "wd"):
            d_ = defs[k]
            defs[k] = ParamDef((cfg.n_experts_held, *d_.shape[1:]), d_.axes)
    if cfg.shared_expert_d_ff:
        defs["shared"] = mlp_defs(cfg, cfg.shared_expert_d_ff)
    return defs


def route(cfg: ModelConfig, p: dict, xt: jax.Array):
    """xt (T, d) -> (weights (T, K) f32, experts (T, K) int32) over all
    ``n_experts``.

    'softmax': the top-k of the softmax scores, renormalized. 'sigmoid'
    (noaux_tc): s = sigmoid(xt W_r) in float32, the experts chosen by
    top-k of s + b (the selection bias), weighted by s_top / sum(s_top).
    Either is then scaled by ``routed_scaling``."""
    K = cfg.experts_per_token
    if cfg.router_scoring == "sigmoid":
        logits = jnp.dot(xt.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), K)
        top_p = jnp.take_along_axis(s, top_i, -1)
    else:
        logits = (xt @ p["router"].astype(cfg.compute_dtype)).astype(
            jnp.float32)
        top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    if cfg.routed_scaling != 1.0:
        top_p = top_p * cfg.routed_scaling
    return top_p, top_i


def moe(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.moe_groups > 0:
        return _moe_grouped_einsum(cfg, p, x)
    return _moe_scatter(cfg, p, x)


def _moe_grouped_einsum(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """GShard-style dispatch: tokens split into G groups (= data shards);
    per-group one-hot dispatch/combine einsums keep every contraction local
    to the (data, model) device pair — no dispatch collectives.

    buf[g,e,c,:] = sum_t dispatch[g,t,e,c] * x[g,t,:]
    y[g,t,:]     = sum_{e,c} combine[g,t,e,c] * out[g,e,c,:]
    """
    dt = cfg.compute_dtype
    B, S, d = x.shape
    T = B * S
    G = math_gcd_groups(cfg.moe_groups, T)
    Tg = T // G
    E, K = cfg.n_experts, cfg.experts_per_token
    C = max(1, int(Tg * K / E * cfg.capacity_factor))
    C = -(-C // 8) * 8  # small alignment

    xt = x.reshape(G, Tg, d)
    xt = shard_act(xt, "batch", None, "embed")
    top_p, top_i = route(cfg, p, xt.reshape(T, d))
    top_p, top_i = top_p.reshape(G, Tg, K), top_i.reshape(G, Tg, K)

    oh_e = jax.nn.one_hot(top_i, E, dtype=jnp.int32)  # (G, Tg, K, E)
    # position of (token, slot) within its expert, PER GROUP
    pos = jnp.cumsum(oh_e.reshape(G, Tg * K, E), axis=1).reshape(
        G, Tg, K, E
    ) * oh_e - 1  # -1 where not routed
    pos_k = pos.max(-1)  # (G, Tg, K)
    keep = (pos_k >= 0) & (pos_k < C)
    oh_c = jax.nn.one_hot(jnp.where(keep, pos_k, -1), C, dtype=dt)  # (G,Tg,K,C)
    w_k = jnp.where(keep, top_p, 0.0).astype(dt)

    # (G, Tg, E, C) dispatch/combine one-hots (sum over K slots)
    dispatch = jnp.einsum("gtke,gtkc->gtec", oh_e.astype(dt), oh_c)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", oh_e.astype(dt), oh_c, w_k)
    dispatch = shard_act(dispatch, "batch", None, "expert", None)
    combine = shard_act(combine, "batch", None, "expert", None)

    buf = jnp.einsum("gtec,gtd->gecd", dispatch, xt)  # (G, E, C, d)
    buf = shard_act(buf, "batch", "expert", None, None)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["wg"].astype(dt)))
    h = h * jnp.einsum("gecd,edf->gecf", buf, p["wu"].astype(dt))
    h = shard_act(h, "batch", "expert", None, None)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wd"].astype(dt))
    out_buf = shard_act(out_buf, "batch", "expert", None, None)
    y = jnp.einsum("gtec,gecd->gtd", combine, out_buf)

    if cfg.shared_expert_d_ff:
        y = y + mlp(cfg, p["shared"], xt.reshape(B, S, d)).reshape(G, Tg, d)
    return shard_act(y.reshape(B, S, d), "batch", "seq", "embed")


def math_gcd_groups(g: int, t: int) -> int:
    while t % g:
        g -= 1
    return max(1, g)


def _moe_scatter(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """x: (B, S, d) -> (B, S, d). Deterministic capacity-based dispatch."""
    dt = cfg.compute_dtype
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.experts_per_token
    C = max(1, int(T * K / E * cfg.capacity_factor))
    C = -(-C // 128) * 128 if C > 128 else C  # 128-align: MXU + shardable

    xt = x.reshape(T, d)
    top_p, top_i = route(cfg, p, xt)  # (T, K)

    flat_e = top_i.reshape(-1)  # (T*K,)
    flat_w = top_p.reshape(-1).astype(dt)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot  # 1-based positions
    pos = jnp.sum(pos_in_e, axis=-1) - 1  # (T*K,)
    keep = pos < C
    safe_pos = jnp.where(keep, pos, 0)

    # scatter tokens -> (E, C, d) buffers
    tok_rep = jnp.repeat(xt.astype(dt), K, axis=0)  # (T*K, d)
    buf = jnp.zeros((E, C, d), dt)
    buf = buf.at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], tok_rep, 0.0)
    )
    buf = shard_act(buf, "expert", "capacity", None)

    # expert computation (einsum over stacked experts = EP over 'model')
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(dt))
    h = shard_act(h, "expert", "capacity", None)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(dt))
    out_buf = shard_act(out_buf, "expert", "capacity", None)

    # gather back + combine
    gathered = out_buf[flat_e, safe_pos]  # (T*K, d)
    gathered = jnp.where(keep[:, None], gathered, 0.0) * flat_w[:, None]
    y = gathered.reshape(T, K, d).sum(1)

    if cfg.shared_expert_d_ff:
        y = y + mlp(cfg, p["shared"], xt.reshape(B, S, d)).reshape(T, d)
    return shard_act(y.reshape(B, S, d), "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# the held experts' share of an MoE layer (dropless, one grouped matmul)
# ---------------------------------------------------------------------------

def _row_tile(T: int, K: int, E: int) -> int:
    """Rows per tile of the grouped matmul: about twice the rows an expert
    expects under even routing, 16 to 128."""
    tm = 16
    while tm < 128 and tm < 2 * T * K / E:
        tm *= 2
    return tm


def held_experts(cfg: ModelConfig, p: dict, experts: dict, layer,
                 x: jax.Array, valid: jax.Array | None = None):
    """This chip's part of an MoE layer: x (B, S, d) -> (y, routes).

    The router spans all ``n_experts``; of each token's top-k routes, those
    to the held experts [expert_offset, + n_experts_held) are computed and
    none is dropped: the routed rows are laid out expert by expert, each
    expert's rows padded to whole tiles of ``_row_tile`` rows, in a buffer
    sized for every token routing to min(k, held) held experts, and run as
    one grouped matmul (the registry's expert_matmul) per projection.
    ``experts`` holds every MoE layer's held-expert weights stacked
    (n_moe_layers, held, ...) and ``layer`` picks this layer's, so the
    kernel reads them in place. Rows where ``valid`` (B, S) is False
    (padding, empty slots) are routed nowhere. The shared expert adds to
    every token. ``routes`` (held,) int32 counts the routes to each held
    expert. The grouped matmul runs in the default kernel mode
    (``config.kernel_mode``: Pallas on a TPU, REPRO_KERNEL_MODE overrides)."""
    from repro.kernels import ops as KO

    dt = cfg.compute_dtype
    B, S, d = x.shape
    T, K, E = B * S, cfg.experts_per_token, cfg.n_experts_held
    xt = x.reshape(T, d).astype(dt)
    w, idx = route(cfg, p, xt)
    local = idx - cfg.expert_offset
    sel = (local >= 0) & (local < E)
    if valid is not None:
        sel &= valid.reshape(T, 1)
    flat = jnp.where(sel, local, E).reshape(-1)  # E: routed nowhere here
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)  # (T*K, E)
    i32 = jnp.int32  # sums of int32 widen under x64; the counter stays
    routes = onehot.sum(0, dtype=i32)
    rank = jnp.sum((jnp.cumsum(onehot, 0, dtype=i32) - 1) * onehot, -1,
                   dtype=i32)
    tm = _row_tile(T, K, cfg.n_experts)
    tiles = (routes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles, dtype=i32)
    n_tiles = -(-T * min(K, E) // tm) + E  # no batch can need more
    M = n_tiles * tm
    first = (tile_end - tiles) * tm
    pos = jnp.where(sel.reshape(-1), first[jnp.minimum(flat, E - 1)] + rank,
                    M)
    row_tok = jnp.full((M,), T, jnp.int32).at[pos].set(
        jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
    rows = jnp.concatenate([xt, jnp.zeros((1, d), dt)])[row_tok]
    group = layer * E + jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"), E - 1)
    n_active = tile_end[-1]
    flat_w = lambda a: a.reshape(-1, *a.shape[2:]).astype(dt)

    def mm(a, name):
        return KO.expert_matmul(a, flat_w(experts[name]), group, n_active,
                                tm=tm, use_pallas=kernel_mode())

    h = (jax.nn.silu(mm(rows, "wg").astype(jnp.float32))
         * mm(rows, "wu").astype(jnp.float32)).astype(dt)
    out = mm(h, "wd")  # (M, d); rows of tiles past n_active are undefined
    got = out[jnp.minimum(pos, M - 1)].reshape(T, K, d).astype(jnp.float32)
    y = jnp.sum(jnp.where(sel[..., None], got * w[..., None], 0.0), 1)
    y = y.astype(dt).reshape(B, S, d)
    if cfg.shared_expert_d_ff:
        y = y + mlp(cfg, p["shared"], x.astype(dt))
    return shard_act(y, "batch", "seq", "embed"), routes
