"""Core transformer layers: norm, RoPE, GQA attention, (gated) MLP, MoE.

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


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
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


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh) rotated pairwise; positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


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
    if cfg.shared_expert_d_ff:
        defs["shared"] = mlp_defs(cfg, cfg.shared_expert_d_ff)
    return defs


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
    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)  # (G, Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)  # (G, Tg, K)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

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
    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, K)  # (T, K)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)  # renormalize

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
