"""Model assembly for every assigned architecture family.

Families:
  dense / moe / ssm : homogeneous stacks -> jax.lax.scan over stacked layer
                      params (compile-time O(1) in depth; required for the
                      126-layer / 1T-param dry-runs). gemma2's alternating
                      local/global attention scans over layer PAIRS — the
                      stacked params reshape (n, ...) -> (n//2, 2, ...) and
                      the body applies a local then a global block, so both
                      window sizes are STATIC and kernel-eligible.
  hybrid (zamba2)   : python-unrolled Mamba2 stack with a SHARED attention
                      block (one set of weights, applied every
                      cfg.hybrid_period layers).
  encdec (whisper)  : bidirectional encoder over stubbed frame embeddings +
                      causal decoder with cross-attention.

Public API:
  model_defs(cfg)                      -> ParamDef tree
  forward(cfg, params, batch)          -> logits            (train / scoring)
  cache_defs(cfg, batch, max_len)      -> decode-cache ShapeDtypeStructs
  prefill(cfg, params, tok, cache)     -> (cache, logits at valid_len - 1)
  decode_step(cfg, params, tok, cache) -> (cache, logits)

Latent attention (cfg.kv_lora_rank > 0; the DeepSeek-V3 block of kimi-k2)
builds its own stack: ``n_dense_layers`` dense layers, then the MoE layers,
each part a scan, MLA in every layer. Its caches hold one latent row per
token per layer ('latent'); where the chip holds a share of the experts the
MoE layers are the dropless held-expert layer, and prefill's cache and the
paged pools also return the call's route counts ('routes', (n_moe_layers,
held) int32).

Serving API (the paged-pool twin, driven by src/repro/serve/):
  paged_cache_defs(cfg, max_batch, n_blocks, block_size, n_pages)
  decode_step_paged(cfg, params, tok, pools, table, lengths)
                                       -> (pools, logits)
K/V lives in a shared page pool with per-slot block tables instead of one
contiguous (B, max_len) buffer; attention gathers through the table via
the registry's decode_attention kernel (cfg.decode_kernel).

Kernel routing: `cfg.attention_kernel` / `cfg.ssm_kernel` swap the full-seq
attention and SSD within-chunk compute for the kernels/ops.py registry's
custom_vjp Pallas kernels — forward AND backward — so `jax.grad` through
`forward` (train/step.py local_grads) takes the blocked gradient kernels.
The remat policy composes with this unchanged: the custom_vjp boundary is
what gets rematerialized, and its residual contract (O(S), never O(S^2))
is exactly what the scan carries between layers.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.models import layers as L
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# param defs
# ---------------------------------------------------------------------------

def _stack(defs: dict, n: int) -> dict:
    """Prepend a scanned 'layers' axis to every ParamDef leaf."""
    return jax.tree_util.tree_map(
        lambda d: ParamDef((n, *d.shape), ("layers", *d.axes), d.init, d.scale),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def _block_defs(cfg: ModelConfig) -> dict:
    blk = {
        "ln1": L.rms_norm_def(cfg.d_model),
        "attn": L.attention_defs(cfg),
        "ln2": L.rms_norm_def(cfg.d_model),
    }
    blk["moe" if cfg.family == "moe" else "mlp"] = (
        L.moe_defs(cfg) if cfg.family == "moe" else L.mlp_defs(cfg)
    )
    return blk


def _mla_defs(cfg: ModelConfig) -> dict:
    """An MLA stack (DeepSeek-V3 block): ``n_dense_layers`` leading dense
    layers ('dense_blocks') then the MoE layers ('blocks')."""
    if cfg.family != "moe":
        raise ValueError("latent attention is served in the moe family only")
    layer = lambda ffn, defs: {
        "ln1": L.rms_norm_def(cfg.d_model), "attn": L.mla_defs(cfg),
        "ln2": L.rms_norm_def(cfg.d_model), ffn: defs,
    }
    defs = {"blocks": _stack(layer("moe", L.moe_defs(cfg)), cfg.n_moe_layers)}
    if cfg.n_dense_layers:
        defs["dense_blocks"] = _stack(layer("mlp", L.mlp_defs(cfg)),
                                      cfg.n_dense_layers)
    return defs


def _ssm_block_defs(cfg: ModelConfig) -> dict:
    return {"ln": L.rms_norm_def(cfg.d_model), "ssm": S.ssm_defs(cfg)}


def model_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    defs: dict[str, Any] = {
        "embed": ParamDef((cfg.vocab_size, d), ("vocab", "embed"), scale=1.0),
        "final_norm": L.rms_norm_def(d),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, cfg.vocab_size), ("embed", "vocab"))

    if cfg.is_mla:
        defs.update(_mla_defs(cfg))
    elif cfg.family in ("dense", "moe"):
        defs["blocks"] = _stack(_block_defs(cfg), cfg.n_layers)
    elif cfg.family == "ssm":
        defs["blocks"] = _stack(_ssm_block_defs(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        defs["blocks"] = _stack(_ssm_block_defs(cfg), cfg.n_layers)
        shared = _block_defs(cfg)
        defs["shared_attn"] = shared  # one attention+mlp block, reused
    elif cfg.family == "encdec":
        enc_blk = {
            "ln1": L.rms_norm_def(d),
            "attn": L.attention_defs(cfg),
            "ln2": L.rms_norm_def(d),
            "mlp": L.mlp_defs(cfg),
        }
        dec_blk = {
            "ln1": L.rms_norm_def(d),
            "attn": L.attention_defs(cfg),
            "ln_x": L.rms_norm_def(d),
            "xattn": L.attention_defs(cfg, cross=True),
            "ln2": L.rms_norm_def(d),
            "mlp": L.mlp_defs(cfg),
        }
        defs["encoder"] = _stack(enc_blk, cfg.n_encoder_layers)
        defs["decoder"] = _stack(dec_blk, cfg.n_layers)
        defs["enc_final_norm"] = L.rms_norm_def(d)
    else:
        raise ValueError(cfg.family)
    return defs


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _layer_windows(cfg: ModelConfig) -> tuple[int | None, ...]:
    """STATIC per-scan-step window schedule.

    Uniform schedules scan one layer per step with cfg.sliding_window.
    gemma2-style alternation (cfg.local_global) scans layer PAIRS: each
    step applies a local (sliding_window) then a global (None) block, so
    both windows fold at trace time — no traced per-layer scalar, and the
    kernel routing (flash for train/prefill, decode_attention for serving)
    stays eligible."""
    if cfg.local_global and cfg.sliding_window:
        assert cfg.n_layers % 2 == 0, "local_global needs an even stack"
        return (cfg.sliding_window, None)
    return (cfg.sliding_window,)


def _embed(cfg: ModelConfig, params, tokens=None, inputs_embeds=None):
    if inputs_embeds is not None:
        return inputs_embeds.astype(cfg.compute_dtype)
    x = params["embed"][tokens]  # (B, S, d)
    return (x * jnp.asarray(cfg.d_model**0.5, x.dtype)).astype(cfg.compute_dtype)


def _unembed(cfg: ModelConfig, params, x):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(cfg.compute_dtype)
    logits = (x @ head).astype(jnp.float32)
    logits = L.softcap(logits, cfg.final_softcap)
    return L.shard_act(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# dense / moe / ssm stacks (scanned)
# ---------------------------------------------------------------------------

def _dense_block(cfg: ModelConfig, p, x, positions, window, cache):
    # `window` is always STATIC (None / python int): the mask folds at
    # trace time and kernel routing stays eligible. gemma2's alternation
    # is expressed by the pair scan in _scan_stack, never a traced scalar.
    h, new_cache = L.multi_head_attention(
        cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
        causal=True, window=window, cache=cache,
    )
    x = x + h
    inner = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        x = x + L.moe(cfg, p["moe"], inner)
    else:
        x = x + L.mlp(cfg, p["mlp"], inner)
    return x, new_cache


def _ssm_layer(cfg: ModelConfig, p, x, cache, valid_len=None):
    h, new_cache = S.ssm_block(
        cfg, p["ssm"], L.rms_norm(x, p["ln"], cfg.norm_eps), cache=cache,
        valid_len=valid_len,
    )
    return x + h, new_cache


def _substack(t, m: int):
    """Reshape a stacked leaf (n, ...) -> (n/m, m, ...) for the pair scan."""
    return t.reshape(t.shape[0] // m, m, *t.shape[1:])


def _unsubstack(t, m: int):
    """Inverse of _substack on a scan output: (n/m, m, ...) -> (n, ...)."""
    return t.reshape(t.shape[0] * m, *t.shape[2:])


def _scan_stack(cfg, blocks, x, positions, caches):
    """Scan over stacked layer params (+ optional cache).

    Uniform schedules scan one layer per step (STATIC cfg.sliding_window:
    the mask folds at trace time; kernel routing eligible). gemma2-style
    local/global alternation scans layer PAIRS instead — stacked leaves
    reshape (n, ...) -> (n//2, 2, ...) and the body applies the local then
    the global block, so both windows are static too (the carried-over
    traced-window thread is gone). caches['pos'] is a scalar shared by all
    layers, so it rides in the closure; only stacked k/v tensors scan.
    """
    has_cache = caches is not None
    pos = caches["pos"] if has_cache else None
    windows = _layer_windows(cfg)
    m = len(windows)
    blocks = jax.tree_util.tree_map(lambda t: _substack(t, m), blocks)

    def body(carry, xs):
        x = carry
        if has_cache:
            p, k, v = xs
            nk, nv = [], []
            for j, w in enumerate(windows):
                pj = jax.tree_util.tree_map(lambda a: a[j], p)
                x, c = _dense_block(
                    cfg, pj, x, positions, w,
                    {"k": k[j], "v": v[j], "pos": pos},
                )
                nk.append(c["k"])
                nv.append(c["v"])
            return x, (jnp.stack(nk), jnp.stack(nv))
        (p,) = xs
        for j, w in enumerate(windows):
            pj = jax.tree_util.tree_map(lambda a: a[j], p)
            x, _ = _dense_block(cfg, pj, x, positions, w, None)
        return x, None

    body = _remat(cfg, body)
    if has_cache:
        xs = (blocks, _substack(caches["k"], m), _substack(caches["v"], m))
        x, (nk, nv) = jax.lax.scan(body, x, xs)
        return x, {"k": _unsubstack(nk, m), "v": _unsubstack(nv, m),
                   "pos": pos + positions.shape[1]}
    x, _ = jax.lax.scan(body, x, (blocks,))
    return x, None


def _scan_ssm_stack(cfg, blocks, x, caches, valid_len=None):
    has_cache = caches is not None

    def body(carry, xs):
        x = carry
        if has_cache:
            p, c = xs
            x, new_c = _ssm_layer(cfg, p, x, c, valid_len)
            return x, new_c
        (p,) = xs
        x, _ = _ssm_layer(cfg, p, x, None)
        return x, None

    body = _remat(cfg, body)
    xs = (blocks, caches) if has_cache else (blocks,)
    x, new_caches = jax.lax.scan(body, x, xs)
    return x, new_caches


def _split_experts(cfg, blocks):
    """(the per-layer params a scan slices, the held experts' weights).

    Held experts stay stacked over the MoE layers, (n_moe, held, ...), and
    are read in place by the grouped matmul with the layer's index: a
    scan's per-layer slice of them would be a copy every call."""
    if not cfg.n_experts_held or "moe" not in blocks:
        return blocks, None
    moe = dict(blocks["moe"])
    experts = {k: moe.pop(k) for k in ("wg", "wu", "wd")}
    return {**blocks, "moe": moe}, experts


def _mla_ffn(cfg, p, x, experts, layer, valid):
    """The feed-forward half of an MLA-stack layer -> (x, routes or None)."""
    inner = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "mlp" in p:
        return x + L.mlp(cfg, p["mlp"], inner), None
    if experts is None:
        return x + L.moe(cfg, p["moe"], inner), None
    y, routes = L.held_experts(cfg, p["moe"], experts, layer, inner, valid)
    return x + y, routes


def _mla_parts(cfg, params):
    """(blocks, index of their first layer) of each part of the stack."""
    parts = [(params["blocks"], cfg.n_dense_layers)]
    if cfg.n_dense_layers:
        parts.insert(0, (params["dense_blocks"], 0))
    return parts


def _mla_stack(cfg, params, x, positions, cache, valid):
    """Full-sequence MLA stack (train, prefill, the contiguous decode
    cache): the dense layers, then the MoE layers, each a scan. ``cache``
    is {'latent': (n_layers, B, L, R), 'pos'} or None; ``valid`` (B, S)
    marks the tokens the held experts route (None: all). Returns (x, new
    cache), the cache with this call's 'routes' (n_moe, held) where this
    chip holds a share of the experts."""
    lats, routes = [], None
    for blocks, first in _mla_parts(cfg, params):
        blocks, experts = _split_experts(cfg, blocks)
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        lat = None if cache is None else cache["latent"][first:first + n]

        def body(x, xs, experts=experts):
            p, i, c = xs
            cc = None if c is None else {"latent": c, "pos": cache["pos"]}
            h, nc = L.mla_attention(
                cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                positions, cache=cc,
            )
            x, r = _mla_ffn(cfg, p, x + h, experts, i, valid)
            return x, (None if nc is None else nc["latent"], r)

        x, (lat, r) = jax.lax.scan(_remat(cfg, body), x,
                                   (blocks, jnp.arange(n), lat))
        lats.append(lat)
        routes = r if r is not None else routes
    if cache is None:
        return x, None
    new = {"latent": jnp.concatenate(lats) if len(lats) > 1 else lats[0],
           "pos": cache["pos"] + positions.shape[1]}
    if routes is not None:
        new["routes"] = routes
    return x, new


# ---------------------------------------------------------------------------
# forward (train / scoring): full-sequence logits
# ---------------------------------------------------------------------------

def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array | None = None,  # (B, S) int32
    *,
    inputs_embeds: jax.Array | None = None,  # (B, S, d) modality stub
    enc_embeds: jax.Array | None = None,  # (B, S_enc, d) whisper frames
) -> jax.Array:
    if cfg.family == "encdec":
        return _forward_encdec(cfg, params, tokens, enc_embeds)

    B, Seq = (tokens.shape if tokens is not None else inputs_embeds.shape[:2])
    x = _embed(cfg, params, tokens, inputs_embeds)
    x = L.shard_act(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(Seq)[None], (B, Seq))

    if cfg.is_mla:
        x, _ = _mla_stack(cfg, params, x, positions, None, None)
    elif cfg.family in ("dense", "moe"):
        x, _ = _scan_stack(cfg, params["blocks"], x, positions, None)
    elif cfg.family == "ssm":
        x, _ = _scan_ssm_stack(cfg, params["blocks"], x, None)
    elif cfg.family == "hybrid":
        x = _hybrid_forward(cfg, params, x, positions, caches=None)[0]
    else:
        raise ValueError(cfg.family)
    return _unembed(cfg, params, x)


def _hybrid_forward(cfg, params, x, positions, caches, valid_len=None):
    """zamba2: mamba stack with the shared attention block interleaved."""
    blocks = params["blocks"]
    new_ssm_caches, new_attn_caches = [], []
    ai = 0
    block_fn = _remat(
        cfg, lambda p, x, c: _ssm_layer(cfg, p, x, c, valid_len)
    )
    for i in range(cfg.n_layers):
        p_i = jax.tree_util.tree_map(lambda a: a[i], blocks)
        c_i = None if caches is None else jax.tree_util.tree_map(
            lambda a: a[i], caches["ssm"]
        )
        x, nc = block_fn(p_i, x, c_i)
        if caches is not None:
            new_ssm_caches.append(nc)
        if (i + 1) % cfg.hybrid_period == 0:
            ca = None if caches is None else {
                "k": caches["attn"]["k"][ai],
                "v": caches["attn"]["v"][ai],
                "pos": caches["attn"]["pos"],
            }
            x, nca = _dense_block(
                cfg, params["shared_attn"], x, positions, None, ca,
            )
            if caches is not None:
                new_attn_caches.append(nca)
            ai += 1
    if caches is None:
        return x, None
    stack = lambda xs: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs)
    new_caches = {
        "ssm": stack(new_ssm_caches),
        "attn": {
            "k": jnp.stack([c["k"] for c in new_attn_caches]),
            "v": jnp.stack([c["v"] for c in new_attn_caches]),
            "pos": new_attn_caches[0]["pos"],
        },
    }
    return x, new_caches


def _forward_encdec(cfg, params, tokens, enc_embeds):
    enc = _encode(cfg, params, enc_embeds)
    B, Sd = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(Sd)[None], (B, Sd))
    enc_pos = jnp.broadcast_to(
        jnp.arange(enc.shape[1])[None], (B, enc.shape[1])
    )

    def body(carry, p):
        x = carry
        h, _ = L.multi_head_attention(
            cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            causal=True,
        )
        x = x + h
        h, _ = L.multi_head_attention(
            cfg, p["xattn"], L.rms_norm(x, p["ln_x"], cfg.norm_eps), positions,
            kv_x=enc, kv_positions=enc_pos, causal=False, use_rope=False,
        )
        x = x + h
        x = x + L.mlp(cfg, p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["decoder"])
    return _unembed(cfg, params, x)


def _encode(cfg, params, enc_embeds):
    x = enc_embeds.astype(cfg.compute_dtype)
    B, Se, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))

    def body(carry, p):
        x = carry
        h, _ = L.multi_head_attention(
            cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            causal=False,
        )
        x = x + h
        x = x + L.mlp(cfg, p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(_remat(cfg, body), x, params["encoder"])
    return L.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# decode: cache defs + prefill + single-token step
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """ShapeDtypeStruct tree for the decode cache (dry-run friendly)."""
    kv = lambda n: {
        "k": jax.ShapeDtypeStruct(
            (n, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype
        ),
        "v": jax.ShapeDtypeStruct(
            (n, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype
        ),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if cfg.is_mla:
        return {
            "latent": jax.ShapeDtypeStruct(
                (cfg.n_layers, batch, max_len, cfg.latent_dim),
                cfg.compute_dtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
            **_routes_def(cfg),
        }
    if cfg.family in ("dense", "moe"):
        return kv(cfg.n_layers)
    if cfg.family == "ssm":
        one = S.ssm_cache_defs(cfg, batch)
        return {
            k: jax.ShapeDtypeStruct((cfg.n_layers, *v.shape), v.dtype)
            for k, v in one.items()
        }
    if cfg.family == "hybrid":
        one = S.ssm_cache_defs(cfg, batch)
        n_attn = cfg.n_layers // cfg.hybrid_period
        return {
            "ssm": {
                k: jax.ShapeDtypeStruct((cfg.n_layers, *v.shape), v.dtype)
                for k, v in one.items()
            },
            "attn": kv(n_attn),
        }
    if cfg.family == "encdec":
        return {
            "self": kv(cfg.n_layers),
            "cross": {
                "k": jax.ShapeDtypeStruct(
                    (cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads,
                     cfg.head_dim), cfg.compute_dtype
                ),
                "v": jax.ShapeDtypeStruct(
                    (cfg.n_layers, batch, cfg.encoder_len, cfg.n_kv_heads,
                     cfg.head_dim), cfg.compute_dtype
                ),
            },
        }
    raise ValueError(cfg.family)


def latent_width(cfg: ModelConfig) -> int:
    """Width of a latent pool's row: the latent, padded to 128 lanes."""
    return -(-cfg.latent_dim // 128) * 128


def _routes_def(cfg: ModelConfig) -> dict:
    """The route counter a cache or pool carries where this chip holds a
    share of the experts: routes to each held expert in each MoE layer by
    the last call, (n_moe_layers, held) int32."""
    if not cfg.n_experts_held:
        return {}
    return {"routes": jax.ShapeDtypeStruct(
        (cfg.n_moe_layers, cfg.n_experts_held), jnp.int32)}


def cache_pspecs(cfg: ModelConfig) -> dict:
    """PartitionSpecs matching cache_defs: shard batch over 'data', kv heads
    over 'model' (ssm states: heads over 'model')."""
    from jax.sharding import PartitionSpec as P

    kvp = lambda: {
        "k": P(None, "data", None, "model", None),
        "v": P(None, "data", None, "model", None),
        "pos": P(),
    }
    if cfg.is_mla:
        return {"latent": P(None, "data", None, None), "pos": P(),
                **{k: P() for k in _routes_def(cfg)}}
    if cfg.family in ("dense", "moe"):
        return kvp()
    if cfg.family == "ssm":
        return {
            "state": P(None, "data", "model", None, None),
            "conv": P(None, "data", None, "model"),
        }
    if cfg.family == "hybrid":
        return {
            "ssm": {
                "state": P(None, "data", "model", None, None),
                "conv": P(None, "data", None, "model"),
            },
            "attn": kvp(),
        }
    if cfg.family == "encdec":
        return {
            "self": kvp(),
            "cross": {
                "k": P(None, "data", None, "model", None),
                "v": P(None, "data", None, "model", None),
            },
        }
    raise ValueError(cfg.family)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_defs(cfg, batch, max_len)
    )


def _stack_apply(cfg, params, tokens, cache, enc_embeds, valid_len):
    """Shared decode/prefill body -> (new_cache, x (B, S, d))."""
    if cfg.family == "encdec":
        return _decode_encdec(cfg, params, tokens, cache, enc_embeds)
    B, Sq = tokens.shape
    x = _embed(cfg, params, tokens)
    pos0 = _cache_pos(cfg, cache)
    positions = pos0 + jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))

    if cfg.is_mla:
        valid = None if valid_len is None else (
            jnp.arange(Sq)[None] < valid_len[:, None])
        x, new_cache = _mla_stack(cfg, params, x, positions, cache, valid)
    elif cfg.family in ("dense", "moe"):
        x, new_cache = _scan_stack(cfg, params["blocks"], x, positions, cache)
    elif cfg.family == "ssm":
        x, new_cache = _scan_ssm_stack(
            cfg, params["blocks"], x, cache, valid_len
        )
    elif cfg.family == "hybrid":
        x, new_cache = _hybrid_forward(
            cfg, params, x, positions, caches=cache, valid_len=valid_len
        )
    else:
        raise ValueError(cfg.family)
    return new_cache, x


def decode_step(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S_step) — S_step = 1 for decode, S for prefill
    cache: dict,
    *,
    enc_embeds: jax.Array | None = None,
) -> tuple[dict, jax.Array]:
    """Process tokens at positions cache['pos']..+S, return updated cache +
    logits for the last position."""
    new_cache, x = _stack_apply(cfg, params, tokens, cache, enc_embeds, None)
    logits = _unembed(cfg, params, x[:, -1:])
    return new_cache, logits[:, 0]


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S) — prompts, right-padded to a fixed S
    cache: dict,
    *,
    enc_embeds: jax.Array | None = None,
    valid_len: jax.Array | None = None,  # (B,) true prompt lengths
) -> tuple[dict, jax.Array]:
    """Run the (padded) prompt through the stack once at a FIXED compiled
    shape, returning (cache, logits at each row's last valid position).

    valid_len=None means every row uses the full S (same as decode_step).
    With valid_len, rows are right-padded: attention is causal so pad
    positions never influence valid ones, and the SSM recurrence treats
    pad tokens as exact identity updates (dt forced to 0, conv history
    sliced at valid_len) — the state after prefill equals processing
    exactly valid_len tokens. Attention K/V *at pad positions* hold
    garbage; the serving layer only copies the valid blocks into the pool,
    and the contiguous cache's 'pos' advances by the PADDED S.

    `cache` must be empty (fresh from ``init_cache``): its position is
    replaced by a static 0, which tells the attention layers that the
    prompt attends to itself only and routes it through the full-sequence
    attention path (the flash kernel under ``cfg.attention_kernel``).
    """
    new_cache, x = _stack_apply(
        cfg, params, tokens, _with_pos(cfg, cache, 0), enc_embeds, valid_len
    )
    new_cache = _with_pos(cfg, new_cache, jnp.int32(tokens.shape[1]))
    if valid_len is None:
        xl = x[:, -1:]
    else:
        idx = jnp.maximum(valid_len.astype(jnp.int32) - 1, 0)
        xl = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    logits = _unembed(cfg, params, xl)
    return new_cache, logits[:, 0]


def _with_pos(cfg, cache, pos):
    """Shallow copy of a decode cache with its attention position set."""
    if cfg.family in ("dense", "moe"):
        return {**cache, "pos": pos}
    if cfg.family == "hybrid":
        return {**cache, "attn": {**cache["attn"], "pos": pos}}
    if cfg.family == "encdec":
        return {**cache, "self": {**cache["self"], "pos": pos}}
    return cache  # ssm caches carry no position


def _cache_pos(cfg, cache):
    if cfg.family in ("dense", "moe"):
        return cache["pos"]
    if cfg.family == "ssm":
        return 0  # ssm caches carry no position (state is summary)
    if cfg.family == "hybrid":
        return cache["attn"]["pos"]
    raise ValueError(cfg.family)


def _decode_encdec(cfg, params, tokens, cache, enc_embeds):
    B, Sq = tokens.shape
    x = _embed(cfg, params, tokens)
    pos0 = cache["self"]["pos"]
    positions = pos0 + jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))
    enc_pos = jnp.broadcast_to(
        jnp.arange(cfg.encoder_len)[None], (B, cfg.encoder_len)
    )

    def body(carry, xs):
        x = carry
        p, ck, cv, xk, xv = xs
        h, nc = L.multi_head_attention(
            cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            causal=True, cache={"k": ck, "v": cv, "pos": pos0},
        )
        x = x + h
        h, _ = L.multi_head_attention(
            cfg, p["xattn"], L.rms_norm(x, p["ln_x"], cfg.norm_eps), positions,
            kv_x=jnp.zeros((B, 1, cfg.d_model), x.dtype),  # unused; cached K/V
            kv_positions=enc_pos, causal=False, use_rope=False,
            cache={"k": xk, "v": xv, "pos": jnp.int32(0)},
        )
        x = x + h
        x = x + L.mlp(cfg, p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
        return x, (nc["k"], nc["v"])

    xs = (
        params["decoder"],
        cache["self"]["k"], cache["self"]["v"],
        cache["cross"]["k"], cache["cross"]["v"],
    )
    x, (nk, nv) = jax.lax.scan(_remat(cfg, body), x, xs)
    new_cache = {
        "self": {"k": nk, "v": nv, "pos": pos0 + Sq},
        "cross": cache["cross"],
    }
    return new_cache, x


# ---------------------------------------------------------------------------
# paged decode: shared KV page pool + per-slot block tables (serving)
# ---------------------------------------------------------------------------

def paged_cache_defs(
    cfg: ModelConfig, max_batch: int, n_blocks: int, block_size: int,
    n_pages: int,
) -> dict:
    """ShapeDtypeStruct tree for the serving pool state.

    Attention K/V live in a SHARED page pool (n_layers, n_blocks,
    block_size, KV, Dh) — slots reference pages through the scheduler's
    (max_batch, n_pages) block table, so device memory scales with live
    tokens, not max_batch * max_len. SSM states, conv histories, and
    whisper cross K/V are per-slot fixed-size (their size is
    length-independent), indexed by slot id — the adapter that lets every
    family sit behind the same CachePool interface.
    """
    del n_pages  # table shape is scheduler state, not pool state
    if cfg.is_mla:
        # one latent row (kv_lora_rank + rope values) a token a layer,
        # zero-padded to whole 128-lane tiles: a TPU lays an unpadded pool
        # out with the page axis minor, which every kernel call would have
        # to copy back to row-major
        return {
            "latent": jax.ShapeDtypeStruct(
                (cfg.n_layers, n_blocks, block_size, latent_width(cfg)),
                cfg.compute_dtype),
            **_routes_def(cfg),
        }
    kv = lambda n: {
        "k": jax.ShapeDtypeStruct(
            (n, n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim),
            cfg.compute_dtype,
        ),
        "v": jax.ShapeDtypeStruct(
            (n, n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim),
            cfg.compute_dtype,
        ),
    }
    if cfg.family in ("dense", "moe"):
        return kv(cfg.n_layers)
    if cfg.family == "ssm":
        one = S.ssm_cache_defs(cfg, max_batch)
        return {
            k: jax.ShapeDtypeStruct((cfg.n_layers, *v.shape), v.dtype)
            for k, v in one.items()
        }
    if cfg.family == "hybrid":
        one = S.ssm_cache_defs(cfg, max_batch)
        return {
            "ssm": {
                k: jax.ShapeDtypeStruct((cfg.n_layers, *v.shape), v.dtype)
                for k, v in one.items()
            },
            "attn": kv(cfg.n_layers // cfg.hybrid_period),
        }
    if cfg.family == "encdec":
        cross = lambda: jax.ShapeDtypeStruct(
            (cfg.n_layers, max_batch, cfg.encoder_len, cfg.n_kv_heads,
             cfg.head_dim), cfg.compute_dtype,
        )
        return {"self": kv(cfg.n_layers),
                "cross": {"k": cross(), "v": cross()}}
    raise ValueError(cfg.family)


def _paged_block(cfg, p, x, positions, window, pk, pv, table, lengths):
    h, pk, pv = L.paged_attention(
        cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
        pk, pv, table, lengths, window=window,
    )
    x = x + h
    inner = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        x = x + L.moe(cfg, p["moe"], inner)
    else:
        x = x + L.mlp(cfg, p["mlp"], inner)
    return x, pk, pv


def _paged_scan_stack(cfg, blocks, x, positions, pools, table, lengths):
    """The paged twin of _scan_stack: k/v pool pages scanned per layer,
    table/lengths shared across layers in the closure. Same pair-scan
    treatment of gemma2's local/global alternation (static windows)."""
    windows = _layer_windows(cfg)
    m = len(windows)
    blocks = jax.tree_util.tree_map(lambda t: _substack(t, m), blocks)

    def body(carry, xs):
        x = carry
        p, k, v = xs
        nk, nv = [], []
        for j, w in enumerate(windows):
            pj = jax.tree_util.tree_map(lambda a: a[j], p)
            x, k1, v1 = _paged_block(
                cfg, pj, x, positions, w, k[j], v[j], table, lengths
            )
            nk.append(k1)
            nv.append(v1)
        return x, (jnp.stack(nk), jnp.stack(nv))

    xs = (blocks, _substack(pools["k"], m), _substack(pools["v"], m))
    x, (nk, nv) = jax.lax.scan(body, x, xs)
    return x, {"k": _unsubstack(nk, m), "v": _unsubstack(nv, m)}


def _mla_paged_stack(cfg, params, x, positions, pools, table, lengths):
    """The paged MLA stack: the whole latent pool rides in the scans'
    carry and each layer writes its row and reads its pages in place by
    the layer's index (a per-layer slice would copy the pool). Slots with
    length 0 (empty) route to no expert."""
    pool, routes = pools["latent"], None
    valid = (lengths > 0)[:, None]
    for blocks, first in _mla_parts(cfg, params):
        blocks, experts = _split_experts(cfg, blocks)
        n = jax.tree_util.tree_leaves(blocks)[0].shape[0]

        def body(carry, xs, experts=experts, first=first):
            x, pool = carry
            p, i = xs
            h, pool = L.mla_paged_attention(
                cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps),
                positions, pool, first + i, table, lengths,
            )
            x, r = _mla_ffn(cfg, p, x + h, experts, i, valid)
            return (x, pool), r

        (x, pool), r = jax.lax.scan(body, (x, pool),
                                    (blocks, jnp.arange(n)))
        routes = r if r is not None else routes
    out = {"latent": pool}
    if routes is not None:
        out["routes"] = routes
    return x, out


def _paged_hybrid(cfg, params, x, positions, pools, table, lengths):
    blocks = params["blocks"]
    new_ssm, new_k, new_v = [], [], []
    ai = 0
    pk, pv = pools["attn"]["k"], pools["attn"]["v"]
    for i in range(cfg.n_layers):
        p_i = jax.tree_util.tree_map(lambda a: a[i], blocks)
        c_i = jax.tree_util.tree_map(lambda a: a[i], pools["ssm"])
        x, nc = _ssm_layer(cfg, p_i, x, c_i)
        new_ssm.append(nc)
        if (i + 1) % cfg.hybrid_period == 0:
            x, k1, v1 = _paged_block(
                cfg, params["shared_attn"], x, positions, None,
                pk[ai], pv[ai], table, lengths,
            )
            new_k.append(k1)
            new_v.append(v1)
            ai += 1
    stack = lambda xs: jax.tree_util.tree_map(lambda *a: jnp.stack(a), *xs)
    return x, {
        "ssm": stack(new_ssm),
        "attn": {"k": jnp.stack(new_k), "v": jnp.stack(new_v)},
    }


def _paged_encdec(cfg, params, x, positions, pools, table, lengths):
    B = x.shape[0]
    enc_pos = jnp.broadcast_to(
        jnp.arange(cfg.encoder_len)[None], (B, cfg.encoder_len)
    )

    def body(carry, xs):
        x = carry
        p, pk, pv, xk, xv = xs
        h, pk, pv = L.paged_attention(
            cfg, p["attn"], L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            pk, pv, table, lengths, window=None,
        )
        x = x + h
        # cross K/V are per-slot contiguous (encoder length is fixed and
        # fully live — paging buys nothing); reuse the cached-K/V MHA path
        h, _ = L.multi_head_attention(
            cfg, p["xattn"], L.rms_norm(x, p["ln_x"], cfg.norm_eps),
            positions,
            kv_x=jnp.zeros((B, 1, cfg.d_model), x.dtype),  # unused; cached
            kv_positions=enc_pos, causal=False, use_rope=False,
            cache={"k": xk, "v": xv, "pos": jnp.int32(0)},
        )
        x = x + h
        x = x + L.mlp(cfg, p["mlp"], L.rms_norm(x, p["ln2"], cfg.norm_eps))
        return x, (pk, pv)

    xs = (
        params["decoder"],
        pools["self"]["k"], pools["self"]["v"],
        pools["cross"]["k"], pools["cross"]["v"],
    )
    x, (nk, nv) = jax.lax.scan(body, x, xs)
    return x, {"self": {"k": nk, "v": nv}, "cross": pools["cross"]}


def decode_step_paged(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, 1) — one new token per scheduler slot
    pools: dict,  # paged_cache_defs-shaped pool state
    table: jax.Array,  # (B, n_pages) int32 — pool page ids per slot
    lengths: jax.Array,  # (B,) int32 — tokens already cached per slot
) -> tuple[dict, jax.Array]:
    """One serving decode step at a fixed (max_batch, 1) shape.

    The new token is appended at position lengths[b] (its page/offset come
    from the block table), attention covers lengths + 1 tokens, and rope
    positions are per-slot (slots decode at different depths in the same
    jitted step — the continuous-batching contract). Inactive padding
    slots carry length 0 and all-null table rows: they compute garbage
    into the reserved null page and are ignored by the scheduler. SSM /
    conv / cross caches are slot-indexed; their padding rows idle
    harmlessly. Returns (new_pools, logits (B, vocab)).
    """
    x = _embed(cfg, params, tokens)
    positions = lengths[:, None].astype(jnp.int32)  # (B, 1)
    if cfg.is_mla:
        x, pools = _mla_paged_stack(
            cfg, params, x, positions, pools, table, lengths
        )
    elif cfg.family in ("dense", "moe"):
        x, pools = _paged_scan_stack(
            cfg, params["blocks"], x, positions, pools, table, lengths
        )
    elif cfg.family == "ssm":
        # the recurrent state is a length-independent summary: the paged
        # interface is the slot adapter, the math is the contiguous step
        x, pools = _scan_ssm_stack(cfg, params["blocks"], x, pools)
    elif cfg.family == "hybrid":
        x, pools = _paged_hybrid(
            cfg, params, x, positions, pools, table, lengths
        )
    elif cfg.family == "encdec":
        x, pools = _paged_encdec(
            cfg, params, x, positions, pools, table, lengths
        )
    else:
        raise ValueError(cfg.family)
    logits = _unembed(cfg, params, x[:, -1:])
    return pools, logits[:, 0]


def encode_cross_cache(cfg, params, enc_embeds, batch) -> dict:
    """Whisper: run the encoder once, precompute per-layer cross K/V."""
    enc = _encode(cfg, params, enc_embeds)
    dt = cfg.compute_dtype

    def body(_, p):
        k = jnp.einsum("bsd,dhq->bshq", enc, p["xattn"]["wk"].astype(dt))
        v = jnp.einsum("bsd,dhq->bshq", enc, p["xattn"]["wv"].astype(dt))
        return None, (k, v)

    _, (ks, vs) = jax.lax.scan(body, None, params["decoder"])
    return {"k": ks, "v": vs}
