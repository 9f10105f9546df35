"""Per-architecture smoke tests: reduced config, one forward + decode on CPU.

Asserts output shapes, finiteness, and (for decode-capable archs) that
incremental decode agrees with teacher-forced full-sequence logits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced
from repro.kernels import ops as KO
from repro.models import transformer as T
from repro.models.params import tree_materialize, tree_num_params


def _make(arch):
    cfg = get_reduced(arch)
    defs = T.model_defs(cfg)
    params = tree_materialize(defs, jax.random.PRNGKey(0), cfg.param_dtype)
    return cfg, params


def _inputs(cfg, batch=2, seq=16):
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    kwargs = {}
    if cfg.family == "encdec":
        kwargs["enc_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (batch, cfg.encoder_len, cfg.d_model)
        )
    return tokens, kwargs


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg, params = _make(arch)
    tokens, kwargs = _inputs(cfg)
    logits = T.forward(cfg, params, tokens, **kwargs)
    assert logits.shape == (*tokens.shape, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_no_nans(arch):
    cfg, params = _make(arch)
    tokens, kwargs = _inputs(cfg)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss_fn(p):
        logits = T.forward(cfg, p, tokens, **kwargs)
        lp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)
        return -ll.mean()

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat), f"{arch}: NaN grads"
    # sgd step changes the loss
    params2 = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g.astype(p.dtype),
                                     params, grads)
    loss2 = loss_fn(params2)
    assert bool(jnp.isfinite(loss2))
    assert float(loss2) != float(loss)


def _pin_jnp(cfg):
    """Decode ignores attention_kernel (the cache path is always the
    in-layer einsum), so decode-vs-forward comparisons pin forward to the
    same 'jnp' path — keeping the assertion about CACHE correctness rather
    than f32-vs-bf16 attention accumulation (the registry oracle keeps
    attention in f32; under the 'auto' default that drift is legitimate)."""
    import dataclasses

    return dataclasses.replace(cfg, attention_kernel="jnp")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_teacher_forcing(arch):
    cfg, params = _make(arch)
    cfg = _pin_jnp(cfg)
    batch, seq = 2, 8
    tokens, kwargs = _inputs(cfg, batch, seq)
    full_logits = T.forward(cfg, params, tokens, **kwargs)

    cache = T.init_cache(cfg, batch, max_len=seq + 4)
    if cfg.family == "encdec":
        cache["cross"] = T.encode_cross_cache(
            cfg, params, kwargs["enc_embeds"], batch
        )
    step_logits = []
    for t in range(seq):
        cache, logit = T.decode_step(cfg, params, tokens[:, t : t + 1], cache)
        step_logits.append(logit)
    got = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_logits), rtol=2e-2, atol=2e-2,
    )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode(arch):
    """Prefill 6 tokens at once, decode 2 more; equals token-by-token."""
    cfg, params = _make(arch)
    cfg = _pin_jnp(cfg)
    batch, seq = 1, 8
    tokens, kwargs = _inputs(cfg, batch, seq)

    cache = T.init_cache(cfg, batch, max_len=seq)
    if cfg.family == "encdec":
        cache["cross"] = T.encode_cross_cache(
            cfg, params, kwargs["enc_embeds"], batch
        )
    cache, logits_p = T.decode_step(cfg, params, tokens[:, :6], cache)
    cache, l6 = T.decode_step(cfg, params, tokens[:, 6:7], cache)
    cache, l7 = T.decode_step(cfg, params, tokens[:, 7:8], cache)

    full = T.forward(cfg, params, tokens, **kwargs)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(full[:, 5]),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(l6), np.asarray(full[:, 6]),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(l7), np.asarray(full[:, 7]),
                               rtol=2e-2, atol=2e-2)


def test_param_count_analytic_close_to_actual():
    for arch in ARCH_IDS:
        cfg = get_reduced(arch)
        defs = T.model_defs(cfg)
        actual = tree_num_params(defs)
        analytic = cfg.param_count()
        # analytic formula ignores norm scales etc. — within 10%
        assert abs(actual - analytic) / actual < 0.15, (
            arch, actual, analytic
        )


@pytest.mark.parametrize("arch", ["minitron_8b", "whisper_small"])
def test_attention_kernel_routing_matches_jnp(arch):
    """cfg.attention_kernel routes full-seq self-attention through the
    kernels/ops.py registry; 'off' (jnp oracle) and 'interpret' (Pallas
    interpreter) must match the in-layer einsum path."""
    import dataclasses

    cfg, params = _make(arch)
    tokens, kwargs = _inputs(cfg, batch=1, seq=12)
    # at f32 compute dtype the registry's oracle path and the in-layer
    # einsum path are the same math in the same dtype, but not the same
    # reduction order (grouped-head einsum vs the oracle's own contraction
    # and softmax fusion), so they agree to f32 rounding rather than
    # bitwise: a few ulps per op, compounded through the layers' residual
    # adds. The bound is the registry's f32 attention tolerance, the one
    # every attention backend is held to for ONE attention output, scaled
    # by the number of routed self-attention layers the logits pass through
    # (the same rule as the paged-decode parity in tests/test_serve.py).
    # base must be the in-layer einsum EXPLICITLY: the config default is
    # 'auto' now, which on CPU already resolves to the registry oracle
    cfg32 = dataclasses.replace(
        cfg, compute_dtype=jnp.float32, attention_kernel="jnp"
    )
    base = T.forward(cfg32, params, tokens, **kwargs)
    ref = T.forward(
        dataclasses.replace(cfg32, attention_kernel="off"), params, tokens,
        **kwargs,
    )
    tol = KO.get_kernel("flash_attention").tolerance(jnp.float32)
    depth = cfg.n_layers + cfg.n_encoder_layers
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(base),
        rtol=tol.rtol * depth, atol=tol.atol * depth,
    )
    # oracle vs the Pallas interpreter through the same routing
    interp = T.forward(
        dataclasses.replace(cfg32, attention_kernel="interpret"), params,
        tokens, **kwargs,
    )
    np.testing.assert_allclose(
        np.asarray(interp), np.asarray(ref), rtol=1e-4, atol=1e-4
    )
