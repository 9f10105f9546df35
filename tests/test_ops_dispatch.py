"""kernels/ops.py backend registry: resolution, dispatch, parity harness.

Every registered kernel x every use_pallas mode must resolve to a backend
callable; 'interpret' must match 'off' within the kernel's declared
tolerance over a shape/dtype grid; the sparse-AXPY f64 interpret path is
bit-exact by registry policy.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


def _distinct_idx(key, N, D, k):
    """Distinct indices per row (padded-CSR guarantee in data/synthetic.py)."""
    return jnp.stack([
        jax.random.permutation(jax.random.fold_in(key, n), D)[:k]
        for n in range(N)
    ]).astype(jnp.int32)


def _example_args(name, key, dtype=jnp.float32, small=True):
    ks = jax.random.split(key, 5)
    if name == "flash_attention":
        B, Hq, Hkv, S, D = (1, 4, 2, 96, 32) if small else (2, 8, 2, 192, 64)
        q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
        k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
        v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
        return (q, k, v), {"causal": True}
    if name == "decode_attention":
        B, Hq, Hkv, D, bs, nb, npg = (
            (2, 4, 2, 24, 8, 9, 3) if small else (4, 8, 2, 64, 16, 33, 6)
        )
        q = jax.random.normal(ks[0], (B, Hq, D), dtype)
        kp = jax.random.normal(ks[1], (nb, bs, Hkv, D), dtype)
        vp = jax.random.normal(ks[2], (nb, bs, Hkv, D), dtype)
        table = jax.random.randint(ks[3], (B, npg), 1, nb).astype(jnp.int32)
        lengths = jnp.asarray(
            [npg * bs - 3, 0, 1, 5][:B], jnp.int32
        )
        return (q, kp, vp, table, lengths), {"window": 7, "softcap": 30.0}
    if name == "mla_decode_attention":
        L, B, H, R, C, bs, nb, npg = (
            (2, 3, 4, 24, 16, 8, 9, 3) if small else (3, 4, 8, 72, 64, 16, 33, 6)
        )
        q = jax.random.normal(ks[0], (B, H, R), dtype)
        pool = jax.random.normal(ks[1], (L, nb, bs, R), dtype)
        table = jax.random.randint(ks[3], (B, npg), 1, nb).astype(jnp.int32)
        lengths = jnp.asarray([npg * bs - 3, 0, 1, 5 * bs + 2][:B], jnp.int32)
        return (q, pool, jnp.int32(L - 1), table, lengths), {
            "scale": R**-0.5, "value_dim": C}
    if name == "expert_matmul":
        tm, nt, G, K, N = (16, 4, 3, 32, 48) if small else (16, 6, 8, 256, 384)
        x = jax.random.normal(ks[0], (tm * nt, K), dtype)
        w = jax.random.normal(ks[1], (G, K, N), dtype) * K**-0.5
        groups = jax.random.randint(ks[2], (nt,), 0, G).astype(jnp.int32)
        return (x, w, groups, jnp.int32(nt)), {"tm": tm}
    if name == "ssd_chunk":
        B, nc, Q, nh, hd, ds = (1, 2, 32, 2, 16, 8) if small else (2, 2, 64, 4, 32, 16)
        xdt = jax.random.normal(ks[0], (B, nc, Q, nh, hd), dtype)
        cum = -jnp.cumsum(
            jax.random.uniform(ks[1], (B, nc, Q, nh), dtype,
                               minval=0.01, maxval=0.2), axis=2)
        Bc = jax.random.normal(ks[2], (B, nc, Q, ds), dtype)
        Cc = jax.random.normal(ks[3], (B, nc, Q, ds), dtype)
        return (xdt, cum, Bc, Cc), {}
    if name == "sparse_dot":
        N, D, k = (4, 200, 8) if small else (8, 1000, 16)
        psi = jax.random.normal(ks[0], (N, D), dtype)
        idx = _distinct_idx(ks[1], N, D, k)
        val = jax.random.normal(ks[2], (N, k), dtype)
        return (psi, idx, val), {}
    if name == "sparse_axpy":
        N, D, k = (4, 200, 8) if small else (8, 1000, 16)
        psi = jax.random.normal(ks[0], (N, D), dtype)
        idx = _distinct_idx(ks[1], N, D, k)
        val = jax.random.normal(ks[2], (N, k), dtype)
        coef = jax.random.normal(ks[3], (N,), dtype)
        rho = jax.random.uniform(ks[4], (N,), dtype, minval=0.5, maxval=1.0)
        return (psi, idx, val, coef, rho), {}
    if name == "block_topk":
        nb, block, k = (4, 64, 8) if small else (8, 256, 16)
        x = jax.random.normal(ks[0], (nb, block), dtype)
        return (x, k), {}
    raise ValueError(name)


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------

def test_registry_is_complete():
    assert ops.registered_kernels() == (
        "block_topk", "decode_attention", "expert_matmul", "flash_attention",
        "mla_decode_attention", "sparse_axpy", "sparse_dot", "ssd_chunk",
    )


@pytest.mark.parametrize("name", ops.registered_kernels())
@pytest.mark.parametrize("mode", ops.MODES)
def test_every_kernel_x_mode_resolves(name, mode):
    backend = ops.resolve_mode(mode)
    assert backend in ops.BACKENDS
    impl = ops.get_kernel(name).impl(backend)
    assert callable(impl)


def test_auto_resolves_to_ref_off_tpu():
    want = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert ops.resolve_mode("auto") == want


def test_unknown_mode_and_backend_raise():
    with pytest.raises(ValueError):
        ops.resolve_mode("pallas")  # backend name, not a mode
    with pytest.raises(ValueError):
        ops.get_kernel("flash_attention").impl("jit")


def test_duplicate_registration_rejected():
    spec = ops.get_kernel("flash_attention")
    with pytest.raises(ValueError):
        ops.register_kernel(spec)


def test_tolerance_fallback_to_f32():
    spec = ops.get_kernel("flash_attention")
    assert spec.tolerance(jnp.float64) == spec.tolerance(jnp.float32)
    assert spec.tolerance(jnp.bfloat16).atol == 2e-2


# ---------------------------------------------------------------------------
# parity: interpret matches off within the declared tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ops.registered_kernels())
@pytest.mark.parametrize("small", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interpret_matches_ref_within_declared_tol(name, small, dtype):
    if dtype == jnp.bfloat16 and name not in (
        "flash_attention", "decode_attention", "mla_decode_attention",
        "expert_matmul",
    ):
        # DSBA/selection kernels are f32/f64 paths; ssd_chunk's oracle
        # accumulates in the input dtype, so bf16 parity is not a kernel
        # property (models/ssm.py always feeds it f32)
        pytest.skip("bf16 policy only declared for the attention kernels")
    args, kw = _example_args(name, jax.random.PRNGKey(0), dtype, small)
    err = ops.parity_check(name, *args, use_pallas="interpret", **kw)
    assert np.isfinite(err)


def test_flash_attention_parity_tol_matches_acceptance():
    # the declared policy IS the acceptance bar: 2e-5 (f32) / 2e-2 (bf16)
    spec = ops.get_kernel("flash_attention")
    assert spec.tolerance(jnp.float32).atol == 2e-5
    assert spec.tolerance(jnp.bfloat16).atol == 2e-2


def test_sparse_axpy_f64_interpret_is_bit_exact():
    """The relay's CPU fallback: exact-zero tolerance enforced centrally.

    The contract is the relay's call shape — unit decay (rho = 1, delta
    densification). With arbitrary rho, XLA's FMA fusion of rho*psi + ...
    legally differs from the oracle by 1 ulp.
    """
    tol = ops.get_kernel("sparse_axpy").tolerance(jnp.float64)
    assert (tol.rtol, tol.atol) == (0.0, 0.0)
    with jax.enable_x64(True):
        args, kw = _example_args(
            "sparse_axpy", jax.random.PRNGKey(1), jnp.float64, small=False
        )
        psi, idx, val, coef, _ = args
        rho = jnp.ones_like(coef)
        err = ops.parity_check("sparse_axpy", psi, idx, val, coef, rho, **kw)
    assert err == 0.0


def test_sparse_dot_f64_interpret_meets_policy_with_kernel_kwargs():
    """f64 oracle stays f64 (1e-12 policy is meetable), and kernel-only
    kwargs (block_d) are stripped before the oracle leg runs."""
    with jax.enable_x64(True):
        args, _ = _example_args(
            "sparse_dot", jax.random.PRNGKey(5), jnp.float64, small=False
        )
        err = ops.parity_check("sparse_dot", *args, block_d=64)
    assert err <= 1e-12


def test_topk_parity_rejects_inconsistent_indices():
    """_topk_compare cross-checks vals against x[idx]: corrupt indices with
    correct values must fail, not pass silently."""
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 64))
    vals, idx = ops.dispatch("block_topk", x, 8, use_pallas="interpret")
    spec = ops.get_kernel("block_topk")
    tol = spec.tolerance(x.dtype)
    spec.compare((x, 8), (vals, idx), (vals, idx), tol)  # consistent: ok
    bad_idx = (idx + 1) % x.shape[1]
    with pytest.raises(AssertionError):
        spec.compare((x, 8), (vals, bad_idx), (vals, idx), tol)


def test_wrapper_axpy_interpret_defaults_to_input_dtype():
    """compute_dtype is resolved in ONE place (the registry adapter):
    interpret -> psi.dtype, so f64 inputs give bit-exact oracles without
    call sites re-deriving the dtype."""
    with jax.enable_x64(True):
        args, _ = _example_args(
            "sparse_axpy", jax.random.PRNGKey(2), jnp.float64
        )
        psi, idx, val, coef, _ = args
        rho = jnp.ones_like(coef)  # the relay's unit-decay call shape
        got = ops.saga_sparse_axpy(psi, idx, val, coef, rho,
                                   use_pallas="interpret")
        from repro.kernels import ref as R

        want = R.sparse_axpy_ref(psi, idx, val, coef, rho)
    assert got.dtype == jnp.float64
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# dispatch through the public wrappers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_public_wrappers_dispatch(mode):
    key = jax.random.PRNGKey(3)
    (q, k, v), _ = _example_args("flash_attention", key)
    o = ops.flash_attention(q, k, v, use_pallas=mode)
    assert o.shape == q.shape
    (x, kk), _ = _example_args("block_topk", key)
    vals, idx = ops.topk_blocks(x, kk, use_pallas=mode)
    assert vals.shape == idx.shape == (x.shape[0], kk)
    (xdt, cum, Bc, Cc), _ = _example_args("ssd_chunk", key)
    y, st = ops.ssd_chunk(xdt, cum, Bc, Cc, use_pallas=mode)
    assert y.shape == xdt.shape
    (psi, idx2, val), _ = _example_args("sparse_dot", key)
    s = ops.saga_sparse_dot(psi, idx2, val, use_pallas=mode)
    assert s.shape == (psi.shape[0],)


def test_flash_attention_wrapper_is_differentiable_in_interpret():
    """The custom_vjp path: grads flow through the Pallas kernel without a
    reference-forward recompute (the old wrapper was fwd-only)."""
    (q, k, v), _ = _example_args("flash_attention", jax.random.PRNGKey(4))
    g = jax.grad(
        lambda q: jnp.sum(ops.flash_attention(q, k, v, use_pallas="interpret"))
    )(q)
    assert g.shape == q.shape and bool(jnp.all(jnp.isfinite(g)))
