"""Backend registry + jit'd public wrappers for the Pallas kernels.

Every kernel is registered once as a :class:`KernelSpec` mapping its name to
the three backends the suite exercises

  pallas     the compiled Pallas kernel (TPU)
  interpret  the same kernel body under the Pallas interpreter (CPU parity)
  ref        the pure-jnp oracle in kernels/ref.py

plus a per-kernel tolerance policy (keyed by input dtype) and an optional
custom comparator. ``parity_check`` is the shared harness: it runs a kernel
in a given mode and in ``off`` (ref) mode and asserts agreement within the
kernel's declared tolerance — tests/test_ops_dispatch.py drives it over the
whole registry; tests/test_kernels.py uses the same policies for its shape
sweeps.

use_pallas modes: 'auto' picks the Pallas kernel on TPU and the jnp
reference on CPU (this container); 'on' forces the compiled kernel;
'interpret' forces the kernel body in interpret mode (how the tests
validate the kernels here); 'off' is the pure-jnp oracle.

dtype policy: kernels accumulate in f32 (the TPU MXU-native dtype). The
sparse kernels' interpret path is the one exception — it is the CPU
fallback of the DSBA relay (core/sparse_comm.py), whose f64 truth-checking
needs BIT EXACTNESS, so ``_resolve_compute_dtype`` (the registry adapters'
single policy point) picks psi.dtype under interpret mode, and the registry
declares an exact (0, 0) f64 sparse-AXPY tolerance that the parity harness
enforces.

gradient policy: the differentiable kernels (flash_attention, ssd_chunk —
``jax.custom_vjp`` with blocked Pallas backward kernels) additionally
declare ``grad_argnums`` (which positional args carry cotangents) and a
``grad_tol`` tolerance map. ``parity_check(..., grads=True)`` pulls vjp
outputs through the requested backend and through 'off' — where the
pure-jnp oracle's ordinary autodiff is the gradient ground truth — and
asserts agreement within the declared grad tolerance.
"""
from __future__ import annotations

import dataclasses
import inspect
from functools import partial, wraps
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention as DA
from repro.kernels import expert_matmul as EM
from repro.kernels import flash_attention as FA
from repro.kernels import ref as R
from repro.kernels import ssd_scan as SSD
from repro.kernels.sparse_saga import sparse_axpy, sparse_dot
from repro.kernels.topk_compress import block_topk

MODES = ("auto", "on", "interpret", "off")
BACKENDS = ("pallas", "interpret", "ref")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_mode(use_pallas: str) -> str:
    """use_pallas mode -> backend name ('pallas' | 'interpret' | 'ref')."""
    if use_pallas not in MODES:
        raise ValueError(
            f"use_pallas={use_pallas!r} not in {MODES}"
        )
    if use_pallas == "auto":
        return "pallas" if _on_tpu() else "ref"
    return {"on": "pallas", "interpret": "interpret", "off": "ref"}[use_pallas]


@dataclasses.dataclass(frozen=True)
class Tolerance:
    """An (rtol, atol) parity bound; (0, 0) means bit-exact."""

    rtol: float
    atol: float


# default policies; kernels override per dtype at registration
_F32_TOL = Tolerance(2e-5, 2e-5)
_BF16_TOL = Tolerance(2e-2, 2e-2)
# gradient defaults: one recompute deeper than the forward, so ~10x looser
_F32_GRAD_TOL = Tolerance(2e-4, 2e-4)
_BF16_GRAD_TOL = Tolerance(5e-2, 5e-2)


def _strip_unknown_kwargs(fn: Callable) -> Callable:
    """Drop kernel-only kwargs (compute_dtype, block_d, ...)
    before calling a pure-jnp oracle, so one call site can dispatch to
    either backend with the kernel's full kwarg surface."""
    params = inspect.signature(fn).parameters.values()
    if any(p.kind == inspect.Parameter.VAR_KEYWORD for p in params):
        return fn
    accepted = {
        p.name for p in params
        if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                      inspect.Parameter.KEYWORD_ONLY)
    }

    @wraps(fn)
    def stripped(*args, **kwargs):
        return fn(*args, **{k: v for k, v in kwargs.items() if k in accepted})

    return stripped


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One kernel's backends + parity policy.

    pallas: callable taking (*args, interpret: bool, **kw) — the Pallas
        launch wrapper. 'interpret' backend is the same callable with
        interpret=True.
    ref: pure-jnp oracle with the same positional surface; kernel-only
        kwargs it doesn't accept are stripped at dispatch (impl('ref')).
    tol: {dtype name: Tolerance} parity policy; missing dtypes fall back
        to float32's entry.
    compare: optional (args, got, want, tol) -> max_err comparator for
        kernels whose outputs match as sets rather than elementwise
        (block_topk); receives the input args for consistency checks.
    grad_argnums: positional args that carry cotangents (None = the kernel
        has no differentiable surface; parity_check(grads=True) rejects it).
    grad_tol: {dtype name: Tolerance} policy for vjp outputs; None falls
        back to the forward `tol` map.
    """

    name: str
    pallas: Callable
    ref: Callable
    tol: dict[str, Tolerance]
    compare: Callable | None = None
    grad_argnums: tuple[int, ...] | None = None
    grad_tol: dict[str, Tolerance] | None = None

    def impl(self, backend: str) -> Callable:
        """Resolve a backend name to its callable (see class docstring)."""
        if backend == "ref":
            return _strip_unknown_kwargs(self.ref)
        if backend == "pallas":
            return partial(self.pallas, interpret=False)
        if backend == "interpret":
            return partial(self.pallas, interpret=True)
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")

    def tolerance(self, dtype) -> Tolerance:
        """Forward-output parity Tolerance for `dtype` (f32 fallback)."""
        key = jnp.dtype(dtype).name
        if key in self.tol:
            return self.tol[key]
        return self.tol.get("float32", _F32_TOL)

    def grad_tolerance(self, dtype) -> Tolerance:
        """Vjp-output parity Tolerance for `dtype` (falls back to `tol`)."""
        if self.grad_tol is None:
            return self.tolerance(dtype)
        key = jnp.dtype(dtype).name
        if key in self.grad_tol:
            return self.grad_tol[key]
        return self.grad_tol.get("float32", _F32_GRAD_TOL)


_REGISTRY: dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    """Add `spec` to the registry; duplicate names are a hard error."""
    if spec.name in _REGISTRY:
        raise ValueError(f"kernel {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get_kernel(name: str) -> KernelSpec:
    """Look up a registered KernelSpec by name (KeyError if unknown)."""
    return _REGISTRY[name]


def registered_kernels() -> tuple[str, ...]:
    """Sorted names of every registered kernel."""
    return tuple(sorted(_REGISTRY))


def dispatch(name: str, *args, use_pallas: str = "auto", **kwargs):
    """Resolve (kernel, mode) -> backend impl and call it."""
    return get_kernel(name).impl(resolve_mode(use_pallas))(*args, **kwargs)


# ---------------------------------------------------------------------------
# parity harness
# ---------------------------------------------------------------------------

def _leaf_max_err(got, want) -> float:
    ga = np.asarray(got, np.float64)
    wa = np.asarray(want, np.float64)
    return float(np.max(np.abs(ga - wa))) if ga.size else 0.0


def _assert_leaves_close(name, got, want, tol: Tolerance) -> float:
    """Elementwise leaf comparison shared by the fwd and vjp parity paths."""
    got_leaves = jax.tree_util.tree_leaves(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves), (name, got, want)
    max_err = 0.0
    for g, w in zip(got_leaves, want_leaves):
        if tol.rtol == 0.0 and tol.atol == 0.0:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64),
                rtol=tol.rtol, atol=tol.atol,
            )
        max_err = max(max_err, _leaf_max_err(g, w))
    return max_err


def _cotangents_like(out):
    """Deterministic non-constant cotangents for vjp parity (no PRNG key:
    a sin ramp avoids the symmetric cancellations an all-ones seed hides)."""

    def one(leaf):
        ramp = jnp.sin(jnp.arange(leaf.size, dtype=jnp.float32) * 0.7)
        return ramp.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree_util.tree_map(one, out)


def _vjp_outputs(spec: KernelSpec, backend: str, args, kwargs):
    """Pull deterministic cotangents back through `backend`'s kernel.

    Differentiates w.r.t. spec.grad_argnums only (sparse kernels carry int
    index args); non-diff args and kwargs are closed over. Returns the
    cotangent tuple, one entry per grad argnum.
    """
    if spec.grad_argnums is None:
        raise ValueError(f"kernel {spec.name!r} declares no grad_argnums")
    impl = spec.impl(backend)
    diff_args = tuple(args[i] for i in spec.grad_argnums)

    def fn(*diff):
        full = list(args)
        for i, a in zip(spec.grad_argnums, diff):
            full[i] = a
        return impl(*full, **kwargs)

    out, pullback = jax.vjp(fn, *diff_args)
    return pullback(_cotangents_like(out))


def parity_check(
    name: str, *args, use_pallas: str = "interpret", tol_dtype=None,
    grads: bool = False, **kwargs
) -> float:
    """Assert kernel-vs-oracle agreement within the declared tolerance.

    Runs `name` under `use_pallas` and under 'off', compares every output
    leaf with the kernel's Tolerance for `tol_dtype` (default: dtype of the
    first array argument), and returns the max abs error across leaves.
    A Tolerance of (0, 0) asserts bit-exactness.

    grads=True additionally compares vjp outputs (deterministic cotangents
    pulled back through the kernel's grad_argnums) under the kernel's
    grad tolerance — for the 'off' leg this is plain jax autodiff of the
    pure-jnp oracle, i.e. the registry-resolved custom_vjp backward is
    checked against reference autodiff. The returned max error covers both
    the forward and vjp leaves.
    """
    spec = get_kernel(name)
    if tol_dtype is None:
        tol_dtype = next(
            a.dtype for a in args if hasattr(a, "dtype")
            and jnp.issubdtype(a.dtype, jnp.floating)
        )
    tol = spec.tolerance(tol_dtype)
    got = dispatch(name, *args, use_pallas=use_pallas, **kwargs)
    want = dispatch(name, *args, use_pallas="off", **kwargs)
    if spec.compare is not None:
        max_err = spec.compare(args, got, want, tol)
    else:
        max_err = _assert_leaves_close(name, got, want, tol)
    if grads:
        backend = resolve_mode(use_pallas)
        got_ct = _vjp_outputs(spec, backend, args, kwargs)
        want_ct = _vjp_outputs(spec, "ref", args, kwargs)
        grad_err = _assert_leaves_close(
            f"{name}:vjp", got_ct, want_ct, spec.grad_tolerance(tol_dtype)
        )
        max_err = max(max_err, grad_err)
    return max_err


def _topk_compare(args, got, want, tol: Tolerance) -> float:
    """block_topk parity: selected SETS match (tie order may differ) AND
    every returned (value, index) pair is self-consistent with the input —
    gossip builds its wire-format global indices from these, so a value
    that doesn't live at its claimed index must fail parity."""
    x = np.asarray(args[0])
    vals, idx = (np.asarray(a) for a in got)
    vals_r, idx_r = (np.asarray(a) for a in want)
    gm = np.sort(np.abs(vals.astype(np.float64)), axis=1)
    wm = np.sort(np.abs(vals_r.astype(np.float64)), axis=1)
    np.testing.assert_allclose(gm, wm, rtol=tol.rtol, atol=tol.atol)
    # tolerance, not equality: the kernel body rounds through f32, so f64
    # inputs gather back 1 f32-ulp off; wrong indices miss by far more
    np.testing.assert_allclose(np.take_along_axis(x, idx, axis=1), vals,
                               rtol=tol.rtol, atol=tol.atol)
    np.testing.assert_allclose(np.take_along_axis(x, idx_r, axis=1), vals_r,
                               rtol=tol.rtol, atol=tol.atol)
    return float(np.max(np.abs(gm - wm))) if gm.size else 0.0


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------

def _flash_pallas(q, k, v, *, causal=True, window=None, softcap=None,
                  interpret=False):
    """Registry adapter: the flash-attention custom_vjp wrapper (forward
    kernel + blocked Pallas backward; statics are positional for
    jax.custom_vjp)."""
    return FA.flash_attention(
        q, k, v, causal, window, softcap, 128, 128, interpret
    )


register_kernel(KernelSpec(
    name="flash_attention",
    pallas=_flash_pallas,
    ref=R.attention_ref,
    tol={"float32": _F32_TOL, "bfloat16": _BF16_TOL},
    grad_argnums=(0, 1, 2),
    # vjp vs ref autodiff: blocked-recompute bwd measured <5e-6 f32 /
    # <4e-2 bf16 worst-case over the statics grid (tests/test_kernel_grads)
    grad_tol={"float32": _F32_GRAD_TOL, "bfloat16": _BF16_GRAD_TOL},
))


def _decode_attn_pallas(q, k_pool, v_pool, table, lengths, *, window=None,
                        softcap=None, interpret=False):
    """Registry adapter: the paged single-query decode-attention launch
    (block-table gather in the scalar-prefetch index maps)."""
    return DA.decode_attention(
        q, k_pool, v_pool, table, lengths,
        window=window, softcap=softcap, interpret=interpret,
    )


register_kernel(KernelSpec(
    name="decode_attention",
    pallas=_decode_attn_pallas,
    ref=R.decode_attention_ref,
    # decode is inference-only: no grad surface is declared
    tol={"float32": _F32_TOL, "bfloat16": _BF16_TOL},
))


def _mla_decode_pallas(q, pool, layer, table, lengths, *, scale, value_dim,
                      interpret=False):
    """Registry adapter: the paged latent (MLA) decode launch."""
    return DA.mla_decode_attention(
        q, pool, layer, table, lengths, scale=scale, value_dim=value_dim,
        interpret=interpret,
    )


register_kernel(KernelSpec(
    name="mla_decode_attention",
    pallas=_mla_decode_pallas,
    ref=R.mla_decode_attention_ref,
    tol={"float32": _F32_TOL, "bfloat16": _BF16_TOL},
))


def _expert_matmul_pallas(x, w, tile_group, n_active, *, tm, interpret=False):
    """Registry adapter: the held experts' grouped matmul."""
    return EM.expert_matmul(x, w, tile_group, n_active, tm=tm,
                            interpret=interpret)


register_kernel(KernelSpec(
    name="expert_matmul",
    pallas=_expert_matmul_pallas,
    ref=R.expert_matmul_ref,
    tol={"float32": _F32_TOL, "bfloat16": _BF16_TOL},
))


def _ssd_pallas(xdt, cum, Bc, Cc, *, head_block=None, interpret=False):
    """Registry adapter: the ssd_chunk custom_vjp wrapper (within-chunk
    forward kernel + chunked backward kernel over the saved residuals).
    head_block=None picks the largest grid-legal block (<= 4 heads) that
    divides the model's head count."""
    if head_block is None:
        nh = xdt.shape[3]
        head_block = next(hb for hb in (4, 3, 2, 1) if nh % hb == 0)
    return SSD.ssd_chunk(xdt, cum, Bc, Cc, head_block, interpret)


register_kernel(KernelSpec(
    name="ssd_chunk",
    pallas=_ssd_pallas,
    ref=R.ssd_chunk_ref,
    tol={"float32": _F32_TOL, "bfloat16": _BF16_TOL},
    grad_argnums=(0, 1, 2, 3),
    # vjp vs ref autodiff measured <6e-5 f32 worst-case; models/ssm.py
    # always feeds f32, so no bf16 grad policy is declared
    grad_tol={"float32": _F32_GRAD_TOL},
))


def _resolve_compute_dtype(psi, interpret, compute_dtype):
    """THE one place the sparse-kernel dtype policy lives: the interpret
    (CPU-fallback) path computes in the model dtype — the f64 DSBA relay
    stays bit-exact — while the compiled TPU kernel accumulates in
    MXU-native f32."""
    if compute_dtype is not None:
        return compute_dtype
    return psi.dtype if interpret else jnp.float32


def _sparse_dot_pallas(psi, idx, val, *, interpret=False, compute_dtype=None,
                       **kw):
    return sparse_dot(
        psi, idx, val, interpret=interpret,
        compute_dtype=_resolve_compute_dtype(psi, interpret, compute_dtype),
        **kw,
    )


def _sparse_axpy_pallas(psi, idx, val, coef, rho, *, interpret=False,
                        compute_dtype=None, **kw):
    return sparse_axpy(
        psi, idx, val, coef, rho, interpret=interpret,
        compute_dtype=_resolve_compute_dtype(psi, interpret, compute_dtype),
        **kw,
    )


register_kernel(KernelSpec(
    name="sparse_dot",
    pallas=_sparse_dot_pallas,
    ref=R.sparse_dot_ref,
    tol={"float32": Tolerance(1e-5, 1e-5), "float64": Tolerance(1e-12, 1e-12)},
))

register_kernel(KernelSpec(
    name="sparse_axpy",
    pallas=_sparse_axpy_pallas,
    ref=R.sparse_axpy_ref,
    # f64 interpret is the DSBA relay's CPU fallback: BIT EXACT by policy
    # for the relay's call shape (rho = 1, distinct per-row indices —
    # delta densification). Arbitrary rho can differ by 1 ulp via legal
    # FMA fusion of rho*psi + coef*scat.
    tol={"float32": Tolerance(1e-5, 1e-5), "float64": Tolerance(0.0, 0.0)},
))

register_kernel(KernelSpec(
    name="block_topk",
    pallas=block_topk,
    ref=R.block_topk_ref,
    tol={"float32": Tolerance(1e-6, 1e-6)},
    compare=_topk_compare,
))


# ---------------------------------------------------------------------------
# jit'd public wrappers (the stable call surface; modes are static)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("causal", "window", "softcap", "use_pallas"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    use_pallas: str = "auto"):
    """Registry-dispatched attention, differentiable under every mode
    (custom_vjp blocked backward on the kernel backends, plain autodiff of
    the oracle under 'off'/CPU-'auto')."""
    return dispatch("flash_attention", q, k, v, causal=causal, window=window,
                    softcap=softcap, use_pallas=use_pallas)


@partial(jax.jit, static_argnames=("window", "softcap", "use_pallas"))
def decode_attention(q, k_pool, v_pool, table, lengths, *, window=None,
                     softcap=None, use_pallas: str = "auto"):
    """Registry-dispatched paged single-query decode attention (the serving
    hot path; ModelConfig.decode_kernel picks the mode)."""
    return dispatch(
        "decode_attention", q, k_pool, v_pool, table, lengths,
        window=window, softcap=softcap, use_pallas=use_pallas,
    )


@partial(jax.jit, static_argnames=("scale", "value_dim", "use_pallas"))
def mla_decode_attention(q, pool, layer, table, lengths, *, scale: float,
                         value_dim: int, use_pallas: str = "auto"):
    """Registry-dispatched paged latent (MLA) decode attention."""
    return dispatch(
        "mla_decode_attention", q, pool, layer, table, lengths,
        scale=scale, value_dim=value_dim, use_pallas=use_pallas,
    )


@partial(jax.jit, static_argnames=("tm", "use_pallas"))
def expert_matmul(x, w, tile_group, n_active, *, tm: int,
                  use_pallas: str = "auto"):
    """Registry-dispatched grouped matmul over held experts' row tiles."""
    return dispatch("expert_matmul", x, w, tile_group, n_active, tm=tm,
                    use_pallas=use_pallas)


@partial(jax.jit, static_argnames=("use_pallas",))
def ssd_chunk(xdt, cum, Bc, Cc, *, use_pallas: str = "auto"):
    """Registry-dispatched within-chunk SSD -> (y_intra, chunk states);
    differentiable under every mode (chunked custom_vjp backward on the
    kernel backends)."""
    return dispatch("ssd_chunk", xdt, cum, Bc, Cc, use_pallas=use_pallas)


@partial(jax.jit, static_argnames=("use_pallas",))
def saga_sparse_dot(psi, idx, val, *, use_pallas: str = "auto"):
    """Registry-dispatched per-node sparse dot (DSBA step, eq. 30 input)."""
    return dispatch("sparse_dot", psi, idx, val, use_pallas=use_pallas)


@partial(jax.jit, static_argnames=("use_pallas", "compute_dtype"))
def saga_sparse_axpy(psi, idx, val, coef, rho, *, use_pallas: str = "auto",
                     compute_dtype=None):
    """Registry-dispatched sparse AXPY row update (the DSBA-s relay's
    densification hot path)."""
    # compute_dtype=None -> the registry adapter's central policy
    # (_resolve_compute_dtype); the ref backend strips kernel-only kwargs
    return dispatch(
        "sparse_axpy", psi, idx, val, coef, rho, use_pallas=use_pallas,
        compute_dtype=compute_dtype,
    )


@partial(jax.jit, static_argnames=("k", "use_pallas"))
def topk_blocks(x, k: int, *, use_pallas: str = "auto"):
    """Registry-dispatched block-local top-|value| selection (gossip)."""
    return dispatch("block_topk", x, k, use_pallas=use_pallas)
