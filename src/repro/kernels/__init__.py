"""Pallas TPU kernels for the paper's and substrate's compute hot-spots.

  flash_attention  GQA/causal/window/softcap online-softmax attention,
                   custom_vjp with blocked backward kernels (dq + dk/dv
                   tiles recomputed from the saved log-sum-exp)
  ssd_scan         Mamba2/SSD within-chunk compute (MXU blocking),
                   custom_vjp with a chunked backward kernel
  decode_attention paged single-query decode (GQA), and its latent (MLA)
                   mode over a paged latent pool
  expert_matmul    grouped matmul over the experts a chip holds (the
                   dropless MoE layer)
  sparse_saga      DSBA per-node sparse row update (one-hot gather and
                   scatter — the TPU adaptation, DESIGN.md §5)
  topk_compress    block-local top-k for gossip delta streams

Each kernel: <name>.py (``pallas_call`` + BlockSpec); ops.py is the backend
REGISTRY (KernelSpec: pallas/interpret/ref impls + per-kernel forward AND
gradient tolerance policies + the parity_check harness) plus jit'd public
wrappers; ref.py the pure-jnp oracles whose autodiff is also the gradient
ground truth (tests/test_kernels.py sweeps shapes/dtypes in interpret mode;
tests/test_ops_dispatch.py sweeps the registry; tests/test_kernel_grads.py
sweeps the vjps). See docs/kernels.md for the authoring guide.
"""

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, interpret: bool = False, **kwargs):
    """``pl.pallas_call`` whose compiled form is traced in 32-bit mode.

    Under ``jax_enable_x64`` (which the solver entry points turn on), Python
    ints in a kernel body or index map trace as int64 — loop counters,
    ``//`` constants — and Mosaic cannot lower 64-bit values. The kernels
    take 32-bit operands on the TPU anyway, so the compiled kernel is traced
    with x64 off; the surrounding program keeps its own mode. Interpret mode
    keeps the caller's mode, so a float64 kernel stays float64 on the CPU.
    """
    call = pl.pallas_call(kernel, interpret=interpret, **kwargs)
    if interpret:
        return call

    def traced_x32(*args):
        with jax.enable_x64(False):
            return call(*args)

    return traced_x32
