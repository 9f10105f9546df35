"""Pod-axis decentralized training — DSBA generalized to the TPU 'pod' mesh axis.

The paper's setting maps 1:1 onto multi-pod training: each pod is a graph
node holding a data shard and its own model replica; pods exchange parameter
information with GRAPH NEIGHBORS ONLY (collective-permute over the 'pod'
axis — the ICI/DCI-native pattern) instead of a global all-reduce; and the
wire payload is a SPARSE (values, indices) difference stream, the fixed-size
SPMD adaptation of the paper's delta_n^t messages (DESIGN.md §5).

Modes
  allreduce  synchronous DP baseline (dense global reduction — what the
             paper's Table 1 calls 'dense communication')
  dsgd       single-mix gossip:  theta <- Adam(W~ theta, g)  — practical
             Adam-preconditioned decentralized SGD
  dsba       the paper's update structure, faithfully:
               theta^{t+1} = W~ (2 theta^t - theta^{t-1}) - lr (g_t - g_{t-1})
             i.e. eq. (28)'s double-mix + update-DIFFERENCE correction
             (with B_{n,i} = grad of the local loss, forward-evaluated —
             the exact resolvent needs invertible I + alpha*B, DESIGN.md §6;
             stacking Adam on top of the extrapolation compounds momentum
             and diverges — tested).
Compression ('topk')
  CHOCO-style (Koloskova et al. 2019) reconstruction gossip: each pod keeps
  a reconstruction theta_hat of every stream it hears (its own + each
  neighbor's), communicates only top-k(|theta - theta_hat|) as (values,
  int32 indices), and applies the consensus correction
      theta <- theta + gamma * sum_m w~_pm (theta_hat_m - theta_hat_p).
  The untransmitted remainder stays in theta - theta_hat and is retried
  next round (self-correcting residual — no separate error-feedback
  accumulator is needed, and adding one double-counts and diverges; see
  tests/test_gossip.py::test_reconstruction_residual_is_self_correcting).
  This preserves the paper's O(rho d) wire complexity for dense NN params
  where exact data-sparsity (the convex case) no longer holds.

Topologies: ring (1 hop) and exponential (hypercube-like, log P hops) —
both ppermute-only, scaling O(deg) not O(P): the 1000+ node design point.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import mixing as MX
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.params import tree_pspecs, tree_sds

from repro.optim.adam import adam_init, adam_update
from repro.train.step import TrainConfig, local_grads


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """Pod-axis decentralized-training setup: topology, mode, compression."""

    n_pods: int = 2
    topology: str = "ring"  # ring | exponential | allreduce
    mode: str = "dsba"  # dsba | dsgd | allreduce
    # none | topk (exact global top-k; O(n log n) select) |
    # block_topk (top-k_b per fixed block — linear-time, embarrassingly
    # parallel, the wire format of kernels/topk_compress.py; the choice for
    # 10^9+-element leaves)
    compression: str = "none"
    topk_ratio: float = 0.01
    block_size: int = 4096  # block_topk selection granularity
    # kernels/ops.py use_pallas mode for the block_topk selection
    kernel_mode: str = "auto"
    consensus_lr: float = 0.9  # CHOCO gamma
    seed: int = 0

    def graph_and_weights(self) -> tuple[MX.Graph, np.ndarray]:
        """Pod graph + Laplacian mixing matrix for this topology."""
        g, w = MX.make_pod_mixing(self.n_pods, self.topology
                                  if self.topology != "allreduce" else "ring",
                                  self.seed)
        return g, w

    def shifts_and_weights(self) -> tuple[list[int], list[float], float]:
        """Ring/exponential graphs are circulant: mixing = self-weight +
        symmetric shifts. Returns (shifts, per-shift weight, self-weight)."""
        g, w = self.graph_and_weights()
        wt = MX.w_tilde(w)
        if self.n_pods == 1:
            return [], [], 1.0
        row = wt[0]
        shifts, weights = [], []
        for s in range(1, self.n_pods // 2 + 1):
            if abs(row[s]) > 1e-12:
                shifts.append(s)
                weights.append(float(row[s]))
        return shifts, weights, float(row[0])


# ---------------------------------------------------------------------------
# top-k difference compression (jnp reference; kernels/topk_compress.py is the
# Pallas version) + reconstruction scatter
# ---------------------------------------------------------------------------

def topk_compress(x: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Flattened top-k by |value|: returns (values (k,), indices (k,) int32)."""
    flat = x.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def block_topk_compress(
    x: jax.Array, ratio: float, block: int, *, use_pallas: str = "auto"
) -> tuple[jax.Array, jax.Array]:
    """Block-local top-k: k_b = ratio*block entries per `block`-sized chunk.

    Linear-time selection (per-block), same fixed-size (values, GLOBAL idx)
    wire format as topk_compress. Selection dispatches through the
    kernels/ops.py registry ('block_topk'): the Pallas kernel on TPU, the
    lax.top_k oracle on CPU under 'auto'.
    """
    from repro.kernels.ops import topk_blocks

    n = x.size
    flat = x.reshape(-1)
    block = min(block, n)
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nb = flat.size // block
    k_b = max(1, int(block * ratio))
    rows = flat.reshape(nb, block)
    vals, li = topk_blocks(rows, k_b, use_pallas=use_pallas)  # (nb, k_b)
    gi = (li + (jnp.arange(nb) * block)[:, None]).astype(jnp.int32)
    # padded tail indices point past n; zero their values so scatter is a noop
    valid = gi < n
    vals = jnp.where(valid, vals, 0.0)
    gi = jnp.where(valid, gi, 0)
    return vals.reshape(-1), gi.reshape(-1)


def scatter_decompress(shape, vals: jax.Array, idx: jax.Array) -> jax.Array:
    """Inverse of the top-k wire format: scatter (vals, idx) into `shape`."""
    out = jnp.zeros((int(np.prod(shape)),), vals.dtype)
    return out.at[idx].add(vals).reshape(shape)


def leaf_k(leaf_shape, ratio: float) -> int:
    """Per-leaf top-k count for a compression ratio (at least 1)."""
    n = int(np.prod(leaf_shape))
    return max(1, int(n * ratio))


# ---------------------------------------------------------------------------
# gossip state
# ---------------------------------------------------------------------------

def gossip_state_defs(cfg: ModelConfig, tc: TrainConfig, gc: GossipConfig):
    """(sds, pspecs) for the gossip train state — leading 'pod' dim on all
    replicated-per-pod leaves."""
    defs = T.model_defs(cfg)
    p_sds = tree_sds(defs, cfg.param_dtype)
    p_spec = tree_pspecs(defs)
    pod = lambda s: jax.ShapeDtypeStruct((gc.n_pods, *s.shape), s.dtype)
    pod_spec = lambda sp: P("pod", *sp)
    sds = {"params": jax.tree_util.tree_map(pod, p_sds),
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    spec = {"params": jax.tree_util.tree_map(pod_spec, p_spec), "step": P()}

    st_dt = tc.optimizer.state_dtype
    opt_sds = {"mu": tree_sds(defs, st_dt)}
    opt_spec = {"mu": p_spec}
    if tc.optimizer.kind != "sgdm":
        opt_sds["nu"] = tree_sds(defs, st_dt)
        opt_spec["nu"] = p_spec
    sds["opt"] = jax.tree_util.tree_map(pod, opt_sds)
    spec["opt"] = jax.tree_util.tree_map(pod_spec, opt_spec)

    if gc.mode == "dsba":
        sds["params_prev"] = sds["params"]
        spec["params_prev"] = spec["params"]
        sds["g_prev"] = sds["params"]
        spec["g_prev"] = spec["params"]
    if gc.compression != "none":
        shifts, _, _ = gc.shifts_and_weights()
        n_streams = 1 + 2 * len(shifts)  # own + each neighbor direction
        rec = lambda s: jax.ShapeDtypeStruct(
            (gc.n_pods, n_streams, *s.shape), s.dtype
        )
        rec_spec = lambda sp: P("pod", None, *sp)
        sds["recon"] = jax.tree_util.tree_map(rec, p_sds)
        spec["recon"] = jax.tree_util.tree_map(rec_spec, p_spec)
    return sds, spec


def init_gossip_state(cfg: ModelConfig, tc: TrainConfig, gc: GossipConfig, key):
    """Materialize (small configs only). All pods start at consensus."""
    from repro.models.params import tree_materialize

    defs = T.model_defs(cfg)
    params0 = tree_materialize(defs, key, cfg.param_dtype)
    tile = lambda x: jnp.broadcast_to(x[None], (gc.n_pods, *x.shape)).copy()
    params = jax.tree_util.tree_map(tile, params0)
    opt = jax.tree_util.tree_map(tile, adam_init(tc.optimizer, params0))
    state = {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}
    if gc.mode == "dsba":
        state["params_prev"] = params
        state["g_prev"] = jax.tree_util.tree_map(jnp.zeros_like, params)
    if gc.compression != "none":
        shifts, _, _ = gc.shifts_and_weights()
        n_streams = 1 + 2 * len(shifts)
        state["recon"] = jax.tree_util.tree_map(
            lambda p: jnp.zeros((gc.n_pods, n_streams, *p.shape[1:]), p.dtype),
            params,
        )
    return state


# ---------------------------------------------------------------------------
# exchange primitives
#
# Two interchangeable backends with IDENTICAL semantics (tested equal):
#   spmd  — shard_map over 'pod' + lax.ppermute: blocks move between devices;
#           this is what the production mesh compiles (collective-permute
#           only — O(deg), never O(P)).
#   local — jnp.roll over the leading pod dim (single-device tests; also the
#           semantic reference: roll(x, s)[j] = x[j-s] == ppermute send
#           i -> i+s).
# ---------------------------------------------------------------------------

def _perm(shift: int, n: int):
    return [(i, (i + shift) % n) for i in range(n)]


def _shift_fns(mesh, n):
    if mesh is None:
        return lambda x, s: jnp.roll(x, s, axis=0)
    return lambda x, s: jax.lax.ppermute(x, "pod", _perm(s, n))


def make_dense_mix(mesh, gc: GossipConfig, leaf_specs):
    """tree -> tree: x_p <- w_self x_p + sum_shift w_s (x_{p-s} + x_{p+s})."""
    shifts, weights, w_self = gc.shifts_and_weights()
    n = gc.n_pods
    shift = _shift_fns(mesh, n)

    def body(tree):
        def mix_leaf(x):
            out = w_self * x
            for s, wgt in zip(shifts, weights):
                # circulant symmetry: antipodal shift on even rings appears
                # once in the row, so halve the double-count
                scale = wgt if (2 * s) % n else wgt / 2.0
                out = out + scale * (shift(x, s) + shift(x, -s))
            return out

        return jax.tree_util.tree_map(mix_leaf, tree)

    if mesh is None:
        return body
    full_specs = jax.tree_util.tree_map(lambda sp: P("pod", *sp), leaf_specs)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(full_specs,), out_specs=full_specs
    )


def make_topk_exchange(mesh, gc: GossipConfig, leaf_specs):
    """Compressed CHOCO exchange.

    Returns fn(source_tree, recon_tree) -> (correction_tree, new_recon_tree)
    where correction = gamma * sum_m w~_pm (theta_hat_m - theta_hat_p).
    Only the fixed-size top-k (values, int32 indices) streams move between
    pods. recon layout per leaf: (pods, streams, *shape): stream 0 = own
    broadcast reconstruction, then one per (shift, direction).
    """
    shifts, weights, w_self = gc.shifts_and_weights()
    n = gc.n_pods
    gamma = gc.consensus_lr
    shift = _shift_fns(mesh, n)

    def body(source, recon):
        # leading dim: n pods (local backend) or 1 (per-shard in shard_map).
        # Non-pod dims are SHARD-shaped inside shard_map, so the wire format
        # derives from the actual block shape: each device compresses its
        # own shard of every stream.
        def one(src, rec):
            shape = src.shape[1:]
            resid = (src - rec[:, 0]).astype(jnp.float32)
            if gc.compression == "block_topk":
                vals, idx = jax.vmap(
                    lambda r: block_topk_compress(r, gc.topk_ratio,
                                                  gc.block_size,
                                                  use_pallas=gc.kernel_mode)
                )(resid)
            else:
                k = leaf_k(shape, gc.topk_ratio)
                vals, idx = jax.vmap(lambda r: topk_compress(r, k))(resid)
            upd = jax.vmap(lambda v, i: scatter_decompress(shape, v, i))(
                vals, idx
            ).astype(src.dtype)
            new_rec0 = rec[:, 0] + upd
            new_rec = [new_rec0]
            corr = jnp.zeros(src.shape, jnp.float32)
            si = 1
            for s, wgt in zip(shifts, weights):
                scale = wgt if (2 * s) % n else wgt / 2.0
                for sign in (+1, -1):
                    v_in = shift(vals, sign * s)
                    i_in = shift(idx, sign * s)
                    inc = jax.vmap(
                        lambda v, i: scatter_decompress(shape, v, i)
                    )(v_in, i_in).astype(src.dtype)
                    rec_m = rec[:, si] + inc
                    new_rec.append(rec_m)
                    corr = corr + scale * (rec_m - new_rec0).astype(jnp.float32)
                    si += 1
            correction = (gamma * corr).astype(src.dtype)
            return correction, jnp.stack(new_rec, axis=1)

        flat_src, treedef = jax.tree_util.tree_flatten(source)
        flat_rec = treedef.flatten_up_to(recon)
        outs = [one(s_, r_) for s_, r_ in zip(flat_src, flat_rec)]
        corr = treedef.unflatten([o[0] for o in outs])
        new_rec = treedef.unflatten([o[1] for o in outs])
        return corr, new_rec

    if mesh is None:
        return body
    src_specs = jax.tree_util.tree_map(lambda sp: P("pod", *sp), leaf_specs)
    rec_specs = jax.tree_util.tree_map(lambda sp: P("pod", None, *sp), leaf_specs)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(src_specs, rec_specs),
        out_specs=(src_specs, rec_specs),
    )


# ---------------------------------------------------------------------------
# the decentralized train step
# ---------------------------------------------------------------------------

def make_gossip_train_step(mesh, cfg: ModelConfig, tc: TrainConfig,
                           gc: GossipConfig):
    """Returns a jit-able step(state, batch) for the multi-pod mesh.

    batch leaves carry a leading (n_pods,) dim sharded over 'pod'; per-pod
    compute is vmapped with spmd_axis_name='pod' so internal sharding
    constraints stay pod-local.
    """
    defs = T.model_defs(cfg)
    leaf_specs = tree_pspecs(defs)
    if mesh is not None:
        from repro.models.params import shardable_pspecs

        leaf_specs = shardable_pspecs(
            leaf_specs, tree_sds(defs, cfg.param_dtype), mesh
        )
    dense_mix = make_dense_mix(mesh, gc, leaf_specs)
    topk_ex = (
        make_topk_exchange(mesh, gc, leaf_specs)
        if gc.compression != "none"
        else None
    )

    vgrads = jax.vmap(
        lambda p, b: local_grads(cfg, tc, p, b),
        spmd_axis_name="pod" if mesh is not None else None,
    )
    vadam = jax.vmap(
        lambda p, g, o, s: adam_update(tc.optimizer, p, g, o, s),
        in_axes=(0, 0, 0, None),
        spmd_axis_name="pod" if mesh is not None else None,
    )

    def step(state, batch):
        tm = jax.tree_util.tree_map
        params = state["params"]
        losses, grads = vgrads(params, batch)
        new_state = dict(state)

        if gc.mode == "dsba":
            # paper eq. (28): double-mix + update-difference correction.
            # CONSTANT step size: the g_t - g_{t-1} telescoping assumes the
            # same alpha on both terms (a warmup schedule silently breaks
            # the recursion's fixed point — observed as consensus blow-up).
            lr = tc.optimizer.lr
            extrap = tm(
                lambda p, pp: (2.0 * p.astype(jnp.float32)
                               - pp.astype(jnp.float32)).astype(p.dtype),
                params, state["params_prev"],
            )
            if gc.compression == "none":
                mixed = dense_mix(extrap)
            else:
                corr, new_rec = topk_ex(extrap, state["recon"])
                mixed = tm(lambda e, c: e + c, extrap, corr)
                new_state["recon"] = new_rec
            new_params = tm(
                lambda m, g, gp: (
                    m.astype(jnp.float32)
                    - lr * (g.astype(jnp.float32) - gp.astype(jnp.float32))
                ).astype(m.dtype),
                mixed, grads, state["g_prev"],
            )
            new_state["params_prev"] = params
            new_state["g_prev"] = tm(lambda g, p: g.astype(p.dtype),
                                     grads, params)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(grads)
            ))
            new_state["params"] = new_params
            new_state["step"] = state["step"] + 1
            return new_state, {"loss": losses.mean(), "grad_norm": gnorm}

        if gc.mode == "allreduce":
            grads = tm(
                lambda g: jnp.broadcast_to(
                    jnp.mean(g, axis=0, keepdims=True), g.shape
                ),
                grads,
            )
            mix_src = params
        else:  # dsgd
            mix_src = dense_mix(params) if gc.compression == "none" else params

        new_params, new_opt, metrics = vadam(
            mix_src, grads, state["opt"], state["step"]
        )
        if gc.compression != "none" and gc.mode == "dsgd":
            corr, new_rec = topk_ex(new_params, state["recon"])
            new_params = tm(lambda p, c: p + c, new_params, corr)
            new_state["recon"] = new_rec

        new_state["params"] = new_params
        new_state["opt"] = new_opt
        new_state["step"] = state["step"] + 1
        out_metrics = {
            "loss": losses.mean(),
            "grad_norm": metrics["grad_norm"].mean(),
        }
        return new_state, out_metrics

    return step


def gossip_batch_specs(cfg: ModelConfig) -> dict:
    """PartitionSpecs of the per-pod batch dict (pod axis leads)."""
    spec = {"tokens": P("pod", "data"), "targets": P("pod", "data")}
    if cfg.family == "encdec":
        spec["enc_embeds"] = P("pod", "data", None, None)
    return spec


def consensus_distance(params) -> jax.Array:
    """mean_p ||theta_p - theta_bar||^2 over the pod axis (diagnostics)."""
    def leaf(p):
        pb = p.mean(0, keepdims=True)
        return jnp.sum((p.astype(jnp.float32) - pb.astype(jnp.float32)) ** 2)

    return sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(leaf, params)))
