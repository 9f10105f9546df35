import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we AOT-lower the real step function (train_step / serve_step)
with ShapeDtypeStruct inputs (zero allocation), compile it for the
production mesh, and record:

  memory_analysis()   -> bytes per device (proves fit / measures overflow)
  cost_analysis()     -> per-device HLO FLOPs + bytes (roofline terms)
  HLO collective scan -> per-device collective bytes by op (roofline term 3)

Single-pod mesh = (16, 16) ('data','model'); multi-pod = (2, 16, 16) with
the 'pod' axis running the paper's decentralized gossip step (train) or
pod-sharded batch (serve). Results land in experiments/dryrun/*.json.

Usage:
  python -m repro.launch.dryrun --arch minitron-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every cell (slow)
  python -m repro.launch.dryrun --all --mesh multi
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import numpy as np

from repro.configs import ALIASES, get_config, list_archs
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cells_for, input_specs, batch_axes_for
from repro.models import transformer as T
from repro.models.config import ModelConfig

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _serve_fn(cfg: ModelConfig):
    def serve_step(params, tokens, cache):
        return T.decode_step(cfg, params, tokens, cache)

    return serve_step


def build_cell(cfg: ModelConfig, shape_name: str, mesh, multi_pod: bool,
               gossip_kw: dict | None = None, microbatches: int = 1):
    """Returns (jitted_fn, example_args_sds) ready to .lower()."""
    from jax.sharding import NamedSharding

    from repro.core.gossip import (
        GossipConfig, gossip_batch_specs, gossip_state_defs,
        make_gossip_train_step,
    )
    from repro.train.step import (
        TrainConfig, make_train_state_defs, train_step,
    )

    from repro.models.params import shardable_pspecs

    shape = SHAPES[shape_name]
    ns = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree
    )
    fix = lambda spec, sds: shardable_pspecs(spec, sds, mesh)

    if shape.kind == "train":
        tc = TrainConfig(batch_axes=batch_axes_for(shape.batch, mesh),
                         microbatches=microbatches)
        args, arg_specs = input_specs(cfg, shape, mesh)
        if multi_pod:
            # the paper's feature: decentralized DSBA gossip over 'pod'
            gkw = {"mode": "dsba", **(gossip_kw or {})}
            gc = GossipConfig(n_pods=mesh.shape["pod"], **gkw)
            state_sds, state_spec = gossip_state_defs(cfg, tc, gc)
            state_spec = fix(state_spec, state_sds)
            # batch gets a leading pod dim
            pods = mesh.shape["pod"]
            bsds = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    (pods, s.shape[0] // pods, *s.shape[1:]), s.dtype
                ),
                args,
            )
            bspec = gossip_batch_specs(cfg)
            step = make_gossip_train_step(mesh, cfg, tc, gc)
            fn = jax.jit(
                step,
                in_shardings=(ns(state_spec), ns(bspec)),
                out_shardings=(ns(state_spec), None),
                donate_argnums=(0,),
            )
            return fn, (state_sds, bsds)
        state_sds, state_spec = make_train_state_defs(cfg, tc)
        state_spec = fix(state_spec, state_sds)
        fn = jax.jit(
            lambda st, b: train_step(cfg, tc, st, b),
            in_shardings=(ns(state_spec), ns(arg_specs)),
            out_shardings=(ns(state_spec), None),
            donate_argnums=(0,),
        )
        return fn, (state_sds, args)

    # serve (prefill or decode)
    from repro.models.params import tree_pspecs, tree_sds

    defs = T.model_defs(cfg)
    p_sds = tree_sds(defs, cfg.param_dtype)
    p_spec = fix(tree_pspecs(defs), p_sds)
    args, arg_specs = input_specs(cfg, shape, mesh)
    cache_spec = fix(arg_specs["cache"], args["cache"])
    fn = jax.jit(
        _serve_fn(cfg),
        in_shardings=(ns(p_spec), ns(arg_specs["tokens"]), ns(cache_spec)),
        out_shardings=None,
        donate_argnums=(2,),
    )
    return fn, (p_sds, args["tokens"], args["cache"])


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None,
             gossip_kw: dict | None = None,
             hlo_path: pathlib.Path | None = None,
             microbatches: int = 1) -> dict:
    """Lower + compile the cell; account costs with loop-trip multiplication.

    XLA's cost_analysis counts a `while` (lax.scan) body ONCE, not
    trip_count times, so a scanned-L-layer model under-reports flops/bytes/
    collectives by ~L x. hlo_analysis.program_costs walks the optimized
    HLO's call graph with loop trip counts and accumulates per-instruction
    costs at true execution multiplicity (validated in
    tests/test_hlo_analysis.py). memory_analysis needs no correction
    (while-loop buffers are allocated per iteration, sized correctly).

    overrides: ModelConfig field overrides for §Perf hillclimb variants.
    """
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    rec: dict = {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    from repro.models.layers import use_constraint_mesh

    overrides_act = {"embed": "model"} if cfg.shard_residual_embed else None
    t0 = time.time()
    try:
        with mesh, use_constraint_mesh(mesh, overrides_act):
            fn, sds_args = build_cell(cfg, shape_name, mesh, multi_pod,
                                      gossip_kw, microbatches)
            lowered = fn.lower(*sds_args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            print(compiled.memory_analysis())  # proves it fits
            cost = compiled.cost_analysis()
            print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
            hlo_text = compiled.as_text()
            if hlo_path is not None:
                import zstandard

                hlo_path.write_bytes(
                    zstandard.ZstdCompressor(level=6).compress(
                        hlo_text.encode()
                    )
                )
            pc = H.program_costs(hlo_text)
        shape = SHAPES[shape_name]
        mf = H.model_flops(cfg, shape.kind, shape.batch, shape.seq)
        cost_x = {"flops": pc.flops, "bytes accessed": pc.bytes}
        colls_x = H.CollectiveStats(
            dict(pc.coll_bytes_by_op), dict(pc.coll_count_by_op)
        )
        rec["xla_cost_analysis"] = {  # uncorrected, for reference
            "hlo_flops": float(cost.get("flops", 0.0)),
            "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        }
        rl = H.roofline_terms(cost_x, colls_x, chips, mf)
        rec.update(
            ok=True,
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            },
            hlo_flops=rl.hlo_flops,
            hlo_bytes=rl.hlo_bytes,
            collective_bytes=rl.collective_bytes,
            collectives={"bytes": colls_x.bytes_by_op,
                         "count": colls_x.count_by_op},
            model_flops=mf,
            roofline={
                "compute_s": rl.compute_s,
                "memory_s": rl.memory_s,
                "collective_s": rl.collective_s,
                "dominant": rl.dominant,
                "useful_flop_ratio": rl.useful_flop_ratio,
                "roofline_fraction": rl.roofline_fraction,
            },
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def cell_list(archs, shapes, meshes):
    cells = []
    for arch in archs:
        cfg = get_config(arch)
        names = cells_for(cfg) if shapes is None else shapes
        for s in names:
            if s not in cells_for(cfg):
                continue
            for m in meshes:
                cells.append((arch, s, m == "multi"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--tag", default="", help="suffix for experiment variants")
    ap.add_argument(
        "--set", nargs="*", default=[], metavar="FIELD=VALUE",
        help="ModelConfig overrides for perf variants, e.g. "
             "blockwise_attention=True remat=dots",
    )
    ap.add_argument("--gossip-mode", default=None,
                    choices=["dsba", "dsgd", "allreduce"])
    ap.add_argument("--gossip-compression", default=None,
                    choices=["none", "topk", "block_topk"])
    ap.add_argument("--gossip-topk-ratio", type=float, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    import ast

    overrides = {}
    for kv in args.set:
        key, val = kv.split("=", 1)
        try:
            overrides[key] = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            overrides[key] = val

    gossip_kw = {}
    if args.gossip_mode:
        gossip_kw["mode"] = args.gossip_mode
    if args.gossip_compression:
        gossip_kw["compression"] = args.gossip_compression
    if args.gossip_topk_ratio is not None:
        gossip_kw["topk_ratio"] = args.gossip_topk_ratio

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = None if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    cells = cell_list(archs, shapes, meshes)
    print(f"{len(cells)} cells to run")
    for arch, shape, multi in cells:
        aid = ALIASES.get(arch, arch)
        tag = f"_{args.tag}" if args.tag else ""
        path = out / f"{aid}_{shape}_{'multi' if multi else 'single'}{tag}.json"
        if path.exists() and not args.force:
            print(f"skip (cached): {path.name}")
            continue
        print(f"=== {arch} x {shape} x {'multi' if multi else 'single'} "
              f"{overrides or ''} ===", flush=True)
        rec = run_cell(arch, shape, multi, overrides, gossip_kw,
                       hlo_path=path.with_suffix(".hlo.zst"),
                       microbatches=args.microbatches)
        if overrides or gossip_kw or args.microbatches > 1:
            rec["overrides"] = {k: str(v) for k, v in overrides.items()}
            rec["gossip"] = {k: str(v) for k, v in gossip_kw.items()}
            rec["microbatches"] = args.microbatches
        path.write_text(json.dumps(rec, indent=2, default=str))
        status = "OK" if rec.get("ok") else f"FAIL: {rec.get('error')}"
        print(f"--> {status} ({rec['total_s']}s)", flush=True)


if __name__ == "__main__":
    main()
