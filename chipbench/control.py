"""Readings that set a cell's correctness limits: the sound program and
its control, seed after seed in one process. The benchmark's own runs do
not run this.

    python -m chipbench.control --workload <cell> --seconds <s> --seeds 1 2 3

For each seed it sets up the cell as ``chipbench.run`` does, measures a
window of ``--seconds``, and checks it; then it reads the control:
  dsba_solve  with ``--control program``: the program on the same
              deployment in bfloat16, the precision below the float32 it
              states (its own path: the dense exchange takes bf16 data),
              checked against the same float64 references; with
              ``--control reference``: the plain reference put in the
              program's place and computed in bfloat16 (for the relay,
              whose compiled kernel does not compile for bf16 data);
  serve_lm    at the same prompts and served tokens, the float32
              reference's gap of the token that the model computed in
              float8 (the precision below bf16) puts first.
One JSON line per reading goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control on the first this many seeds")
    ap.add_argument("--control", choices=("program", "reference"),
                    default="program", help="the solver cells' control")
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, cfg, traffic, limits = run.cell_files(bench, args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(run.CACHE_DIR))
    sys.path.insert(0, str(run.ROOT / "src"))
    why = run.refusal(cell["chips"])
    if why:
        print(f"chipbench.control: refusing to run: {why}", file=sys.stderr)
        return 2
    import importlib

    import jax

    from chipbench import tracing

    devices = jax.devices()[: cell["chips"]]
    mod = importlib.import_module(f"chipbench.systems.{cfg['system']}")

    def emit(rec):
        print(json.dumps(rec), flush=True)

    def reading(seed, controls=False, **kw):
        t0 = time.perf_counter()
        system = mod.System(cfg, traffic, seed, devices, tracing.Spans(False),
                            **kw)
        system.warm()
        system.run_window(args.seconds)
        system.finish()
        ctr = system.counters()
        system.release()
        checks, attempted, failed = system.check(limits)
        emit({"kind": "control" if kw else "sound", "seed": seed,
              "correct": run.verdict(checks, failed), "checks": checks,
              "attempted": attempted, "failed": failed,
              "seconds": time.perf_counter() - t0,
              "window_s": ctr["window_s"]})
        if controls and cfg["system"] == "serve_lm":
            t0 = time.perf_counter()
            checks, _, failed = system.check(limits, quant="fp8")
            emit({"kind": "control", "seed": seed,
                  "correct": run.verdict(checks, failed), "checks": checks,
                  "checked_tokens": system.checked_tokens,
                  "seconds": time.perf_counter() - t0})

    def reference_reading(seed):
        t0 = time.perf_counter()
        system = mod.System(cfg, traffic, seed, devices, tracing.Spans(False))
        checks = system.reference_control(limits, "bfloat16")
        return {"seed": seed, "checks": checks,
                "seconds": time.perf_counter() - t0}

    for i, seed in enumerate(args.seeds):
        reading(seed, controls=i < args.controls)
        if cfg["system"] == "dsba_solve" and i < args.controls:
            if args.control == "program":
                reading(seed, dtype="bfloat16")
            else:
                emit({"kind": "control_reference", **reference_reading(seed)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
