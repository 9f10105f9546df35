"""Readings of a serving cell, seed after seed or rate after rate, in one
process: what a cell's limits, bounds and rate are set from. The
benchmark's own runs do not run this.

    python -m chipbench.readings --workload <cell> --seconds <s> --seeds 1 2 3 [--controls 1]
    python -m chipbench.readings --workload <cell> --seconds <s> --rates 1 2 3

With ``--seeds``, for each seed it sets up the cell as ``chipbench.run``
does (the system its configuration names), measures a window, reads the
end-to-end numbers, the peak device memory and the checks, and for the
first ``--controls`` seeds the fp8 control (the system's
``check(..., quant="fp8")``). With ``--rates`` it is the knee sweep of
``sweep.py`` for any serving system: the same seed at each fixed rate,
with the queue depth at the window's first and last step (the knee is the
highest rate at which the queue does not grow through the window). One
JSON line per reading goes to standard output.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--rates", type=float, nargs="+")
    ap.add_argument("--seed", type=int, default=1, help="the sweep's seed")
    ap.add_argument("--drain", type=float, default=None,
                    help="drain limit in s (the sweep's is 15; default the "
                    "traffic's)")
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, cfg, traffic, limits = run.cell_files(bench, args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(run.CACHE_DIR))
    sys.path.insert(0, str(run.ROOT / "src"))
    why = run.refusal(cell["chips"])
    if why:
        print(f"chipbench.readings: refusing to run: {why}", file=sys.stderr)
        return 2
    import jax

    from chipbench import tracing

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()[: cell["chips"]]
    mod = importlib.import_module(f"chipbench.systems.{cfg['system']}")

    def emit(rec):
        print(json.dumps(rec), flush=True)

    for rate in args.rates or ():
        system = mod.System(cfg, dict(traffic, drain_limit_s=args.drain or 15),
                            args.seed,
                            devices, tracing.Spans(False), rate=rate)
        system.warm()
        system.run_window(args.seconds)
        system.finish()
        c = system.counters()
        emit({"rate_rps": rate, **system.end_to_end(),
              **{k: c[k] for k in ("requests_due", "active_mean",
                                   "queue_first", "queue_last", "queue_max",
                                   "prefills", "steps", "preempted",
                                   "report")}})
        system.release()
        del system
    for i, seed in enumerate(args.seeds or ()):
        t0 = time.perf_counter()
        tr = traffic if args.drain is None else dict(traffic,
                                                     drain_limit_s=args.drain)
        system = mod.System(cfg, tr, seed, devices, tracing.Spans(False))
        system.warm()
        setup_s = time.perf_counter() - t0
        system.run_window(args.seconds)
        system.finish()
        c = system.counters()
        e2e = system.end_to_end()
        peak = run.memory_peak(devices)
        system.release()
        checks, attempted, failed = system.check(limits)
        emit({"kind": "sound", "seed": seed, "setup_s": setup_s, **e2e,
              "memory_peak_bytes": peak,
              "correct": run.verdict(checks, failed), "checks": checks,
              "attempted": attempted, "failed": failed,
              "checked_tokens": system.checked_tokens,
              "gaps": getattr(system, "gap_report", None),
              "seconds": time.perf_counter() - t0, "report": c["report"],
              **{k: c[k] for k in ("active_mean", "queue_first",
                                   "queue_last", "queue_max")}})
        if i < args.controls:
            t1 = time.perf_counter()
            checks, _, failed = system.check(limits, quant="fp8")
            emit({"kind": "control", "seed": seed,
                  "correct": run.verdict(checks, failed), "checks": checks,
                  "gaps": getattr(system, "gap_report", None),
                  "seconds": time.perf_counter() - t1})
        del system
    return 0


if __name__ == "__main__":
    sys.exit(main())
