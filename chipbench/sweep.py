"""The knee of a serving cell: the same traffic at several fixed rates.

    python -m chipbench.sweep --workload <cell> --seconds <s> --rates 3 4 5

For each rate it sets the cell up afresh (same seed), runs the pre-roll
and a window of ``--seconds``, and prints one JSON line with the end-to-end
numbers and the queue depth at the window's first and last step. The knee
is the highest rate at which the queue does not grow through the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from chipbench import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell, cfg, traffic, _ = run.cell_files(bench, args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(run.CACHE_DIR))
    sys.path.insert(0, str(run.ROOT / "src"))
    why = run.refusal(cell["chips"])
    if why:
        print(f"chipbench.sweep: refusing to run: {why}", file=sys.stderr)
        return 2
    import jax

    from chipbench import tracing
    from chipbench.systems import serve_lm

    traffic = dict(traffic, drain_limit_s=15)  # an overloaded point drains
    for rate in args.rates:
        system = serve_lm.System(cfg, traffic, args.seed,
                                 jax.devices()[: cell["chips"]],
                                 tracing.Spans(False), rate=rate)
        system.warm()
        system.run_window(args.seconds)
        system.finish()
        c = system.counters()
        rec = {"rate_rps": rate, **system.end_to_end(),
               **{k: c[k] for k in ("requests_due", "active_mean",
                                    "queue_first", "queue_last", "queue_max",
                                    "prefills", "steps", "preempted",
                                    "report")}}
        system.release()
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
