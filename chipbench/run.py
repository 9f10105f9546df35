"""Run one benchmark cell on the chips of this machine and print its result.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (an entry of ``workloads`` in
``BENCHMARK.json``) names a configuration (``chipbench/configs/<name>.json``)
and a traffic mix (``chipbench/traffic/<name>.json``); the configuration
names the system that drives it (``chipbench/systems/<system>.py``), and
``chipbench/cells/<cell>.json`` holds the limits of the cell's correctness
check. Per-layer metrics are read by ``chipbench/metrics/<metric>.py``.

The run sets up (inputs and weights from ``--seed``, every shape warmed),
measures a window of ``--seconds`` (with ``--trace 1``: the traffic's
shorter traced window, profiled), reads the peak device memory, frees the
program's state, checks what the window produced against the plain
reference, and prints as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``. It refuses to run
(exit 2, no result) when JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_compile_cache"
TRACE_DIR = ROOT / ".chipbench_trace"


def load_json(path: Path) -> dict:
    """Read one JSON file of the benchmark."""
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict, dict]:
    """(cell entry, configuration, traffic mix, limits) of a workload."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(HERE / "cells" / f"{workload}.json")
    return cell, cfg, traffic, limits


def reported(bench: dict, workload: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_reader(name: str):
    """The ``read(obs)`` function of one per-layer metric's reader file."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Observation:
    """What a per-layer metric's reader may read."""

    def __init__(self, trace, counters, peaks, chips):
        self.trace = trace  # normal-form trace of the window (tracing.py)
        self.counters = counters  # the system's counters of the window
        self.peaks = peaks  # peaks.Peaks of the device
        self.chips = chips


def refusal(chips: int) -> str | None:
    """Why this machine cannot run a cell on ``chips`` chips, or None."""
    if importlib.util.find_spec("repro") is None:
        return f"the program is not in {ROOT / 'src'}"
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return (f"JAX found no TPU (devices()[0] is {devs[0].platform!r}); "
                "the benchmark measures the chip only")
    if len(devs) < chips:
        return f"the cell asks for {chips} chips, JAX found {len(devs)}"
    return None


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def verdict(checks: dict, failed: int) -> bool:
    """``correct``: nothing failed and every number within its limit."""
    return failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The contract's last line, ``checks`` as its last key."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def run_cell(workload: str, cell: dict, cfg: dict, traffic: dict,
             limits: dict, e2e: list[dict], layer: list[dict], seed: int,
             seconds: float, trace: bool, devices, t_start: float) -> dict:
    """Set up, measure, check; print the checks and return the result."""
    import jax

    from chipbench import tracing
    from chipbench.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind) if trace else None
    spans = tracing.Spans(trace)
    system_mod = importlib.import_module(f"chipbench.systems.{cfg['system']}")
    system = system_mod.System(cfg, traffic, seed, devices, spans)
    system.warm()
    setup_s = time.perf_counter() - t_start - system.reference_setup_s

    tr = None
    if trace:
        TRACE_DIR.mkdir(exist_ok=True)
        tdir = TRACE_DIR / workload
        with tracing.captured(tdir):
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                system.run_window(min(seconds, traffic["trace_seconds"]))
        system.finish()
        tr = tracing.load_xplane(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        system.run_window(seconds)
        system.finish()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "device_kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    counters = system.counters()
    metrics = {}
    breakdown = None
    if trace:
        obs = Observation(tr, counters, peaks, cell["chips"])
        for m in layer:
            value = load_reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = tracing.busy_s(tr)
        device["window_s"] = tracing.window_s(tr)
        breakdown = {"device_ops": tracing.top_ops(tr, 10),
                     "idle_gaps": tracing.idle_gaps(tr, 10)}
    else:
        values = dict(system.end_to_end(), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    for title, numbers in counters.get("report", {}).items():
        print(f"{title}: " + ", ".join(f"{k} {v!r}" for k, v in
                                      numbers.items()), file=sys.stderr)

    system.release()
    checks, attempted, failed = system.check(limits)
    correct = verdict(checks, failed)
    print(f"attempted {attempted}, failed {failed}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, "checks": checks,
            "breakdown": breakdown}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell, cfg, traffic, limits = cell_files(bench, args.workload)
    e2e, layer = reported(bench, args.workload)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    why = refusal(cell["chips"])
    if why:
        print(f"chipbench: refusing to run: {why}", file=sys.stderr)
        return 2

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    res = run_cell(args.workload, cell, cfg, traffic, limits, e2e, layer,
                   args.seed, args.seconds, bool(args.trace),
                   jax.devices()[: cell["chips"]], T_START)
    print(result_line(res["correct"], res["attempted"], res["failed"],
                      res["metrics"], res["device"], res["checks"],
                      res["breakdown"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
