"""Capture a profiler trace of the measured window and reduce it to numbers.

The trace is read with ``jax.profiler.ProfileData`` into a small normal
form, kept as plain lists so that a recorded trace can be stored as JSON
(``tests/data``) and reduced by the same code:

    {"window": [t0, t1],                       # ns, the bench.window span
     "ops": {device: [[start, dur, name], ...]},   # "XLA Ops" lines
     "modules": {device: [[start, dur, name], ...]},       # "XLA Modules"
     "host": [[start, dur, name], ...]}        # bench.* host spans

Device ops are what ran on the chip, each named by its HLO instruction
text (``%fusion.12 = f32[8]{0} fusion(...)``). Host spans are the benchmark's own
``TraceAnnotation`` spans around its calls into each layer, on the same
clock as the device ops.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import shutil
from pathlib import Path

WINDOW = "bench.window"  # the host span around the measured window
SPAN_PREFIX = "bench."


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------


class Spans:
    """Host spans around the benchmark's calls into the program.

    With ``on`` the spans are ``jax.profiler.TraceAnnotation`` events in the
    trace; without it they cost one attribute lookup.
    """

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax

            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        return self._ann(SPAN_PREFIX + name)


@contextlib.contextmanager
def captured(directory: Path):
    """Profile the enclosed block into ``directory`` (emptied first)."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True)
    jax.profiler.start_trace(str(directory))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load_xplane(directory: Path) -> dict:
    """Read the newest ``.xplane.pb`` under ``directory`` into normal form."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        str(directory), "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = ProfileData.from_file(files[-1])
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = plane.name.split("/device:")[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(dev, []).extend(
                        [ev.start_ns, ev.duration_ns, ev.name]
                        for ev in line.events
                    )
                elif line.name == "XLA Modules":
                    modules.setdefault(dev, []).extend(
                        [ev.start_ns, ev.duration_ns, ev.name]
                        for ev in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [ev.start_ns, ev.duration_ns, ev.name]
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX)
                )
    win = [h for h in host if h[2] == WINDOW]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    t0, dur = win[0][0], win[0][1]
    return {"window": [t0, t0 + dur], "ops": ops, "modules": modules,
            "host": sorted(h for h in host if h[2] != WINDOW)}


def save(trace: dict, path: Path) -> None:
    """Write a normal-form trace as gzipped JSON."""
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: Path) -> dict:
    """Read a normal-form trace written by ``save``."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def _clip(events, t0: float, t1: float):
    """(start, end) of each event, clipped to [t0, t1]; empty ones dropped."""
    out = []
    for ev in events:
        s, e = max(ev[0], t0), min(ev[0] + ev[1], t1)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted union of (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def window_s(trace: dict) -> float:
    """Length of the traced window in seconds."""
    t0, t1 = trace["window"]
    return (t1 - t0) * 1e-9


def busy_s(trace: dict) -> float:
    """Seconds in which an op ran on the device, averaged over the devices."""
    t0, t1 = trace["window"]
    devs = trace["ops"]
    if not devs:
        return 0.0
    total = 0.0
    for evs in devs.values():
        total += sum(e - s for s, e in union(_clip(evs, t0, t1)))
    return total / len(devs) * 1e-9


def idle_share(trace: dict) -> float | None:
    """1 - busy / window, in percent; None for an empty window."""
    w = window_s(trace)
    if w <= 0 or not trace["ops"]:
        return None
    return 100.0 * (1.0 - busy_s(trace) / w)


def matching(trace: dict, pred) -> list:
    """Op events inside the window whose name satisfies ``pred``, over
    every device."""
    t0, t1 = trace["window"]
    return [ev for evs in trace["ops"].values() for ev in evs
            if ev[0] >= t0 and ev[0] + ev[1] <= t1 and pred(ev[2])]


def module_events(trace: dict, pred) -> list:
    """Program (XLA module) events inside the window whose name matches."""
    t0, t1 = trace["window"]
    return [ev for evs in trace["modules"].values() for ev in evs
            if ev[0] >= t0 and ev[0] + ev[1] <= t1 and pred(ev[2])]


def seconds(events) -> float:
    """Summed duration of events, in seconds."""
    return sum(ev[1] for ev in events) * 1e-9


CONTAINERS = ("while", "conditional", "call")  # ops that hold other ops


def op_name(text: str) -> str:
    """The HLO instruction name of an op event: ``fusion.12`` from
    ``%fusion.12 = f32[8]{0} fusion(...)``; other names pass unchanged."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_base(text: str) -> str:
    """An op's instruction name without its instance number."""
    head, _, tail = op_name(text).rpartition(".")
    return head if head and tail.isdigit() else op_name(text)


def op_label(text: str) -> str:
    """Instruction name and result shape, e.g. ``fusion.12 f32[8]``."""
    name = op_name(text)
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {shape}".strip()[:80]


def is_leaf(text: str) -> bool:
    """True for an op that holds no other ops (not a loop or a call)."""
    return op_base(text) not in CONTAINERS


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """The n ops that took most device time in the window, with their
    seconds averaged over the devices. Loops and calls, whose events span
    the ops inside them, are left out."""
    ndev = max(len(trace["ops"]), 1)
    tot: dict[str, float] = {}
    for ev in matching(trace, is_leaf):
        key = op_label(ev[2])
        tot[key] = tot.get(key, 0.0) + ev[1] * 1e-9 / ndev
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> list[list]:
    """Device idle time in the window, summed by what the host was doing.

    Each gap between busy intervals (of the first device) is split over
    the innermost host spans that cover it; time no span covers counts as
    ``host.other``. Returns the n largest [activity, seconds].
    """
    t0, t1 = trace["window"]
    if not trace["ops"]:
        return []
    dev = sorted(trace["ops"])[0]
    busy = union(_clip(trace["ops"][dev], t0, t1))
    gaps, prev = [], t0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if t1 > prev:
        gaps.append((prev, t1))
    # innermost span = the latest-starting one that covers a point; cut
    # the gaps at every span edge and attribute each piece by its midpoint
    spans = [(h[0], h[0] + h[1], h[2][len(SPAN_PREFIX):])
             for h in trace["host"]]
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    tot: dict[str, float] = {}
    import bisect

    for gs, ge in gaps:
        cuts = [gs] + edges[bisect.bisect_right(edges, gs):
                            bisect.bisect_left(edges, ge)] + [ge]
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [sp for sp in spans if sp[0] <= mid < sp[1]]
            name = max(cover)[2] if cover else "host.other"
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
