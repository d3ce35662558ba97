"""Reduce a JAX profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` the profiler wrote into plain planes:
``[{"name": str, "lines": [{"name": str, "events": [(name, start_ns,
duration_ns), ...]}]}]``.  ``reduce`` works on that form only, so the
tests can hand it a synthetic trace.

Device planes are the planes named ``/device:<kind>:<n>`` other than
the CPU and the runtime's ``/device:CUSTOM:...`` planes.  On each, the
ops are the events of its ``XLA Ops`` line (all its lines when it has
none).  An op event on a TPU is named by its HLO instruction
(``%bitunpack.1 = s32[480,128] custom-call(...)``); ``load`` keeps the
instruction's name (``bitunpack.1``).

Host spans are the events of every line (thread) of a ``/host:`` plane
but the Python tracer's function calls (named ``$<file>:<line> <fn>``):
the benchmark's ``TraceAnnotation``s, any the program opens, and the
runtime's own.  They are kept by name, so a span that a later change
opens and the metric reader that reads it need nothing here.  The
``window`` span bounds the measured window; only what lies inside it
counts.

Busy time is the union of the op intervals on a device, averaged over
the devices (0, with no device plane, as in a CPU run).  An idle gap is
a stretch of the window in which no op of a device runs; it is labelled
by the innermost span open at its midpoint on a line that runs Python
code (one with Python tracer calls, or the ``window`` span's own), so
that what the program was doing names the gap, and no runtime thread
idling beside it; ``(no span)`` when none is open.
"""

from __future__ import annotations

import heapq
import pathlib
import re
from collections import defaultdict

WINDOW = "window"
PY_CALL = "$"  # the Python tracer names a function call "$<file>:<line> <fn>"
NO_SPAN = "(no span)"
OPS_LINE = "XLA Ops"
TOP = 10
DEVICE_PLANE = re.compile(r"^/device:(?!CPU:|CUSTOM:)[A-Za-z]+:\d+$")
HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``; other names
    as they are."""
    m = HLO_NAME.match(name)
    return m.group(1) if m else name


def load(trace_dir: pathlib.Path) -> list[dict]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(op_name(e.name), float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(name: str) -> bool:
    return bool(DEVICE_PLANE.match(name))


def device_ops(plane: dict) -> list[tuple]:
    lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE] \
        or plane["lines"]
    return [ev for ln in lines for ev in ln["events"] if ev[2] > 0]


def host_spans(planes: list[dict]) -> list[tuple]:
    """(name, start_ns, end_ns, python) of every span on the host;
    ``python`` says whether its line runs Python code."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            evs = [(n, s, s + d) for n, s, d in ln["events"]
                   if not n.startswith(PY_CALL)]
            py = len(evs) < len(ln["events"]) \
                or any(n == WINDOW for n, _, _ in evs)
            out += [(n, s, e, py) for n, s, e in evs]
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) that ``busy`` (disjoint, sorted) leaves
    uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: list[tuple], points: list[float]) -> list[str]:
    """For each of the ascending ``points``, the name of the innermost
    (latest-starting) span of ``spans`` — (name, start, end), sorted by
    start — open at it, or ``NO_SPAN``."""
    out: list[str] = []
    heap: list[tuple] = []
    i = 0
    for t in points:
        while i < len(spans) and spans[i][1] <= t:
            name, s, e = spans[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= t:  # points ascend: it stays closed
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else NO_SPAN)
    return out


def reduce(planes: list[dict]) -> dict:
    """Busy and idle time, op time by name, idle gaps by host span, and
    the host spans' durations, all inside the ``window`` span."""
    spans = host_spans(planes)
    windows = [(s, e) for n, s, e, _ in spans if n == WINDOW]
    if not windows:
        raise ValueError("trace has no 'window' span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_ns = hi - lo
    devs = [p for p in planes if is_device_plane(p["name"])]
    inside = sorted((sp for sp in spans if sp[2] > lo and sp[1] < hi
                     and sp[0] != WINDOW), key=lambda sp: sp[1])

    op_ns: dict[str, float] = defaultdict(float)
    op_n: dict[str, int] = defaultdict(int)
    busy_total = 0.0
    idle: list[tuple[float, float]] = []  # (midpoint, length) of each gap
    for plane in devs:
        ivs = []
        for name, s, d in device_ops(plane):
            c = clip([(s, s + d)], lo, hi)
            if not c:
                continue
            op_ns[name] += c[0][1] - c[0][0]
            op_n[name] += 1
            ivs.append(c[0])
        busy = union(ivs)
        busy_total += sum(e - s for s, e in busy)
        idle += [((s + e) / 2, e - s) for s, e in gaps(busy, lo, hi)]
    idle.sort()
    gap_ns: dict[str, float] = defaultdict(float)
    labellers = [(n, s, e) for n, s, e, py in inside if py]
    for label, (_, length) in zip(
            innermost(labellers, [t for t, _ in idle]), idle):
        gap_ns[label] += length

    n_dev = max(len(devs), 1)  # no device plane: nothing ran on one
    durations: dict[str, list[float]] = defaultdict(list)
    for name, s, e, _ in inside:
        durations[name].append((e - s) / 1e9)
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "devices": len(devs),
        "ops": {k: {"seconds": v / n_dev / 1e9, "count": op_n[k]}
                for k, v in op_ns.items()},
        "top_ops": [[k, v / n_dev / 1e9] for k, v in top_ops],
        "top_gaps": [[k, v / n_dev / 1e9] for k, v in top_gaps],
        "spans": dict(durations),
    }


def op_seconds(red: dict, kernel: str) -> tuple[float, int]:
    """Summed device time and event count of the ops that a kernel named
    ``kernel`` shows up as: its name, or its name with a numeric
    suffix (``bitunpack.3``)."""
    t, n = 0.0, 0
    for name, v in red["ops"].items():
        base, _, suffix = name.partition(".")
        if name == kernel or (base == kernel and suffix.isdigit()):
            t += v["seconds"]
            n += v["count"]
    return t, n
