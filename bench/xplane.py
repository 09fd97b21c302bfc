"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

A device is a plane named ``/device:TPU:<n>``.  Its ``XLA Ops`` line holds
one event per HLO operation run on the chip, its ``XLA Modules`` line one
event per program execution.  Host planes (``/host:CPU``) hold the
``jax.profiler.TraceAnnotation`` spans the benchmark writes around each
step, the data fetch and the trainer's host phases; their names start with
``bench.`` and ``host.``.  Times are in nanoseconds on one clock for all
planes.

- busy: the union of a device's op intervals inside the window;
- window: the ``bench.traced`` annotation, else the span of the device ops;
- ops are named by their HLO instruction (``fusion.603``); a ``while`` op's
  event spans its whole loop, so its time also holds its body's ops;
- idle gaps: the complement of busy inside the window, each labelled with
  the innermost benchmark annotation open on the host at its midpoint.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench.traced"
HOST_MARKS = ("bench.", "host.")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``ProfileData``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def op_name(event_name: str) -> str:
    """``%fusion.603 = (f32[...]) fusion(...)`` -> ``fusion.603``: a device
    op event is named by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            return [(op_name(e.name), float(e.start_ns), float(e.end_ns)) for e in line.events]
    return []


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint cover of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def host_marks(pd) -> list[tuple[str, float, float]]:
    """The benchmark's own annotations on every host plane."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_MARKS):
                    out.append((e.name, float(e.start_ns), float(e.end_ns)))
    return out


def _label(marks, t: float) -> str:
    """Innermost (shortest) annotation open at ``t``; "none" if none is."""
    open_ = [(e - s, name) for name, s, e in marks if s <= t <= e and name != WINDOW_MARK]
    return min(open_)[1] if open_ else "none"


def reduce(pd) -> dict:
    """Device metrics of one trace, averaged over the traced chips.

    Returns ``{"chips", "window_ns", "busy_ns", "ops_ns": {name: ns},
    "modules_ns": {name: ns}, "module_runs": {name: count},
    "collective_ns", "collective_exposed_ns", "gaps": [(label, ns)]}``,
    with ``gaps`` sorted longest first.  Raises when the trace holds no
    device plane with ops."""
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    ops = {p.name: _events(p, OPS_LINE) for p in planes}
    planes = [p for p in planes if ops[p.name]]
    if not planes:
        raise ValueError("trace holds no device plane with XLA ops")
    marks = host_marks(pd)
    wins = [(s, e) for name, s, e in marks if name == WINDOW_MARK]
    if wins:
        t0, t1 = wins[0]
    else:
        t0 = min(s for p in planes for _, s, _ in ops[p.name])
        t1 = max(e for p in planes for _, _, e in ops[p.name])
    n = len(planes)
    busy = 0.0
    coll = coll_exposed = 0.0
    op_ns: dict[str, float] = {}
    mod_ns: dict[str, float] = {}
    mod_runs: dict[str, int] = {}
    gaps = []
    for p in planes:
        evs = [(name, s, e) for name, s, e in ops[p.name] if e > t0 and s < t1]
        cover = union(_clip([(s, e) for _, s, e in evs], t0, t1))
        busy += sum(e - s for s, e in cover) / n
        for name, s, e in evs:
            op_ns[name] = op_ns.get(name, 0.0) + (min(e, t1) - max(s, t0)) / n
        c_iv = union(_clip([(s, e) for name, s, e in evs if COLLECTIVE.search(name)], t0, t1))
        compute = union(_clip([(s, e) for name, s, e in evs if not COLLECTIVE.search(name)], t0, t1))
        c_len = sum(e - s for s, e in c_iv)
        coll += c_len / n
        coll_exposed += (c_len - _overlap(c_iv, compute)) / n
        for name, s, e in _events(p, MODULES_LINE):
            if e > t0 and s < t1:
                mod_ns[name] = mod_ns.get(name, 0.0) + (min(e, t1) - max(s, t0)) / n
                mod_runs[name] = mod_runs.get(name, 0) + 1
        prev = t0
        for s, e in cover + [(t1, t1)]:
            if s > prev:
                gaps.append((_label(marks, (prev + s) / 2), s - prev))
            prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    return {
        "chips": n, "window_ns": t1 - t0, "busy_ns": busy, "ops_ns": op_ns,
        "modules_ns": mod_ns, "module_runs": {k: v // n for k, v in mod_runs.items()},
        "collective_ns": coll, "collective_exposed_ns": coll_exposed, "gaps": gaps,
    }


def _overlap(a, b) -> float:
    """Total length of the intersection of two disjoint sorted covers."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    and the longest idle gaps, in seconds."""
    ops = sorted(red["ops_ns"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, ns * 1e-9] for name, ns in ops],
        "idle_gaps": [[label, ns * 1e-9] for label, ns in red["gaps"][:top]],
    }
