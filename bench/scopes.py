"""The program's own names in a profiler trace: device self time by the part
of the step each op belongs to, and device idle time by the program span
open on the dispatching thread.

The fused step's ops carry the program's name scopes in their HLO
``op_name`` (``jit(step_fn)/jvp()/while/body/closed_call/layers/dot``; the
backward under ``transpose(...)``, remat recompute included).  A TPU trace
keeps it as the ``tf_op`` stat of each op's event metadata, which
``ProfileData`` does not expose, so ``op_scopes`` reads it from the
serialized ``XSpace`` itself.  The program's wall-clock spans are
``TraceAnnotation``s on the host thread that runs them.

The device metric readers take the first through ``device_by_scope(ctx)``,
which reads the traced run's trace once and keeps the result in ``ctx``.
To print both for a trace directory, in seconds per traced step::

    python3 bench/scopes.py [trace_dir] [--steps N]
"""

from __future__ import annotations

import argparse
import glob
import heapq
import json
import os
import re
import sys

import xplane
from xplane import DEVICE_PLANE, OPS_LINE, WINDOW_MARK, _clip, _label, host_marks, union

SCOPE_STAT = "tf_op"
MODEL_SCOPES = frozenset({"embed", "layers", "head_loss"})
PHASES = ("forward", "backward", "adamw", "coded_pack", "unscoped")
STEP_SPAN = "step"


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one serialized protobuf message; a
    length-delimited value is a zero-copy slice."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not in an XSpace")
        yield key >> 3, value


def _map_values(entry) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(entry):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(xspace: bytes) -> dict[str, str]:
    """``{device op event name: its name stack}`` from a serialized
    ``XSpace``: the ``tf_op`` stat of every device plane's event metadata
    (an op with no such stat is left out).  Keyed by the whole event name,
    the op's HLO text, so ops of two programs that share an instruction
    name stay apart."""
    out: dict[str, str] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(_map_values(v)[1])
            elif pf == 5:
                key, md = _map_values(v)
                stat_names[key] = next((bytes(x).decode() for mf, x in _fields(md) if mf == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        for md in events:
            op, stack = "", ""
            for mf, v in _fields(md):
                if mf == 2:
                    op = bytes(v).decode()
                elif mf == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:
                        stack = bytes(stat[5]).decode()
                    elif 7 in stat:
                        stack = stat_names.get(stat[7], "")
            if op and stack:
                out[op] = stack
    return out


def load_scopes(trace_dir: str) -> dict[str, str]:
    """``op_scopes`` of the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        return op_scopes(f.read())


def phase(stack: str) -> str:
    """The part of the step an op's name stack puts it in: ``backward``
    (under ``transpose(``), ``adamw``, ``coded_pack``, ``forward`` (a model
    scope), else ``unscoped``.  An op named after an argument
    (``params['embed']``: a copy of an input) is in no scope."""
    if "transpose(" in stack:
        return "backward"
    words = set(re.findall(r"[\w.-]+", re.sub(r"\[[^\]]*\]", "", stack)))
    for scope in ("adamw", "coded_pack"):
        if scope in words:
            return scope
    return "forward" if words & MODEL_SCOPES else "unscoped"


def self_ns(events) -> list[float]:
    """Self time of each ``(name, start, end)`` event: every instant of
    their union goes to the innermost event open then (the latest started,
    the shortest of those), so a ``while`` op keeps only the time its body's
    ops leave uncovered and the self times add up to the union."""
    order = sorted(range(len(events)), key=lambda i: events[i][1])
    times = sorted({t for _, s, e in events for t in (s, e)})
    out = [0.0] * len(events)
    heap: list[tuple[float, float, int]] = []
    k = 0
    for t, t_next in zip(times, times[1:]):
        while k < len(order) and events[order[k]][1] <= t:
            i = order[k]
            heapq.heappush(heap, (-events[i][1], events[i][2], i))
            k += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        if heap:
            out[heap[0][2]] += t_next - t
    return out


def _window(pd, planes, ops) -> tuple[float, float]:
    wins = [(s, e) for name, s, e in host_marks(pd) if name == WINDOW_MARK]
    if wins:
        return wins[0]
    return (min(s for p in planes for _, s, _ in ops[p.name]),
            max(e for p in planes for _, _, e in ops[p.name]))


def _device_ops(pd):
    """Device planes with ops, ``{plane: [(event name, start, end)]}`` with
    whole event names, and the window."""
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    ops = {p.name: [(e.name, float(e.start_ns), float(e.end_ns))
                    for line in p.lines if line.name == OPS_LINE for e in line.events]
           for p in planes}
    planes = [p for p in planes if ops[p.name]]
    if not planes:
        raise ValueError("trace holds no device plane with XLA ops")
    return planes, ops, _window(pd, planes, ops)


def scope_ns(pd, scopes: dict[str, str]) -> dict[str, float]:
    """Device self time inside the traced window by the part of the step
    each op belongs to (``PHASES``), averaged over chips; ``scopes`` maps an
    op's event name to its name stack (``op_scopes``)."""
    planes, ops, (t0, t1) = _device_ops(pd)
    out = dict.fromkeys(PHASES, 0.0)
    for p in planes:
        evs = [(name, max(s, t0), min(e, t1)) for name, s, e in ops[p.name] if e > t0 and s < t1]
        for (name, _, _), ns in zip(evs, self_ns(evs)):
            out[phase(scopes.get(name, ""))] += ns / len(planes)
    return out


def idle_by_span(pd, names) -> dict[str, float] | None:
    """The traced window's device idle time (the gaps ``reduce`` finds),
    summed by the innermost program span open at each gap's midpoint on the
    host thread that dispatches the step (the one holding a ``step`` span),
    "none" where none is; averaged over chips.  ``names`` are the program's
    span names.  None when no thread holds a ``step`` span."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.end_ns)) for e in line.events if e.name in names]
            if any(name == STEP_SPAN for name, _, _ in evs):
                spans += evs
    if not spans:
        return None
    planes, ops, (t0, t1) = _device_ops(pd)
    out: dict[str, float] = {}
    for p in planes:
        prev = t0
        for s, e in union(_clip([(s, e) for _, s, e in ops[p.name]], t0, t1)) + [(t1, t1)]:
            if s > prev:
                label = _label(spans, (prev + s) / 2)
                out[label] = out.get(label, 0.0) + (s - prev) / len(planes)
            prev = max(prev, e)
    return out


def device_by_scope(ctx) -> dict[str, float] | None:
    """``scope_ns`` of the traced run's trace, read once and kept in
    ``ctx``; None where the run made no trace."""
    if "device_by_scope" not in ctx:
        ctx["device_by_scope"] = None
        if ctx.get("trace") is not None:
            import harness

            trace_dir = str(harness.TRACE_DIR)
            ctx["device_by_scope"] = scope_ns(xplane.load(trace_dir), load_scopes(trace_dir))
    return ctx["device_by_scope"]


PROGRAM_SPANS = re.compile(r"^(step|step\..+|phase\..+|prefetch\..+)$")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Device time by scope and idle time by program span "
                                             "of a profiler trace, in seconds per traced step.")
    ap.add_argument("trace_dir", nargs="?", default=".bench_trace")
    ap.add_argument("--steps", type=int, default=3, help="steps the trace holds")
    args = ap.parse_args(argv)
    pd = xplane.load(args.trace_dir)
    names = {e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if PROGRAM_SPANS.match(e.name)}
    out = {"device_by_scope": scope_ns(pd, load_scopes(args.trace_dir)),
           "idle_by_span": idle_by_span(pd, names)}
    print(json.dumps({key: None if v is None else {k: ns * 1e-9 / args.steps for k, ns in v.items()}
                      for key, v in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
