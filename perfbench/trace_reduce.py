"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

The harness wraps its own phases in ``jax.profiler.TraceAnnotation`` spans
whose names start with ``pb:``; the window is the span ``pb:window``. From
the device planes (``/device:TPU:<n>``) this takes:

* busy time: the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips in the trace;
* per-executable device time: the ``XLA Modules`` events, by module name
  with the run id stripped (``jit_ps_step(42)`` -> ``jit_ps_step``);
* per-op device self time, keyed ``module/op`` (a reader sums the ops it
  needs, such as the collectives, from it);
* the breakdown: the ten ops with the most self time (``module/op``), and
  the ten longest idle gaps, each labelled by the innermost harness span
  that covers its midpoint.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "pb:"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_RUN_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of ``[start, end)`` intervals, sorted."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the merged intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Self time per name: an event's duration less that of the events
    nested directly inside it (a ``while`` op holds its body's ops)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float, float]] = []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(e, stack[-1][2]) - s
        out[name] += e - s
        stack.append((name, s, e))
    return dict(out)


def _load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def read_planes(path) -> Tuple[List[Tuple[str, float, float]],
                               Dict[int, Dict[str, list]]]:
    """Harness spans (name without prefix, start, end) in seconds, and per
    device id its ``ops`` and ``modules`` events as (name, start, end)."""
    spans: List[Tuple[str, float, float]] = []
    devices: Dict[int, Dict[str, list]] = {}
    for plane in _load(path).planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend((ev.name, ev.start_ns * 1e-9,
                                     ev.end_ns * 1e-9) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9,
                              ev.end_ns * 1e-9) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return spans, devices


def _label(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    best: Optional[Tuple[float, str]] = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside spans"


def _module_of(mods: Sequence[Tuple[str, float, float]]):
    starts = [s for _, s, _ in mods]

    def find(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and mods[i][1] <= t < mods[i][2]:
            return _RUN_ID.sub("", mods[i][0])
        return "?"
    return find


def reduce_trace(path, top: int = 10) -> Dict:
    """Everything the per-layer readers and the breakdown need."""
    spans, devices = read_planes(path)
    win = [(s, e) for name, s, e in spans if name == "window"]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if win:
        lo, hi = win[0]
    else:
        evs = [x for d in devices.values() for x in d["ops"] + d["modules"]]
        lo, hi = min(s for _, s, _ in evs), max(e for _, _, e in evs)
    n = len(devices)
    busy = 0.0
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    op_s: Dict[str, float] = defaultdict(float)
    span_busy: Dict[int, float] = defaultdict(float)
    inner = [(name, s, e) for name, s, e in spans if name != "window"]
    gaps: List[Tuple[float, str]] = []
    for dev_id in sorted(devices):
        dev = devices[dev_id]
        ops = [x for x in dev["ops"] if x[2] > lo and x[1] < hi]
        mods = sorted((x for x in dev["modules"] if x[2] > lo and x[1] < hi),
                      key=lambda x: x[1])
        merged = merge([(s, e) for _, s, e in (ops or mods)])
        busy += covered(merged, lo, hi) / n
        for i, (_, s, e) in enumerate(inner):
            span_busy[i] += covered(merged, s, e) / n
        for name, s, e in mods:
            key = _RUN_ID.sub("", name)
            module_s[key] += (min(e, hi) - max(s, lo)) / n
            module_n[key] += 1
        find = _module_of(mods)
        labelled = [(f"{find(s)}/{name}", s, e) for name, s, e in ops]
        for name, t in self_times(labelled).items():
            op_s[name] += t / n
        if dev_id == min(devices):
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                a, b = max(a, lo), min(b, hi)
                if b > a:
                    gaps.append((b - a, _label(inner, (a + b) / 2)))
    module_n = {k: v // n for k, v in module_n.items()}
    gaps.sort(reverse=True)
    return dict(
        window_s=hi - lo, busy_s=busy, n_devices=n,
        module_s=dict(module_s), module_n=module_n, ops_s=dict(op_s),
        spans=[dict(name=name, seconds=e - s, device_busy_s=span_busy[i])
               for i, (name, s, e) in enumerate(inner)],
        device_ops=[[k, v] for k, v in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[label, g] for g, label in gaps[:top]])
