#!/usr/bin/env python3
"""The trainer's own spans in a profiler trace, and where the device
waits inside them.

``run_olaf_async`` (``src/repro/launch/train.py``) opens host spans named
``olaf/<part>`` with ``jax.profiler.TraceAnnotation``: ``olaf/setup`` up to
its step loop; one ``olaf/step`` per PS step (``step_num`` its index),
holding ``olaf/batch``, ``olaf/grad`` and ``olaf/pack`` for each worker
step and one ``olaf/ps_step``; ``olaf/flush`` and ``olaf/ckpt`` where they
run; ``olaf/finish`` after the loop. They lie on the trace's host plane, on
the clock of the device planes. ``trace_reduce`` reads the harness's
``pb:`` spans; this reads the trainer's next to them, over the same window
(``pb:window``, else from the first to the last event):

* ``program``: each span's name, step, seconds and device-busy seconds
  (the union of device ops inside it, averaged over the chips), in order
  of start;
* ``idle``: the window's device-idle seconds, split into the trainer
  call's set-up and close (``call_s``: ``olaf/setup``, each call's first
  ``olaf/step``, which traces and loads both jitted steps, and
  ``olaf/finish``), its steady step loop (``loop_s``: the other steps) and
  the rest of the window (``outside_s``); ``in_spans_share`` is the share
  of the idle time that lies inside some ``olaf/`` span (%);
* ``call_idle_s`` (device-idle seconds of the set-up and close, per call)
  and ``loop_idle_share`` (% of the steady steps' time the device sat
  idle);
* ``idle_gaps``: the longest device-idle gaps of the window, each labelled
  by the innermost harness or trainer span over its midpoint.

    python3 perfbench/program_spans.py <trace.xplane.pb, or a directory>

prints that as one JSON object.
"""
from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import trace_reduce as tr  # noqa: E402

PREFIX = "olaf/"
TOP = ("olaf/setup", "olaf/step", "olaf/finish")

Span = Tuple[str, Optional[int], float, float]


def read_program(path) -> List[Span]:
    """The trainer's spans as (name, step_num or None, start, end) in
    seconds, in order of start (a parent before the child it opens)."""
    out: List[Span] = []
    for plane in tr._load(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    step = dict(ev.stats).get("step_num")
                    out.append((ev.name, None if step is None else int(step),
                                ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    return sorted(out, key=lambda x: (x[2], -x[3]))


def coverage(merged: Sequence[tr.Interval]) -> Callable[[float, float],
                                                         float]:
    """``trace_reduce.covered`` over fixed merged intervals, by prefix sums:
    each query takes a logarithmic time, not a pass over the intervals."""
    starts = [s for s, _ in merged]
    cum = [0.0]
    for s, e in merged:
        cum.append(cum[-1] + e - s)

    def upto(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = merged[i - 1]
        return cum[i - 1] + min(t, e) - s

    return lambda lo, hi: max(0.0, upto(hi) - upto(lo)) if hi > lo else 0.0


def _split(program: Sequence[Dict]):
    """The number of calls, the spans of their set-up and close, and the
    steady steps, from the top-level spans in order of start."""
    calls, call, loop, first = 0, [], [], False
    for sp in program:
        if sp["name"] == "olaf/setup":
            calls, first = calls + 1, True
            call.append(sp)
        elif sp["name"] == "olaf/finish":
            call.append(sp)
        elif sp["name"] == "olaf/step":
            (call if first else loop).append(sp)
            first = False
    return calls, call, loop


def _idle(sp: Dict) -> float:
    return sp["seconds"] - sp["device_busy_s"]


def call_idle_s(program: Sequence[Dict]) -> Optional[float]:
    """Device-idle seconds of ``olaf/setup``, the call's first ``olaf/step``
    and ``olaf/finish``, per call; None without a set-up span."""
    calls, call, _ = _split(program)
    return sum(map(_idle, call)) / calls if calls else None


def loop_idle_share(program: Sequence[Dict]) -> Optional[float]:
    """Device-idle share (%) of the steady ``olaf/step`` spans: all but
    each call's first; None without one."""
    loop = _split(program)[2]
    total = sum(sp["seconds"] for sp in loop)
    return 100.0 * sum(map(_idle, loop)) / total if total > 0 else None


def reduce_program(path, top: int = 10) -> Dict:
    """The trainer's spans, the split of the window's idle time, and the
    labelled idle gaps (module docstring)."""
    spans, devices = tr.read_planes(path)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    prog = read_program(path)
    win = [(s, e) for name, s, e in spans if name == "window"]
    if win:
        lo, hi = win[0]
    else:
        evs = [x for d in devices.values() for x in d["ops"] + d["modules"]]
        evs += [(n, s, e) for n, _, s, e in prog]
        lo, hi = min(s for _, s, _ in evs), max(e for _, _, e in evs)
    merged = []
    for dev_id in sorted(devices):
        dev = devices[dev_id]
        evs = [x for x in dev["ops"] if x[2] > lo and x[1] < hi] or \
            [x for x in dev["modules"] if x[2] > lo and x[1] < hi]
        merged.append(tr.merge([(s, e) for _, s, e in evs]))
    covs = [coverage(m) for m in merged]

    def busy(s: float, e: float) -> float:
        s, e = max(s, lo), min(e, hi)
        return sum(c(s, e) for c in covs) / len(covs)

    inside = [(name, step, max(s, lo), min(e, hi))
              for name, step, s, e in prog if e > lo and s < hi]
    program = [dict(name=name, step=step, seconds=e - s,
                    device_busy_s=busy(s, e))
               for name, step, s, e in inside]
    _, call, loop = _split(program)
    idle_s = (hi - lo) - busy(lo, hi)
    edges = [lo] + [x for name, _, s, e in inside if name in TOP
                    for x in (s, e)] + [hi]
    outside_s = sum((b - a) - busy(a, b)
                    for a, b in zip(edges[::2], edges[1::2]) if b > a)

    gaps = []
    bounds = [lo] + [x for iv in merged[0] for x in iv] + [hi]
    for a, b in zip(bounds[::2], bounds[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    labels = [(name, s, e) for name, s, e in spans if name != "window"]
    labels += [(name, s, e) for name, _, s, e in inside]
    return dict(
        window_s=hi - lo, n_devices=len(devices), program=program,
        idle=dict(idle_s=idle_s, call_s=sum(map(_idle, call)),
                  loop_s=sum(map(_idle, loop)), outside_s=outside_s,
                  in_spans_share=100.0 * (1.0 - outside_s / idle_s)
                  if idle_s > 0 else None),
        call_idle_s=call_idle_s(program),
        loop_idle_share=loop_idle_share(program),
        idle_gaps=[[tr._label(labels, (a + b) / 2), g]
                   for g, a, b in gaps[:top]])


def main(argv: Sequence[str]) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = Path(argv[0])
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"))
        if not found:
            print(f"no .xplane.pb under {path}", file=sys.stderr)
            return 1
        path = found[-1]
    print(json.dumps(reduce_program(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
