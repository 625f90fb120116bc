"""``run_olaf_async`` names its parts with host spans on the profiler's
clock: one traced call on the CPU at a small size (3 PS steps, bursts of
4) holds every ``olaf/`` span, each worker step's spans inside its PS
step; the call returns its txctl, staleness and screen counters."""
import argparse
import dataclasses
import math
from collections import Counter

import jax
import pytest

SPANS = {"olaf/setup", "olaf/step", "olaf/batch", "olaf/grad", "olaf/pack",
         "olaf/ps_step", "olaf/flush", "olaf/ckpt", "olaf/finish"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from jax.profiler import ProfileData
    from repro.configs import get_config
    from repro.launch.train import run_olaf_async
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2,
                              d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
                              vocab=256)
    tmp = tmp_path_factory.mktemp("olaf_trace")
    args = argparse.Namespace(
        seq=16, batch=8, seed=3, lr=1e-3, workers=4, queue_slots=4,
        burst_size=4, drain_k=2, steps=3, log_every=2,
        ckpt=str(tmp / "ckpt"), ckpt_every=2, step_impl="auto")
    with jax.profiler.trace(str(tmp / "trace")):
        res = run_olaf_async(cfg, args)
    path = sorted((tmp / "trace").rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            spans.extend((ev.name, dict(ev.stats), ev.start_ns, ev.end_ns)
                         for ev in line.events
                         if ev.name.startswith("olaf/"))
    return res, spans


def test_every_span_is_there_once_per_step_or_worker_step(traced):
    _, spans = traced
    counts = Counter(name for name, *_ in spans)
    assert set(counts) == SPANS
    assert counts["olaf/setup"] == counts["olaf/finish"] == 1
    assert counts["olaf/step"] == counts["olaf/ps_step"] == 3
    for name in ("olaf/batch", "olaf/grad", "olaf/pack"):
        assert counts[name] == 12, name
    steps = sorted(st["step_num"] for name, st, *_ in spans
                   if name == "olaf/step")
    assert steps == [0, 1, 2]


def test_worker_spans_lie_inside_a_step(traced):
    _, spans = traced
    steps = [(s, e) for name, _, s, e in spans if name == "olaf/step"]
    (setup_s, setup_e), = [(s, e) for name, _, s, e in spans
                           if name == "olaf/setup"]
    (fin_s, _), = [(s, e) for name, _, s, e in spans if name == "olaf/finish"]
    assert setup_e <= min(s for s, _ in steps)
    assert max(e for _, e in steps) <= fin_s
    for name, stats, s, e in spans:
        if name in ("olaf/batch", "olaf/grad", "olaf/pack", "olaf/ps_step"):
            assert any(a <= s and e <= b for a, b in steps), name
        if name in ("olaf/batch", "olaf/grad", "olaf/pack"):
            assert stats["worker"] in range(4)


def test_counters_come_back_in_the_result(traced):
    res, _ = traced
    assert (res.deferred, res.stale, res.screened) == (0, 0, 0)
    assert math.isfinite(res.avg_aom) and res.avg_aom > 0
    assert len(res.losses) == len(res.applied) == 3
