"""The trainer's ``olaf/`` spans in a trace, and the readers of the worker
step and the step loop: by hand on built inputs, and on a small trace
recorded on one TPU v5e (``data/olaf_small.xplane.pb``, written by
``record_olaf_trace.py``: one call of the trainer at ``cpu_cell.py``'s
small size, 2 layers and seq 16, 3 PS steps in bursts of 4, inside the
harness's ``pb:window`` and ``pb:train_call``)."""
import math
import random
from pathlib import Path

import pytest

from perfbench import harness, trace_reduce as tr, work
from perfbench import program_spans as ps
from perfbench.tests.cpu_cell import small

DATA = Path(__file__).resolve().parent / "data"
OLAF = DATA / "olaf_small.xplane.pb"
STEPS, BURST = 3, 4
grad_mfu = harness.metric_reader("grad_mfu.short")


def sp(name, seconds, busy, step=None):
    return dict(name=name, step=step, seconds=seconds, device_busy_s=busy)


# one call: set-up, a first step that loads both programs, two steady
# steps, and the close; worker spans nest inside the steps
CALL = [sp("olaf/setup", 1.0, 0.25), sp("olaf/step", 0.8, 0.3, 0),
        sp("olaf/grad", 0.1, 0.0), sp("olaf/step", 0.5, 0.45, 1),
        sp("olaf/pack", 0.2, 0.2), sp("olaf/step", 0.5, 0.4, 2),
        sp("olaf/finish", 0.3, 0.1)]


def test_coverage_matches_covered():
    rng = random.Random(7)
    ivs = [(a, a + rng.random()) for a in
           (rng.uniform(0, 20) for _ in range(60))]
    merged = tr.merge(ivs)
    cov = ps.coverage(merged)
    for _ in range(200):
        lo, hi = sorted(rng.uniform(-1, 22) for _ in range(2))
        assert cov(lo, hi) == pytest.approx(tr.covered(merged, lo, hi),
                                            abs=1e-12)
    assert cov(5.0, 5.0) == cov(6.0, 5.0) == 0.0


def test_call_idle_s_sums_setup_first_step_and_finish_per_call():
    assert ps.call_idle_s(CALL) == pytest.approx(0.75 + 0.5 + 0.2)
    # two calls: the sum is halved, and each call's first step counts
    assert ps.call_idle_s(CALL + CALL) == pytest.approx(0.75 + 0.5 + 0.2)


def test_loop_idle_share_reads_the_steady_steps_only():
    assert ps.loop_idle_share(CALL) == pytest.approx(
        100.0 * (0.05 + 0.1) / 1.0)


def test_readers_give_none_without_spans():
    assert ps.call_idle_s([]) is None
    assert ps.loop_idle_share([]) is None
    # one step and no set-up: a loop step, but no call to charge
    assert ps.call_idle_s([sp("olaf/step", 1.0, 0.5, 7)]) is None
    assert ps.loop_idle_share([sp("olaf/setup", 1.0, 0.5),
                               sp("olaf/step", 1.0, 0.5, 0)]) is None


def _ctx(module_s, tokens=1000, seq=16):
    m = small("smollm-360m-async.short")["config"]
    return dict(trace=dict(module_s=module_s, window_s=10.0),
                work=dict(tokens=tokens), config=m, traffic=dict(seq=seq),
                chips=1, peaks=work.peaks("TPU v5 lite"))


def test_grad_mfu_divides_by_the_worker_step_executable():
    ctx = _ctx({"jit_worker_grad": 0.5, "jit_ps_step": 9.0})
    flops = 1000 * work.lm_train_flops_per_token(ctx["config"], 16)
    assert grad_mfu(ctx) == pytest.approx(100 * flops / (0.5 * 197e12))


def test_grad_mfu_is_none_without_the_executable():
    assert grad_mfu(_ctx({"jit__lambda": 0.5})) is None
    assert grad_mfu(dict(_ctx({"jit_worker_grad": 0.5}), peaks=None)) is None


def test_a_trace_without_trainer_spans_reads_as_before():
    path = DATA / "small.xplane.pb"
    got, want = ps.reduce_program(path), tr.reduce_trace(path)
    assert got["program"] == []
    assert got["call_idle_s"] is None and got["loop_idle_share"] is None
    assert [label for label, _ in got["idle_gaps"][:6]] == \
        [label for label, _ in want["idle_gaps"][:6]]
    assert got["idle"]["idle_s"] == pytest.approx(
        want["window_s"] - want["busy_s"], abs=1e-9)


@pytest.fixture(scope="module")
def olaf():
    return ps.reduce_program(OLAF), tr.reduce_trace(OLAF)


def test_recorded_trace_holds_the_trainers_spans(olaf):
    prog, _ = olaf
    names = [s["name"] for s in prog["program"]]
    for name in ("olaf/setup", "olaf/ps_step", "olaf/finish", "olaf/flush",
                 "olaf/ckpt"):
        assert name in names, name
    assert [s["step"] for s in prog["program"]
            if s["name"] == "olaf/step"] == list(range(STEPS))
    for name in ("olaf/batch", "olaf/grad", "olaf/pack"):
        assert names.count(name) == STEPS * BURST, name


def test_recorded_trace_names_both_executables(olaf):
    _, red = olaf
    assert red["module_n"]["jit_worker_grad"] == STEPS * BURST
    assert red["module_n"]["jit_ps_step"] == STEPS
    assert not any("lambda" in k for k in red["module_n"])


def test_recorded_trace_gaps_are_trainer_spans(olaf):
    prog, _ = olaf
    assert len(prog["idle_gaps"]) == 10
    assert all(label.startswith("olaf/") for label, _ in prog["idle_gaps"])
    idle = prog["idle"]
    assert idle["in_spans_share"] >= 95.0
    parts = idle["call_s"] + idle["loop_s"] + idle["outside_s"]
    assert parts == pytest.approx(idle["idle_s"], abs=0.01 * prog["window_s"])


def test_recorded_trace_readers_are_finite(olaf):
    prog, red = olaf
    over = small("smollm-360m-async.short")
    t = over["traffic"]
    tokens = STEPS * BURST * t["batch_per_worker"] * t["seq"]
    ctx = dict(trace=red, work=dict(tokens=tokens), config=over["config"],
               traffic=t, chips=1, peaks=work.peaks("TPU v5 lite"))
    for value in (grad_mfu(ctx), prog["call_idle_s"],
                  prog["loop_idle_share"]):
        assert value is not None and math.isfinite(value) and value > 0
    assert grad_mfu(ctx) < 100.0 and prog["loop_idle_share"] < 100.0
