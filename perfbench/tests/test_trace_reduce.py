"""The trace reduction, on intervals by hand and on a small trace recorded
on one TPU v5e (``data/small.xplane.pb``: three rounds of a 4096 x 4096
matmul jit, a 20 ms host sleep and a sort jit, each in a harness span).
In that trace the device events run about 1 ms ahead of the host spans,
so the checks leave that much room."""
from pathlib import Path

import pytest

from perfbench import trace_reduce as tr

SMALL = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_merge_and_covered():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m == [(0, 3), (5, 9)]
    assert tr.covered(m, 2, 6) == 1 + 1


def test_self_times_subtract_nested_events():
    evs = [("while", 0.0, 10.0), ("body", 1.0, 4.0), ("body", 5.0, 6.0),
           ("after", 10.0, 11.0)]
    assert tr.self_times(evs) == {"while": 6.0, "body": 4.0, "after": 1.0}


@pytest.fixture(scope="module")
def small():
    return tr.reduce_trace(SMALL)


def test_small_trace_window_and_busy(small):
    assert small["n_devices"] == 1
    assert 0 < small["busy_s"] < small["window_s"]
    names = {s["name"] for s in small["spans"]}
    assert names == {"compute", "host", "sort"}
    host = [s for s in small["spans"] if s["name"] == "host"]
    assert len(host) == 3
    for s in host:
        assert s["seconds"] >= 0.02
        assert s["device_busy_s"] < 0.002


def test_small_trace_modules_ops_and_gaps(small):
    mods = small["module_s"]
    assert small["module_n"] == {"jit_matmul_step": 3, "jit_sort_step": 3}
    assert mods["jit_sort_step"] > mods["jit_matmul_step"] > 0
    assert sum(mods.values()) <= small["busy_s"] + 1e-3
    assert len(small["device_ops"]) == 10
    assert all(name.startswith(("jit_matmul_step/", "jit_sort_step/"))
               and t > 0 for name, t in small["device_ops"])
    assert [label for label, _ in small["idle_gaps"][:3]] == ["host"] * 3
    assert small["idle_gaps"][0][1] >= 0.015
    assert sorted(small["ops_s"].items(), key=lambda kv: -kv[1])[:10] == \
        [tuple(x) for x in small["device_ops"]]
    assert not any("all-gather" in name for name in small["ops_s"])
