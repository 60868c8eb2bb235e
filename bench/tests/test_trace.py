"""The trace reduction, on synthetic intervals and on a small trace
recorded on the CPU (data/cpu_trace, made by data/make_cpu_trace.py)."""
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cpu_trace")
NAMES = {"topup", "poll"}  # the annotations make_cpu_trace.py wrote


def test_union_and_gaps():
    ivs = [(5, 9, "b"), (0, 3, "a"), (2, 4, "c"), (12, 20, "d")]
    u = trace.union(ivs, 1, 15)
    assert u == [(1, 4), (5, 9), (12, 15)]
    assert trace.gaps(u, 1, 15) == [(4, 5), (9, 12)]
    assert trace.gaps([], 0, 3) == [(0, 3)]


def test_self_time_subtracts_nested_ops():
    acc = trace.self_times([(0, 10, "while"), (1, 3, "add"), (4, 8, "mul"),
                            (5, 6, "add"), (20, 22, "add")])
    assert acc == {"while": 4.0, "add": 5.0, "mul": 3.0}


def test_idle_is_split_by_what_the_host_was_doing():
    host = [(0, 10, "poll"), (2, 6, "PjitFunction(f)"), (10, 14, "topup")]
    segs = trace.host_segments(host, NAMES)
    assert segs == [(0, 2, "poll"), (2, 6, "poll>PjitFunction(f)"),
                    (6, 10, "poll"), (10, 14, "topup")]
    acc = trace.attribute([(1, 3), (9, 12), (14, 15)], segs)
    assert acc == {"poll": 2.0, "poll>PjitFunction(f)": 1.0, "topup": 2.0,
                   "host outside any annotation": 1.0}


@pytest.fixture(scope="module")
def recorded():
    path = trace.find_xplane(DATA)
    return trace.load(path, 1, NAMES), trace.summarize(DATA, 1, NAMES)


def test_recorded_window_is_the_annotated_span(recorded):
    (chips, annots, _), s = recorded
    assert s["annotations"] == {"topup": 3, "poll": 3}
    lo = min(a[0] for a in annots)
    hi = max(a[1] for a in annots)
    assert s["window_s"] == pytest.approx((hi - lo) / 1e9, abs=1e-12)


def test_recorded_busy_matches_a_sampled_timeline(recorded):
    """Busy time against an independent count: the window sampled every
    100 ns, a sample busy when any op covers it."""
    (chips, annots, _), s = recorded
    lo = min(a[0] for a in annots)
    hi = max(a[1] for a in annots)
    t = np.arange(lo, hi, 100.0)
    busy = np.zeros(len(t), bool)
    for a, b, _ in chips[0]:
        busy |= (t >= a) & (t < b)
    assert s["busy_s"] == pytest.approx(busy.sum() * 100e-9, abs=2e-6)
    assert s["busy_s"] > 0 and 0 < s["idle_share"] < 1
    assert s["idle_share"] == pytest.approx(1 - s["busy_s"] / s["window_s"])


def test_recorded_breakdown(recorded):
    _, s = recorded
    names = [n for n, _ in s["device_ops"]]
    assert any(n.startswith("dot_general") for n in names)
    assert len(s["device_ops"]) <= trace.TOP and len(s["idle_gaps"]) <= trace.TOP
    gaps = dict(s["idle_gaps"])
    # the host slept 2 ms in each of three top-ups with the device idle
    assert gaps["topup"] == pytest.approx(0.006, rel=0.2)
    assert sum(gaps.values()) <= s["window_s"] - s["busy_s"] + 1e-9
