"""The trace reduction on a small trace recorded on one TPU v5e chip
(``record_trace.py``): three matrix products inside ``bench.step#n``
spans, inside ``bench.window``.  The expected numbers were read off the
trace's events by hand (nanoseconds):

window  42420639 + 118997809
step 0  ops [62651871 +13] [62651885 +3111] [62654996 +11820]
        union 13 + 14931 = 14944;  program jit__lambda 14949
step 1  ops [95284305 +13] [95284319 +11576] [95295896 +90087]
        union 13 + 11576 + 90087 = 101676;  program 101682
step 2  ops [127782398 +14] [127782413 +44689] [127827104 +704492]
        union 14 + 44689 + 704492 = 749195;  program 749202

The device's events sit about 0.9 ms before the host spans that
dispatched them, so each program is matched to its span only through the
reduction's slack.
"""
import pytest

from conftest import BENCH
from devtrace import Trace, covered, union

FIXTURE = BENCH / "tests" / "data" / "fixture.xplane.pb"
NS = 1e-9


@pytest.fixture(scope="module")
def trace():
    return Trace(str(FIXTURE))


def test_window_and_busy(trace):
    assert trace.window_s == pytest.approx(118997809 * NS, abs=1e-12)
    assert trace.busy_s == pytest.approx((14944 + 101676 + 749195) * NS,
                                         abs=1e-12)
    assert trace.has_device


def test_programs_and_spans(trace):
    spans = trace.spans("step")
    assert sorted(spans) == [0, 1, 2]
    got = [trace.program_time_in(*spans[n]) for n in range(3)]
    assert got == pytest.approx([14949 * NS, 101682 * NS, 749202 * NS],
                                abs=1e-12)
    # without the slack the device's early clock loses every program
    assert trace.program_time_in(*spans[2], slack=0.0) == 0.0
    assert trace.top_ops(10) == [["jit__lambda",
                                  pytest.approx(865833 * NS, abs=1e-12)]]


def test_idle_gaps_named_by_host_span(trace):
    gaps = dict(trace.idle_gaps(10))
    # the products show before their spans open, so each step span is idle
    # from end to end: 11939060 + 11929260 + 12037629 ns
    assert gaps["step"] == pytest.approx(35905949 * NS, abs=1e-12)
    assert gaps["outside spans"] == pytest.approx(
        (118997809 - 865815 - 35905949) * NS, abs=1e-12)
    assert set(gaps) == {"step", "outside spans"}


def test_interval_helpers():
    merged = union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert covered(merged, 2, 6) == 2
    assert covered(merged, -1, 10) == 7
