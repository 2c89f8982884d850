"""The readers of the program's own spans (``bench/metrics/`` over
``repro.serving.tracing``).

The counters' and durations' readers run on spans made here by hand.
The map onto the trace's clock and ``idle_untraced_share`` run on a small
trace recorded on one TPU v5e chip (``record_program_trace.py``:
``program_fixture.xplane.pb``, with the program's and the harness's spans
on ``perf_counter`` in ``program_fixture.json``): three harness
``bench.step#n`` spans, each holding a program ``step`` with a
``kv_pull``, an ``inject`` and a ``decode_step`` (a matrix product), with
the device idle inside program spans, inside harness spans only, and
outside both.  Read off the trace's events by hand (nanoseconds):

window            42753479 + 133524206
device ops        [74221522 +13] [74221536 +3287] [74224825 +11820]
                  [110422005 +13] [110422020 +11517] [110433537 +90088]
                  [146264314 +13] [146264329 +44895] [146309224 +704492]
                  union 15120 + 101618 + 749400 = 866138
kvserve.step      [63246588 +18490470] [103729317 +14175030]
                  [139459466 +15162609]; every other kvserve span and
                  every device op lies inside one of them
idle              133524206 - 866138 = 132658068
idle in a span    18490470 + 14175030 + 15162609 - 866138 = 46961971
untraced share    (132658068 - 46961971) / 132658068 = 64.5992 %

The reader maps the program's ``perf_counter`` times, not these events,
onto the trace; each mapped start and end lies within 10 us of its
``kvserve.*`` event (the events open just before the clock is read and
close just after).
"""
import importlib.util
import json
import sys

import jax

import pytest

from conftest import BENCH
from devtrace import Trace
from spans import Span as HarnessSpan

from repro.serving import tracing

DATA = BENCH / "tests" / "data"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = ("kv_pull_ms", "host_mb_per_req", "inject_ms", "decode_gap_ms",
           "slots_live_mean", "idle_untraced_share")


class _Recorder:
    def __init__(self, spans):
        self.spans = spans


class _Ctx:
    def __init__(self, harness, trace=None):
        self.spans = _Recorder(harness)
        self.trace = trace


def _span(name, t0, t1, parent=None, rid=None, **counters):
    s = tracing.Span(name, rid)
    s.t0, s.t1, s.parent, s.counters = t0, t1, parent, counters
    return s


@pytest.fixture
def program_spans():
    tracing.SPANS.clear()
    yield tracing.SPANS
    tracing.SPANS.clear()


def test_counters_and_durations(program_spans):
    """Two runs' spans in one list: only those overlapping this run's
    harness spans (10.0-20.0 s) count."""
    old = _span("kv_pull", 1.0, 1.5, bytes=10**9)
    steps = [_span("step", 10.0 + i, 10.9 + i) for i in range(4)]
    pulls = [_span("kv_pull", 10.1, 10.4, steps[0], 3, bytes=150_000_000),
             _span("kv_pull", 12.1, 12.3, steps[2], 4, bytes=150_000_000)]
    injects = [_span("inject", 10.5, 10.6, steps[0], 3, bytes=75_000_000),
               _span("inject", 12.5, 12.8, steps[2], 4, bytes=75_000_000)]
    # decode steps in iterations 0, 1 and 3: only 0 -> 1 are consecutive
    decode = [_span("decode_step", 10.7, 10.8, steps[0], live=2),
              _span("decode_step", 11.2, 11.3, steps[1], live=3),
              _span("decode_step", 13.5, 13.6, steps[3], live=7)]
    program_spans.extend([old] + pulls + injects + decode + steps)
    ctx = _Ctx([HarnessSpan("start_pd", 0, 10.05, 10.9, 3),
                HarnessSpan("decode", 5, 13.4, 19.0, None)])
    got = {name: _reader(name)(ctx) for name in READERS}
    assert got["kv_pull_ms"] == pytest.approx(1e3 * (0.3 + 0.2) / 2)
    assert got["inject_ms"] == pytest.approx(1e3 * (0.1 + 0.3) / 2)
    assert got["host_mb_per_req"] == pytest.approx(225.0)
    assert got["decode_gap_ms"] == pytest.approx(1e3 * (11.2 - 10.8))
    assert got["slots_live_mean"] == pytest.approx(4.0)
    assert got["idle_untraced_share"] is None     # no trace


def test_a_program_without_spans_reads_nothing(program_spans, monkeypatch):
    """The parent program has no ``repro.serving.tracing``: every reader
    returns None and none raises."""
    program_spans.append(_span("kv_pull", 10.1, 10.4, bytes=1))
    monkeypatch.delattr(sys.modules["repro.serving"], "tracing")
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    ctx = _Ctx([HarnessSpan("start_pd", 0, 10.0, 11.0, 0)])
    assert {name: _reader(name)(ctx) for name in READERS} == \
        {name: None for name in READERS}


@pytest.fixture
def fixture_ctx(program_spans):
    """The recorded run: harness spans and program spans as the harness
    and the program held them, the trace as ``bench/run.py`` reads it."""
    rec = json.loads((DATA / "program_fixture.json").read_text())
    made = []
    for p in rec["program"]:
        made.append(_span(p["name"], p["t0"], p["t1"], rid=p["rid"],
                          **p["counters"]))
    for s, p in zip(made, rec["program"]):
        if p["parent"] is not None:
            s.parent = made[p["parent"]]
    program_spans.extend(made)
    harness = [HarnessSpan(h["name"], h["n"], h["t0"], h["t1"], None)
               for h in rec["harness"]]
    return _Ctx(harness, Trace(str(DATA / "program_fixture.xplane.pb")))


def test_program_spans_map_onto_the_trace_clock(fixture_ctx):
    spec = importlib.util.spec_from_file_location(
        "bench_program_spans", BENCH / "metrics" / "_program_spans.py")
    ps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ps)
    to_trace = ps.to_trace(fixture_ctx)
    data = jax.profiler.ProfileData.from_file(
        str(DATA / "program_fixture.xplane.pb"))
    events = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for plane in data.planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith("kvserve."))
    spans = sorted(tracing.SPANS, key=lambda s: s.t0)
    assert [n for _, _, n in events] == [f"kvserve.{s.name}" for s in spans]
    assert [round(s, 9) for s, _, n in events if n == "kvserve.step"] == \
        [0.063246588, 0.103729317, 0.139459466]
    for s, (start, end, _) in zip(spans, events):
        assert to_trace(s.t0) == pytest.approx(start, abs=10e-6)
        assert to_trace(s.t1) == pytest.approx(end, abs=10e-6)


def test_idle_untraced_share_on_the_chip_trace(fixture_ctx):
    trace = fixture_ctx.trace
    assert trace.window_s == pytest.approx(133524206e-9, abs=1e-12)
    assert trace.busy_s == pytest.approx(866138e-9, abs=1e-12)
    got = _reader("idle_untraced_share")(fixture_ctx)
    # each of the three step spans' mapped ends may lie up to 10 us inside
    # its event's: 60 us of 132.66 ms of idle time at most
    assert got == pytest.approx(100 * 85696097 / 132658068, abs=0.05)
    assert _reader("decode_gap_ms")(fixture_ctx) == pytest.approx(
        1e3 * ((33.653535476 - 33.618190857)
               + (33.689260414 - 33.654276825)) / 2)
    assert _reader("slots_live_mean")(fixture_ctx) == 2.0
    assert _reader("host_mb_per_req")(fixture_ctx) == pytest.approx(
        2 * 4194304 / 1e6)
