"""Scheduler: mean milliseconds of host time between two arena steps:
from the end of one ``decode_step`` span to the start of the next, over
decode steps in consecutive iterations (``step`` spans) that both had
live slots (program spans)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    spans = _ps.this_run(ctx)
    order = {id(s): i for i, s in
             enumerate(sorted((s for s in spans if s.name == "step"),
                              key=lambda s: s.t0))}
    steps = sorted(((order[id(s.parent)], s) for s in spans
                    if s.name == "decode_step" and s.parent is not None
                    and id(s.parent) in order
                    and s.counters.get("live", 0) > 0),
                   key=lambda x: x[1].t0)
    gaps = [b.t0 - a.t1 for (i, a), (j, b) in zip(steps, steps[1:])
            if j == i + 1]
    t = _ps.mean(gaps)
    return None if t is None else 1e3 * t
