"""Decode arena: mean live slots an arena step advanced (the ``live``
counter of the program's ``decode_step`` spans).  At a fixed arrival rate
fewer live slots means requests spend less time in the arena (Little's
law)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    return _ps.mean(s.counters["live"]
                    for s in _ps.this_run(ctx, "decode_step")
                    if "live" in s.counters)
