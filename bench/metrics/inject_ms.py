"""Decode arena: mean milliseconds of one write of a request's KV into
its arena slot (the program's ``inject`` spans: the restored KV pushed
from the host, or the prefill cache copied on the device)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    t = _ps.mean(s.seconds for s in _ps.this_run(ctx, "inject"))
    return None if t is None else 1e3 * t
