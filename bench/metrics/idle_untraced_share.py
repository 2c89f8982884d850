"""Device: share of the traced window's device-idle time during which no
span of the program was open, percent (device idle from the profiler
trace, program spans mapped onto its clock through the harness's
spans).  What the program's spans do not cover, they cannot explain."""
import importlib.util
import os

from devtrace import covered, union

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    spans = _ps.this_run(ctx)
    to_trace = _ps.to_trace(ctx)
    tr = ctx.trace
    if not spans or to_trace is None or not tr.busy:
        return None
    w0, w1 = tr.window
    edges = [w0] + [t for iv in tr.busy[sorted(tr.busy)[0]] for t in iv] \
        + [w1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    total = sum(b - a for a, b in idle)
    if total <= 0:
        return None
    open_ = union([(to_trace(s.t0), to_trace(s.t1)) for s in spans])
    untraced = sum((b - a) - covered(open_, a, b) for a, b in idle)
    return 100.0 * untraced / total
