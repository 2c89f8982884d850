"""The program's own spans (``repro.serving.tracing``) of this run, and a
map of their times onto the profiler trace's clock.

The program keeps its spans while a profiler session records, in a list
that outlives a run, so a reader keeps those that overlap this run's
harness spans (``ctx.spans``: ``--seeds`` runs share a process).  A
program without that module has no spans, and every reader of them
returns None.

Program spans are timed on ``time.perf_counter``; the trace counts host
time from the start of its session.  The harness's spans hold both: their
``perf_counter`` ends in ``ctx.spans`` and their ``bench.<name>#<n>``
events in the trace.  A straight line fitted through those pairs maps a
program time onto the trace.
"""
from typing import Callable, List, Optional


def this_run(ctx, name: Optional[str] = None) -> List:
    try:
        from repro.serving import tracing
    except ImportError:
        return []
    own = ctx.spans.spans
    if not own:
        return []
    lo = min(s.t0 for s in own)
    hi = max(s.t1 for s in own)
    return [s for s in tracing.SPANS if s.t1 >= lo and s.t0 <= hi
            and (name is None or s.name == name)]


def mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def to_trace(ctx) -> Optional[Callable[[float], float]]:
    """``perf_counter`` seconds -> trace seconds (least squares through
    the harness spans' starts and ends), or None where no harness span
    was traced."""
    if ctx.trace is None:
        return None
    traced = {}
    xs, ys = [], []
    for s in ctx.spans.spans:
        if s.name not in traced:
            traced[s.name] = ctx.trace.spans(s.name)
        iv = traced[s.name].get(s.n)
        if iv is not None:
            xs += [s.t0, s.t1]
            ys += list(iv)
    if len(xs) < 2:
        return None
    mx, my = sum(xs) / len(xs), sum(ys) / len(ys)
    sxx = sum((x - mx) ** 2 for x in xs)
    slope = (sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / sxx
             if sxx > 0 else 1.0)
    return lambda t: my + slope * (t - mx)
