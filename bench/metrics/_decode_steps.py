"""Decode steps of the traced window: per ``DecodeWorker.decode_iteration``
span, the live slots' positions (harness record) and the device time of
the programs it ran (profiler trace)."""


def steps(ctx):
    if ctx.trace is None:
        return []
    where = ctx.trace.spans("decode")
    out = []
    for s in ctx.spans.of("decode"):
        iv = where.get(s.n)
        if iv is None:
            continue
        busy = ctx.trace.program_time_in(*iv)
        if busy > 0:
            out.append((s.extra["positions"], busy))
    return out
