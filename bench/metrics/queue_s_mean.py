"""Scheduler: mean seconds a sampled request waited in the admission queue
(the program's own ``breakdown["queue"]``, cluster clock)."""


def read(ctx):
    q = [r["breakdown"].get("queue", 0.0) for r in ctx.rows]
    return sum(q) / len(q) if q else None
