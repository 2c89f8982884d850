"""Wire (modelled link): mean milliseconds per sampled request of waiting
for and crossing the link, ``wire_wait + comm`` of the program's
breakdown (cluster clock; the link is the program's BandwidthTrace)."""


def read(ctx):
    t = [r["breakdown"].get("wire_wait", 0.0) + r["breakdown"].get("comm", 0.0)
         for r in ctx.rows]
    return 1e3 * sum(t) / len(t) if t else None
