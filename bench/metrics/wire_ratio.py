"""Compression: uncompressed KV bytes over the bytes the sampled requests'
payloads take on the wire (program counters)."""


def read(ctx):
    wire = sum(r["wire_bytes"] for r in ctx.rows)
    return sum(r["kv_bytes"] for r in ctx.rows) / wire if wire else None
