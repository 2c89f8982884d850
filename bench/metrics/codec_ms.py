"""Compression: mean milliseconds per request of codec work on the host,
compress (with the KV pulled off the device) plus restore (harness
spans, host clock)."""


def read(ctx):
    per = ctx.spans.per_request(("encode", "decode_kv"))
    return 1e3 * sum(per.values()) / len(per) if per else None
