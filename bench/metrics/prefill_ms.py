"""Prefill worker: mean milliseconds of one ``PrefillWorker.prefill`` call
(harness span, host clock; the call waits for its logits)."""


def read(ctx):
    s = ctx.spans.of("prefill")
    return 1e3 * sum(x.seconds for x in s) / len(s) if s else None
