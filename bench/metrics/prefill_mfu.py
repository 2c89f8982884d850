"""Prefill worker: model FLOPs of one prefill at the cell's prompt length
over the device time of the programs the prefill span ran (profiler
trace), as a share of the chip's bf16 peak, in percent."""


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.spans("prefill")
    busy = [ctx.trace.program_time_in(s, e) for s, e in spans.values()]
    busy = [b for b in busy if b > 0]
    if not busy:
        return None
    flops = ctx.counts.prefill_flops(ctx.model, int(ctx.mix["prompt_tokens"]))
    return 100.0 * flops * len(busy) / (sum(busy)
                                        * ctx.peak["bf16_flops_per_s"])
