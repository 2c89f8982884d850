"""Decode arena: model FLOPs of the live slots per decode step over the
device time of the programs the step ran, as a share of the chip's bf16
peak, in percent, over all steps of the traced window."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_decode_steps", os.path.join(os.path.dirname(__file__),
                                       "_decode_steps.py"))
_steps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_steps)


def read(ctx):
    st = _steps.steps(ctx)
    if not st:
        return None
    flops = sum(ctx.counts.decode_flops(ctx.model, pos) for pos, _ in st)
    busy = sum(b for _, b in st)
    return 100.0 * flops / (busy * ctx.peak["bf16_flops_per_s"])
