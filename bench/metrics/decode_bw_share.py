"""Decode arena: bytes a decode step needs (every weight once, the live
slots' cached rows and the new ones) over the device time of the
programs the step ran, as a share of the chip's HBM bandwidth, in percent."""
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
    nbytes = sum(ctx.counts.decode_bytes(ctx.model, pos) for pos, _ in st)
    busy = sum(b for _, b in st)
    return 100.0 * nbytes / (busy * ctx.peak["hbm_bytes_per_s"])
