"""Compression: mean milliseconds of one pull of a prompt's KV to the host
(the program's ``kv_pull`` spans, inside ``extract_kv``)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    t = _ps.mean(s.seconds for s in _ps.this_run(ctx, "kv_pull"))
    return None if t is None else 1e3 * t
