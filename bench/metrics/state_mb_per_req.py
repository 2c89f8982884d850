"""Compression: megabytes (1e6 B) of recurrent state that each hand-off
pulls from the device: the mean of the program's ``kv_pull.state_bytes``
counter over the run's pulls.  A program that counts no state (an
attention-only model, or one that hands no state over) reads None."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    pulls = _ps.this_run(ctx, "kv_pull")
    if not any("state_bytes" in s.counters for s in pulls):
        return None
    return _ps.mean(s.counters.get("state_bytes", 0) for s in pulls) / 1e6
