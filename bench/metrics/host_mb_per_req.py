"""Compression: megabytes (1e6 B) that cross between host and device per
handed-off request: the KV pulled to the host (``kv_pull.bytes``) plus
the KV pushed into the arena (``inject.bytes``), over the ``kv_pull``
spans (program counters)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_program_spans", os.path.join(os.path.dirname(__file__),
                                        "_program_spans.py"))
_ps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ps)


def read(ctx):
    spans = _ps.this_run(ctx)
    pulls = [s for s in spans if s.name == "kv_pull"]
    if not pulls:
        return None
    moved = sum(s.counters.get("bytes", 0) for s in spans
                if s.name in ("kv_pull", "inject"))
    return moved / len(pulls) / 1e6
