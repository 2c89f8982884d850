"""The harness's own spans around calls into each layer of the program.

The program is not edited: each entry of ``SPANS`` names a module, a class
(or None for a module function) and a method, and :func:`install` wraps it
so that every call is timed on the host clock and, when the profiler runs,
shows in its trace as ``bench.<span>#<n>`` (``jax.profiler.TraceAnnotation``,
on the same clock as the device's operations).  A span records the request
it serves when one is in flight: the start-of-life methods set it.
"""
from __future__ import annotations

import functools
import importlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

# span name -> (module, class or None, attribute)
SPANS = {
    "admit": ("repro.serving.cluster", "ClusterRuntime", "_admit_and_start"),
    "start_pd": ("repro.serving.cluster", "ClusterRuntime",
                 "_start_request_pd"),
    "prefill": ("repro.serving.workers", "PrefillWorker", "prefill"),
    "encode": ("repro.serving.workers", "PrefillWorker",
               "select_and_compress"),
    "decode_kv": ("repro.serving.cluster", None, "decompress_kvs"),
    "inject": ("repro.serving.workers", "DecodeWorker", "inject_restored"),
    "slot_copy": ("repro.serving.workers", "DecodeWorker",
                  "copy_from_caches"),
    "decode": ("repro.serving.workers", "DecodeWorker", "decode_iteration"),
    "finish": ("repro.serving.cluster", "ClusterRuntime", "_finish"),
}
# Spans that start a request's life: their second argument is the Request.
STARTS = ("start_pd",)


@dataclass
class Span:
    name: str
    n: int
    t0: float
    t1: float
    rid: Optional[int]
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Recorder:
    """Spans in memory; ``on`` gates recording (the window only)."""

    def __init__(self):
        self.spans: List[Span] = []
        self.on = False
        self.current_rid: Optional[int] = None
        self._count: Dict[str, int] = {}
        self._undo = []

    def _wrap(self, name: str, fn):
        rec = self

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not rec.on:
                return fn(*args, **kwargs)
            n = rec._count.get(name, 0)
            rec._count[name] = n + 1
            outer = rec.current_rid
            extra = {}
            if name in STARTS:
                rec.current_rid = args[1].rid
            elif name == "decode":
                dw, active = args[0], args[1]
                extra["positions"] = [int(dw._positions[s.idx])
                                      for s in active]
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(f"bench.{name}#{n}"):
                    return fn(*args, **kwargs)
            finally:
                t1 = time.perf_counter()
                rec.spans.append(Span(name, n, t0, t1, rec.current_rid,
                                      extra))
                rec.current_rid = outer if name in STARTS else rec.current_rid
        return wrapper

    def install(self) -> "Recorder":
        for name, (mod, cls, attr) in SPANS.items():
            owner = importlib.import_module(mod)
            if cls is not None:
                owner = getattr(owner, cls)
            orig = getattr(owner, attr)
            setattr(owner, attr, self._wrap(name, orig))
            self._undo.append((owner, attr, orig))
        return self

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()

    def of(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def per_request(self, names) -> Dict[int, float]:
        """Seconds per request summed over spans of ``names``."""
        out: Dict[int, float] = {}
        for s in self.spans:
            if s.name in names and s.rid is not None:
                out[s.rid] = out.get(s.rid, 0.0) + s.seconds
        return out
