#!/usr/bin/env python3
"""Record the small profiler trace that ``test_program_spans.py`` reads.

    python3 bench/tests/record_program_trace.py OUT_DIR

Inside a ``bench.window`` span, three harness-style ``bench.step#<n>``
spans (timed on ``perf_counter`` as ``bench/spans.py`` times them) each
hold one program-style iteration made of ``repro.serving.tracing`` spans
around device work: a ``step`` span holding a ``kv_pull`` (a device array
pulled to the host), an ``inject`` (a host array pushed to the device) and
a ``decode_step`` (a matrix product).  Host sleeps leave the device idle
inside program spans, inside harness spans but outside the program's, and
outside both.  Run once on the chip: it writes the ``.xplane.pb`` and
``program_fixture.json`` (both clocks of every span) under OUT_DIR; both are
committed under ``bench/tests/data``.
"""
import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
from repro.serving import tracing  # noqa: E402


def main(out: str) -> None:
    f = jax.jit(lambda x: (x @ x).sum())
    xs = [jnp.ones((n, n), jnp.bfloat16) for n in (1024, 2048, 4096)]
    for x in xs:
        f(x).block_until_ready()             # compile outside the trace
    kv = jnp.ones((1024, 1024), jnp.bfloat16)
    host = np.ones((1024, 1024), np.float32)
    dev = jax.devices()[0]
    jax.device_put(host, dev).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tracing.SPANS.clear()
    harness = []
    trace_dir = os.path.join(out, "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.02)
        for i, x in enumerate(xs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench.step#{i}"):
                with tracing.span("step"):
                    with tracing.span("kv_pull", rid=i) as sp:
                        pulled = np.asarray(kv, np.float32)
                        sp.add(bytes=pulled.nbytes, transfers=1)
                    with tracing.span("inject", rid=i) as sp:
                        jax.device_put(host, dev).block_until_ready()
                        sp.add(bytes=host.nbytes)
                    time.sleep(0.005)
                    with tracing.timed("decode_step") as sp:
                        f(x).block_until_ready()
                        sp.add(live=i + 1)
                    time.sleep(0.005)
                time.sleep(0.01)
            harness.append({"name": "step", "n": i, "t0": t0,
                            "t1": time.perf_counter()})
            time.sleep(0.01)
    jax.profiler.stop_trace()
    spans = list(tracing.SPANS)
    index = {id(s): j for j, s in enumerate(spans)}
    program = [{"name": s.name, "t0": s.t0, "t1": s.t1, "rid": s.rid,
                "parent": index.get(id(s.parent)), "counters": s.counters}
               for s in spans]
    (found,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)
    shutil.copy(found, os.path.join(out, "program_fixture.xplane.pb"))
    with open(os.path.join(out, "program_fixture.json"), "w") as fh:
        json.dump({"harness": harness, "program": program}, fh, indent=1)


if __name__ == "__main__":
    main(sys.argv[1])
