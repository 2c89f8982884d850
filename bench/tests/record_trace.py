#!/usr/bin/env python3
"""Record the small profiler trace that ``test_devtrace.py`` reduces.

    python3 bench/tests/record_trace.py OUT_DIR

Three matrix products of different sizes inside harness-style spans
(``bench.step#<n>``), separated by host sleeps, all inside a
``bench.window`` span, so the trace has device operations, idle gaps
inside and outside spans, and a window to clip to.  Run once on the chip;
the ``.xplane.pb`` it writes is committed under ``bench/tests/data``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: (x @ x).sum())
    xs = [jnp.ones((n, n), jnp.bfloat16) for n in (1024, 2048, 4096)]
    for x in xs:
        f(x).block_until_ready()             # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(0.02)
        for i, x in enumerate(xs):
            with jax.profiler.TraceAnnotation(f"bench.step#{i}"):
                f(x).block_until_ready()
                time.sleep(0.01)
            time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
