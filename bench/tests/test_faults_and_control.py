"""The comparison that decides ``correct`` fails what it must.

Whole runs of the harness, rehearsed on the CPU at the reduced
configuration (the size a test run can hold): a sound run passes; the
lower-precision control (the reference in fp8, judged in the program's
place by ``--control fp8``) and every fault the cell can have
(``fault_run.py``) come out as not correct.  On the chip the same
comparison runs at the cell's own size; PERF.md gives those readings.
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "qwen3-4b.pd-cold"
RUN = ["--workload", CELL, "--seconds", "4", "--rates", "3", "--trace", "0",
       "--rehearse"]


def _run(argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable] + argv, cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("control", [False, True], ids=["sound", "fp8"])
def test_sound_runs_pass_and_the_fp8_control_fails(control):
    extra = ["--control", "fp8"] if control else []
    lines = _run([str(BENCH / "run.py"), *RUN, "--seeds", "31,32,33",
                  *extra])
    per_seed = [l for l in lines if "seed" in l]
    assert len(per_seed) == 3
    for line in per_seed:
        assert line["correct"] is not control, line["checked"]
        if control:
            # the program's own tokens, read in the same run, still pass
            limit = line["checked"]["max_logit_gap"]["limit"]
            assert line["control"]["program_max_logit_gap"] <= limit


@pytest.mark.parametrize("fault", ["token", "state", "handoff"])
def test_a_broken_timed_path_is_not_correct(fault):
    line = _run([str(BENCH / "tests" / "fault_run.py"), fault, *RUN,
                 "--seed", "41"])[-1]
    assert line["correct"] is False, line["checked"]
