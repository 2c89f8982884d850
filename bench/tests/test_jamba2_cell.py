"""The comparison that decides ``correct`` fails what it must in the hybrid
cell: whole rehearsed runs of ``jamba2-3b.pd-state-2k`` on the CPU at the
reduced configuration (14 layers of width 512, 2048-token prompts).  Under
``--control fp8`` the program's own tokens pass and the control's fail;
each planted fault of ``fault_run.py`` (a token, the arena's state, the
hand-off) comes out as not correct.  One short window each."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "jamba2-3b.pd-state-2k"
RUN = ["--workload", CELL, "--seconds", "4", "--rates", "3", "--trace", "0",
       "--rehearse"]


def _run(argv, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable] + argv, cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


def test_the_program_passes_and_the_fp8_control_fails():
    lines = _run([str(BENCH / "run.py"), *RUN, "--seeds", "31,32",
                  "--control", "fp8"])
    per_seed = [l for l in lines if "seed" in l]
    assert len(per_seed) == 2
    for line in per_seed:
        limit = line["checked"]["max_logit_gap"]["limit"]
        assert line["correct"] is False, line["checked"]
        assert line["control"]["program_max_logit_gap"] <= limit


@pytest.mark.parametrize("fault", ["token", "state", "handoff"])
def test_a_broken_timed_path_is_not_correct(fault):
    line = _run([str(BENCH / "tests" / "fault_run.py"), fault, *RUN,
                 "--seed", "41"])[-1]
    assert line["correct"] is False, line["checked"]
