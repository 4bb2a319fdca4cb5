"""The control and the planted faults come out not correct, and the
program correct, by the cell's limits: ``control.py`` at the rehearsal
sizes on the CPU, on seeds that set no limit."""
import json
import os
import subprocess
import sys

import pytest

import run
from conftest import BENCH, ROOT
from test_run import CELLS, faults as program_faults

SEEDS = "4000000001,4000000002"


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_fail_the_limits(cell):
    # the reference can plant every fault of the program's but a state
    # left unchanged
    faults = [f for f in program_faults(cell) if f != "unchanged"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", CHIPBENCH_REHEARSAL="1")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--workload",
         cell, "--seeds", SEEDS, "--faults", ",".join(faults)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # the readings of the first steps; the window's plan is the run's,
    # and the pods' spread the program's own
    limits = {k: v for k, v in run.cell_limits(cell, rehearsal=True).items()
              if k != "plan_swaps"}
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert len(lines) == 2
    for rec in lines:
        assert run.judge(rec["program"], limits), rec["program"]
        for bad in ["control"] + faults:
            held = {k: v for k, v in limits.items() if k in rec[bad]}
            assert not run.judge(rec[bad], held), (bad, rec[bad])
