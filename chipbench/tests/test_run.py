"""The harness end to end on the CPU: a smoke-size rehearsal of each cell,
the fault runs that must come out not correct, and the runs that must
fail without printing a result."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import run
from conftest import BENCH, ROOT, HERE

CELLS = [w["name"] for w in
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
SEED = 3_000_000_019          # beyond 32 signed bits


def pods(cell):
    """The pods of a cell's traffic (one chip each)."""
    return run.pods_of(run.load_cell(cell, rehearsal=True)[3])


def sync_interval(cell):
    """The host steps between a cell's delta exchanges (1: none)."""
    return run.load_cell(cell, rehearsal=True)[3]["sync_interval"]


def faults(cell):
    """The faults a cell can have, planted in the program: every cell's
    step can leave its state unchanged or drop half its batch; a cell's
    delta exchange can be left out, and a pod cell's can drop one pod's
    delta."""
    out = ["unchanged", "half_batch"]
    out += ["no_exchange"] if sync_interval(cell) > 1 else []
    return out + (["drop_pod"] if pods(cell) > 1 else [])


def harness(args, *, rehearsal=True, script=None, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if rehearsal:
        env["CHIPBENCH_REHEARSAL"] = "1"
    else:
        env.pop("CHIPBENCH_REHEARSAL", None)
    script = script or os.path.join(BENCH, "run.py")
    return subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=900)


def result_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cell_args(cell, seed=SEED):
    return ["--workload", cell, "--seed", str(seed), "--seconds", "1",
            "--trace", "0"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(cell):
    proc = harness(cell_args(cell))
    res = result_line(proc)
    assert res["correct"] is True, res["checks"]
    assert res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    # a CPU run names no device metric, memory among them, though the
    # step's memory plan is read as on the chip
    assert "metrics" not in res and "device" not in res
    assert "step_hbm_gib" not in proc.stdout
    assert "[memory] the step's plan per chip" in proc.stderr
    # the memory read lowers the dispatched steps again, on the pod mesh
    # too, and finds each in the compilation cache
    assert " compiled=0 " in proc.stderr
    assert list(res)[-1] == "checks"
    if pods(cell) > 1:
        # the fixed-point fold gives every pod the same bits
        assert res["checks"]["pod_spread"]["value"] == 0.0


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in faults(c)])
def test_broken_program_is_not_correct(cell, fault):
    proc = harness([fault] + cell_args(cell, SEED + 1),
                   script=os.path.join(HERE, "broken_run.py"))
    res = result_line(proc)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_no_accelerator_exits_without_result(cell):
    proc = harness(cell_args(cell), rehearsal=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_fewer_chips_than_the_cell_exits():
    fake = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(SystemExit) as e:
        run.check_devices(fake, 4, rehearsal=False)
    assert e.value.code != 0
    assert run.check_devices(fake, 1, rehearsal=False)


def test_checkout_of_the_benchmark_alone_exits_without_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = harness(cell_args(CELLS[0]), rehearsal=False,
                   script=str(tmp_path / "chipbench" / "run.py"),
                   cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
