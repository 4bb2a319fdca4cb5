#!/usr/bin/env python
"""Crash-isolated dry-run sweep: one subprocess per cell (an XLA CHECK
abort then costs one cell, not the sweep). Resumable: cells with an OK
JSON in the results dir are skipped."""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "benchmarks", "results")
os.makedirs(OUT, exist_ok=True)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import cells  # noqa: E402
from repro.strategies import list_strategies  # noqa: E402


def done_ok(mesh, arch, shape, strategy):
    f = os.path.join(OUT, f"{mesh}_{arch}_{shape}_{strategy}.json")
    if not os.path.exists(f):
        return False
    try:
        return json.load(open(f)).get("ok", False)
    except Exception:
        return False


def run(arch, shape, multi_pod, strategy="acesync", timeout=900):
    mesh = "2x16x16" if multi_pod else "16x16"
    if done_ok(mesh, arch, shape, strategy):
        print(f"skip {mesh} {arch} {shape} {strategy} (done)", flush=True)
        return True
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--strategy", strategy, "--out", OUT]
    if multi_pod:
        cmd.append("--multi-pod")
    # the dry-run is a CPU tool (512 virtual host devices): pin the CPU
    # backend so no child tries to take an attached chip
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    t0 = time.time()
    try:
        r = subprocess.run(cmd, cwd=ROOT, env=env, timeout=timeout,
                           capture_output=True, text=True)
        tail = (r.stdout or "").strip().splitlines()
        print("\n".join(tail[-2:]) if tail else f"rc={r.returncode}",
              flush=True)
        if r.returncode != 0:
            f = os.path.join(OUT, f"{mesh}_{arch}_{shape}_{strategy}.json")
            if not os.path.exists(f):
                json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                           "strategy": strategy, "ok": False,
                           "error": f"subprocess rc={r.returncode}",
                           "stderr_tail": (r.stderr or "")[-2000:]},
                          open(f, "w"), indent=1)
        return r.returncode == 0
    except subprocess.TimeoutExpired:
        print(f"TIMEOUT {mesh} {arch} {shape}", flush=True)
        return False


def main():
    todo = []
    for arch, shape in cells():
        todo.append((arch, shape, True, "acesync"))
    for arch, shape in cells():
        todo.append((arch, shape, False, "acesync"))
    # strategy comparison (HLO-level Table 1 evidence): every registered
    # strategy on the paper arch, the paper's four on qwen3-8b
    for s in list_strategies():
        if s != "acesync":
            todo.append(("paper-350m", "train_4k", True, s))
    for s in ("fullsync", "topk", "fedavg"):
        todo.append(("qwen3-8b", "train_4k", True, s))
    todo.append(("paper-350m", "train_4k", True, "acesync"))
    todo.append(("paper-350m", "train_4k", False, "acesync"))

    t0 = time.time()
    fails = 0
    for i, (arch, shape, mp, strat) in enumerate(todo):
        print(f"--- [{i+1}/{len(todo)}] {arch} {shape} "
              f"{'multi' if mp else 'single'} {strat} "
              f"(t={time.time()-t0:.0f}s)", flush=True)
        if not run(arch, shape, mp, strat):
            fails += 1
    print(f"SWEEP DONE fails={fails} t={time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
