#!/usr/bin/env python3
"""The readings that the limits of a cell's ``correct`` are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        [--faults half_batch,drop_pod] [--control-seeds 2] \\
        [--out <file.jsonl>]

For each seed, at the cell's own sizes and in one process: the program's
first steps through the window's own call, compared with the plain
reference (the lower readings); the control -- the reference put in the
program's place with every matmul operand rounded to float8, the precision
below the configuration's bfloat16 -- compared with the reference; and
each named fault planted in the reference (``reference.Reference``'s
``fault``), compared with the reference; the control and the faults on
the first ``--control-seeds`` seeds only (all by default).  One JSON line
per seed, on standard output and appended to ``--out``.  The benchmark's
own runs do not run this; ``CHIPBENCH_REHEARSAL=1`` runs it on the CPU at the
rehearsal sizes, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import run  # noqa: E402


def readings(cell, cfg, traffic, seed, devices, faults, control=True):
    """The compared numbers of the program, the control and each fault
    against the reference, for one seed."""
    import reference
    n_check = traffic["check_steps"]
    bench_cell = run.Cell(cell, cfg, traffic, seed, devices, trace=False)
    prog = bench_cell.first_steps(n_check)
    rungs, omega = bench_cell.plan_info()
    bench_cell.completions.close()
    del bench_cell
    gc.collect()

    def ref_run(**kw):
        return run.reference_readings(
            cfg, traffic, run.data_seed(seed), run.weight_key(seed), rungs,
            omega, n_check, devices, **kw)

    ref = ref_run()
    rec = {"seed": seed, "rungs": rungs,
           "program": dict(run.compare(prog, ref),
                           pod_spread=prog["pod_spread"])}
    if not control:
        return rec
    rec["control"] = run.compare(ref_run(rnd=reference.fp8_round), ref)
    for fault in faults:
        rec[fault] = run.compare(ref_run(fault=fault), ref)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--faults", default="",
                    help="comma-separated faults planted in the reference")
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control and faults on this many of the "
                         "seeds, the first ones")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]
    rehearsal = os.environ.get(run.REHEARSAL_ENV) == "1"
    cell, _, cfg, traffic = run.load_cell(args.workload, rehearsal)
    devices, _ = run.open_devices(cell["chips"], rehearsal)
    n_control = len(seeds) if args.control_seeds is None \
        else args.control_seeds
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        rec = readings(cell, cfg, traffic, seed, devices, faults,
                       control=i < n_control)
        rec["workload"] = args.workload
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
