"""The readings a rehearsal compares, printed as one JSON line:

    CHIPBENCH_REHEARSAL=1 python3 readings_child.py <cell> <seed>

``program``: the first steps through the window's own call (losses,
pre-clip gradient norms, the first gradient and the change per leaf);
``reference``: the plain reference's over the same rows and weights."""
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main():
    name, seed = sys.argv[1], int(sys.argv[2])
    cell, _, cfg, traffic = run.load_cell(name, rehearsal=True)
    devices, _ = run.open_devices(cell["chips"], rehearsal=True)
    n = traffic["check_steps"]
    bench_cell = run.Cell(cell, cfg, traffic, seed, devices, trace=False)
    prog = bench_cell.first_steps(n)
    rungs, omega = bench_cell.plan_info()
    bench_cell.completions.close()
    del bench_cell
    gc.collect()
    ref = run.reference_readings(cfg, traffic, run.data_seed(seed),
                                 run.weight_key(seed), rungs, omega, n,
                                 devices)
    print(json.dumps({"cell": name, "seed": seed, "program": prog,
                      "reference": ref}), flush=True)


if __name__ == "__main__":
    main()
