"""One rehearsal run of the harness with the program's timed path broken
underneath, for the fault tests:

    CHIPBENCH_REHEARSAL=1 python3 broken_run.py <fault> <run.py arguments>

The fault is planted in the program once the cell's session is built,
before its first step:

* ``unchanged``: every step returns the state it was given;
* ``half_batch``: the loss is taken over the first half of the rows,
  its mean over those.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def plant(fault, cell):
    import jax
    import jax.numpy as jnp
    trainer = cell.loop.trainer
    if fault == "unchanged":
        inner = trainer.step

        def step(state, batch, plan, kind="grad_sync"):
            keep = jax.tree.map(jnp.copy, state)
            _, metrics = inner(state, batch, plan, kind)
            return keep, metrics
        trainer.step = step
    elif fault == "half_batch":
        model = trainer.model
        loss = model.loss

        def half_loss(params, batch):
            return loss(params, {k: v[:v.shape[0] // 2]
                                 for k, v in batch.items()})
        model.loss = half_loss
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main():
    fault, argv = sys.argv[1], sys.argv[2:]
    first_steps = run.Cell.first_steps

    def broken_first_steps(self, n):
        plant(fault, self)
        return first_steps(self, n)
    run.Cell.first_steps = broken_first_steps
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
