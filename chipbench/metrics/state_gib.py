"""GiB per chip of the step executable's arguments -- the train state,
the batch and the plan -- as the compiler plans them
(``argument_size_in_bytes`` of the executable whose peak is
``step_hbm_gib``): the part of the step's memory that lives on from step
to step."""


def read(ctx):
    mem = ctx.get("step_memory")
    return None if mem is None else mem["argument_bytes"] / 2 ** 30
