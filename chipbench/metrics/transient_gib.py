"""GiB per chip that the step executable whose peak is ``step_hbm_gib``
holds above its arguments at that peak (``peak_memory_in_bytes -
argument_size_in_bytes``): the outputs not written over the donated state
and the temporaries -- activations, gradients, the exchange's buffers --
as the compiler plans them.  With ``state_gib`` it adds up to
``step_hbm_gib``."""


def read(ctx):
    mem = ctx.get("step_memory")
    if mem is None:
        return None
    return (mem["peak_bytes"] - mem["argument_bytes"]) / 2 ** 30
