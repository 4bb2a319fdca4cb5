"""Host milliseconds per host step spent inside ``Trainer.step`` calls
during the window: tracing-cache lookup, plan lowering and dispatch
(benchmark span around the call)."""


def read(ctx):
    if not ctx["host_steps"]:
        return None
    t = ctx["spans"].total("dispatch", ctx["t0"], ctx["t_end"])
    return 1e3 * t / ctx["host_steps"]
