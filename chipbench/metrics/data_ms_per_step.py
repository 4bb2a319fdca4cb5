"""Host milliseconds per host step spent in the token pipeline's
``next()`` during the window (benchmark span around the call)."""


def read(ctx):
    if not ctx["host_steps"]:
        return None
    t = ctx["spans"].total("data", ctx["t0"], ctx["t_end"])
    return 1e3 * t / ctx["host_steps"]
