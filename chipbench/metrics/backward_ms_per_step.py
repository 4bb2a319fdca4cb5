"""Device milliseconds per host step of the train step's backward phase:
the ops of ``transpose(jvp(forward))``, the remat recompute included
(self time in the traced window, from ``scopes.py``)."""
import scopes


def read(ctx):
    ms = scopes.reading(ctx)
    return None if ms is None else ms["backward"]
