"""Device milliseconds per host step of the step module's ops in no named
phase: what the forward, backward, optimizer and exchange metrics leave
out of the step (self time in the traced window, from ``scopes.py``)."""
import scopes


def read(ctx):
    ms = scopes.reading(ctx)
    return None if ms is None else ms["unscoped"]
