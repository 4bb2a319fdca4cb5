"""Device milliseconds per host step of the train step's forward phase:
the step module's ops under the program's ``forward`` named scope
(self time in the traced window, from ``scopes.py``)."""
import scopes


def read(ctx):
    ms = scopes.reading(ctx)
    return None if ms is None else ms["forward"]
