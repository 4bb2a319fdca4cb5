"""Device milliseconds per exchanging step of the gradient exchange: the
ops under the program's ``exchange`` named scope -- importance, pack,
encode, collective, decode, scatter -- the codec kernels included
(self time in the traced window, from ``scopes.py``)."""
import scopes


def read(ctx):
    ms = scopes.reading(ctx)
    return None if ms is None else ms["exchange"]
