"""Device milliseconds per exchanging step of the step modules'
collective ops (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all: the exchange between chips), self time
averaged over the chips, from the trace (``scopes.py``).  Nothing where
the window ran none."""
import scopes


def read(ctx):
    ms = scopes.reading(ctx)
    if ms is None or not ms["collective"]:
        return None
    return ms["collective"]
