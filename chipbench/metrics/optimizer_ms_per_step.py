"""Device milliseconds per host step of the train step's optimizer phase:
clipping, AdamW and the rung-ordered apply, under the program's
``optimizer`` named scope (self time in the traced window, from
``scopes.py``)."""
import scopes


def read(ctx):
    ms = scopes.reading(ctx)
    return None if ms is None else ms["optimizer"]
