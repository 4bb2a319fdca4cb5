"""Host spans and counters of the training program.

Off by default.  Off, :func:`span` costs one module-level flag check and
returns a shared null context, and :func:`count` returns at once; nothing
is recorded.  On (:func:`enable`), each span records
``(name, start, end, parent)`` on ``time.perf_counter``'s clock and is
also a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a
profiler trace shows it on the host plane, in the device trace's clock.

    from repro import obs
    obs.enable()
    with obs.span("loop.data"):
        batch = next(pipeline)
    obs.count("step.compiles")
    obs.export()    # {"spans": [...], "counters": {...}}

The device side is named separately: the train step's ``jax.named_scope``
phases (``forward``, ``optimizer``, ``exchange`` and its sub-scopes) reach
the compiled HLO as ``op_name`` metadata.  Nothing here writes to disk.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

#: prefix of every span's profiler annotation
PREFIX = "repro."

_on = False
_NULL = contextlib.nullcontext()
_spans: list = []               # (name, start, end, parent)
_counters: dict = {}
_lock = threading.Lock()
_open = threading.local()       # per thread: the stack of open span names


class _Span:
    __slots__ = ("name", "parent", "start", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        _open.stack.pop()
        _spans.append((self.name, self.start, end, self.parent))
        return False


def span(name: str):
    """A context manager timing ``name``; the shared null context while
    off."""
    if not _on:
        return _NULL
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``; nothing while off."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def export() -> dict:
    """What was recorded: ``{"spans": [{"name", "start", "end",
    "parent"}], "counters": {name: n}}``; times are ``perf_counter``
    seconds."""
    with _lock:
        counters = dict(_counters)
    spans = [{"name": n, "start": s, "end": e, "parent": p}
             for n, s, e, p in list(_spans)]
    return {"spans": spans, "counters": counters}


def reset() -> None:
    """Forget every span and counter recorded so far."""
    with _lock:
        _spans.clear()
        _counters.clear()
