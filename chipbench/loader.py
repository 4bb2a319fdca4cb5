"""Modules the harness finds by a name in its data, loaded by file path:
a configuration's family (``families/<family>.py``) and a per-layer
metric's reader (``metrics/<metric>.py``).  A later configuration or
metric then needs only files of its own."""
from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under the benchmark's directory,
    loaded once per process; a missing file is an error that names the
    path it looked for."""
    key = f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict):
    """The reference model and counts of a configuration's ``family``."""
    return load_module("families", cfg["family"])


def reader(name: str):
    """The ``read(ctx)`` of a per-layer metric."""
    return load_module("metrics", name).read
