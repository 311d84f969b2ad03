"""Find a benchmark file by its name: ``<root>/<kind>/<name>.py`` for code
(``models``, ``generators``, ``layer_metrics``), with no list in code, so
that a later change adds a file and an entry and edits nothing here."""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind: str, name: str, root: str = HERE):
    """Import ``<root>/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"_bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
